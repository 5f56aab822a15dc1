#!/usr/bin/env python
"""Gate BENCH_serve.json's load-invariant metrics against committed baselines.

Wall-clock numbers (tok/s, latency ms) swing +-20% with CI machine load and
are deliberately NOT checked here. What this gates are the *structural*
serving claims that hold on any machine:

  * dispatches per scheduler tick == 1.00 (the unified serve_step contract);
  * tokens advanced per device dispatch (work-per-call packing efficiency);
  * peak forked pages vs single-sample (the COW fork HBM claim);
  * multi-prefill queued-request TTFT tick percentiles (head-of-line fix).

Rules live in ``scripts/bench_baselines.json``, keyed by dotted path into
BENCH_serve.json (list indices are numeric segments). Each rule is any
combination of:

  ``expect`` + ``abs`` and/or ``rel``  -- |value - expect| <= abs (or
                                          rel * |expect|); with neither
                                          tolerance the match must be exact
  ``min`` / ``max``                    -- inclusive bounds

A missing path fails (a metric silently vanishing from the benchmark is
itself a regression). So do the silent-hole cases: a rule with no
``expect``/``min``/``max`` constraint at all (vacuous — it gates
nothing), a rule with an unknown field (``expectt: 1.0`` would otherwise
be ignored forever), and a path resolving to a non-numeric value the
comparisons can't apply to. Exit status 0 iff every rule passes.

Usage:
    python scripts/check_bench.py [--bench BENCH_serve.json]
                                  [--baselines scripts/bench_baselines.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lookup(obj, path: str):
    """Resolve a dotted path; numeric segments index into lists."""
    cur = obj
    for seg in path.split("."):
        if isinstance(cur, list):
            cur = cur[int(seg)]
        elif isinstance(cur, dict):
            if seg not in cur:
                raise KeyError(path)
            cur = cur[seg]
        else:
            raise KeyError(path)
    return cur


KNOWN_FIELDS = {"expect", "abs", "rel", "min", "max", "why"}
CONSTRAINT_FIELDS = {"expect", "min", "max"}


def validate_rule(rule: dict):
    """Structural failures that make a rule a gate that never gates."""
    fails = []
    unknown = sorted(set(rule) - KNOWN_FIELDS)
    if unknown:
        fails.append(f"unknown field(s) {', '.join(unknown)} "
                     f"(typo? known: {', '.join(sorted(KNOWN_FIELDS))})")
    if not set(rule) & CONSTRAINT_FIELDS:
        fails.append("no expect/min/max constraint: rule is vacuous")
    return fails


def check_rule(value, rule: dict):
    """Return a list of failure strings (empty == pass)."""
    fails = []
    if not isinstance(value, (int, float)) or isinstance(value, bool):
        return [f"got non-numeric value {value!r} "
                f"({type(value).__name__}); cannot gate"]
    if "expect" in rule:
        want = rule["expect"]
        tol = max(abs(rule.get("abs", 0.0)),
                  abs(rule.get("rel", 0.0)) * abs(want))
        if abs(value - want) > tol:
            fails.append(f"got {value!r}, want {want!r} (+-{tol:g})")
    if "min" in rule and value < rule["min"]:
        fails.append(f"got {value!r}, below min {rule['min']!r}")
    if "max" in rule and value > rule["max"]:
        fails.append(f"got {value!r}, above max {rule['max']!r}")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench",
                    default=os.path.join(REPO, "BENCH_serve.json"))
    ap.add_argument("--baselines",
                    default=os.path.join(REPO, "scripts",
                                         "bench_baselines.json"))
    args = ap.parse_args(argv)

    with open(args.bench) as f:
        bench = json.load(f)
    with open(args.baselines) as f:
        baselines = json.load(f)

    rules = baselines["rules"]
    failures = 0
    for path in sorted(rules):
        rule = rules[path]
        fails = validate_rule(rule)
        if fails:
            for msg in fails:
                print(f"FAIL {path}: {msg}")
            failures += 1
            continue
        try:
            value = lookup(bench, path)
        except (KeyError, IndexError, ValueError):
            print(f"FAIL {path}: missing from {os.path.basename(args.bench)}"
                  " (stale gate: the rule's key path no longer resolves)")
            failures += 1
            continue
        fails = check_rule(value, rule)
        if fails:
            why = rule.get("why", "")
            for msg in fails:
                print(f"FAIL {path}: {msg}" + (f"  [{why}]" if why else ""))
            failures += 1
        else:
            print(f"ok   {path} = {value!r}")

    if failures:
        print(f"\n{failures}/{len(rules)} baseline rule(s) failed. If the "
              "change is intentional, refresh BENCH_serve.json (PYTHONPATH="
              "src python -m benchmarks.multitask_throughput) and update "
              f"{os.path.relpath(args.baselines, REPO)} in the same commit, "
              "explaining the shift in the PR.")
        return 1
    print(f"\nall {len(rules)} baseline rules pass")
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# placeholder CPU devices only: neither this process nor its --all
# children (which inherit the environment) may claim an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices host the production meshes; every step function is lowered
against ShapeDtypeStruct specs (no allocation) and compiled through GSPMD.
``memory_analysis()`` proves residency, ``cost_analysis()`` + HLO collective
parsing feed the roofline (EXPERIMENTS.md §Roofline).

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
    python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all [--multi-pod] [--jobs 4]

Single-cell runs write JSON into --out-dir (default results/dryrun).
"""
import argparse
import json
import subprocess
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def depth_variant(cfg, mult: int):
    """Reduced-depth config: `mult` expanded-pattern repeats (+ remainder).

    FLOPs/bytes/collectives are affine in the repeat count, so compiling
    mult=1 and mult=2 lets the full-depth cost be extrapolated exactly —
    sidestepping XLA cost analysis's count-loop-bodies-once behavior without
    paying a full-depth unrolled compile.
    """
    import math as _m
    u_b = len(cfg.pattern_unit)
    m = len(cfg.pattern_remainder)
    interleave = cfg.moe.interleave if cfg.moe else 1
    u_exp = _m.lcm(u_b, interleave)
    r_b = mult * (u_exp // u_b)
    return cfg.replace(num_layers=r_b * u_b + m, pattern_repeats=r_b)


def expanded_repeats(cfg) -> int:
    import math as _m
    u_b = len(cfg.pattern_unit)
    interleave = cfg.moe.interleave if cfg.moe else 1
    u_exp = _m.lcm(u_b, interleave)
    return (cfg.pattern_repeats * u_b) // u_exp


def build_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               sequence_parallel: bool = True, peft_method: str = "auto",
               aot_rank: int = 64, loss_chunk: int = 512,
               chunk_q: int = 2048, chunk_kv: int = 0,
               scan_layers: bool = True, cfg_override=None,
               remat_save=(), remat_policy: str = "",
               decode_cache_seq: bool = False):
    """Returns (fn, args, mesh, rules, model, meta). fn(*args) is lower-ready."""
    from repro import configs
    from repro.core import aot as aot_mod
    from repro.core import peft as peft_mod
    from repro.distrib import sharding as shlib
    from repro.launch import specs as sp
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import Model, ModelOptions
    from repro.train.step import TrainConfig, make_train_step

    cfg = cfg_override if cfg_override is not None else configs.get(arch)
    shape = cfg.shape(shape_name)
    reason = cfg.shape_skip_reason(shape_name)
    if reason:
        return None, None, None, None, None, {
            "skipped": reason, "arch": arch, "shape": shape_name,
            "multi_pod": multi_pod}

    mesh = make_production_mesh(multi_pod=multi_pod)
    if shape.kind == "decode" and shape.global_batch == 1:
        rules = shlib.long_context_rules(pod_axis=multi_pod)
    elif shape.kind == "decode" and decode_cache_seq:
        rules = shlib.decode_rules(kv_heads=cfg.num_kv_heads,
                                   pod_axis=multi_pod)
    else:
        rules = shlib.tp_dp_rules(pod_axis=multi_pod,
                                  sequence_parallel=(sequence_parallel and
                                                     shape.kind != "decode"))
    # chunk_kv defaults to the full kv span so each q-chunk is one einsum —
    # no inner lax.scan, so cost_analysis counts every FLOP (XLA's analysis
    # costs while-loop bodies once, not x trip-count). Layers are unrolled
    # (scan_layers=False) for the same reason; remat still bounds memory.
    opts = ModelOptions(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                        attn_impl="chunked", chunk_q=chunk_q,
                        chunk_kv=chunk_kv or shape.seq_len,
                        remat=True, remat_save_names=tuple(remat_save),
                        remat_policy_name=remat_policy,
                        scan_layers=scan_layers,
                        mlstm_chunk=1024, unroll_scans=True)
    model = Model(cfg, opts)

    if peft_method == "auto":
        peft_method = "aot" if cfg.aot_applicable else "bitfit"

    params = sp.param_specs(model, mesh, rules)
    meta = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
            "peft_method": peft_method, "kind": shape.kind,
            "mesh": list(mesh.devices.shape), "n_chips": mesh.devices.size}

    if shape.kind == "train":
        popt = peft_mod.PEFTOptions(
            method=peft_method,
            aot=aot_mod.AoTOptions(mode="fc", rank=aot_rank, dropout=0.0))
        tcfg = TrainConfig(peft=popt, loss_chunk=loss_chunk)
        init_state, train_step = make_train_step(model, tcfg)
        peft_p = sp.peft_specs(model, popt, mesh, rules)
        trainable = {"peft": peft_p}
        frozen = {"backbone": params}
        state = sp.state_specs(init_state, trainable, mesh, rules)
        batch = sp.input_specs(cfg, shape, mesh, rules)
        args = (state, frozen, batch, sp.rng_spec(mesh, rules))
        return train_step, args, mesh, rules, model, meta

    # serving cells use the paper's zero-cost path: fused AoT tables
    use_aot = cfg.aot_applicable
    table = (sp.fused_table_specs(model, 1, mesh, rules) if use_aot else None)
    fopt = peft_mod.PEFTOptions(method="aot",
                                aot=aot_mod.AoTOptions(mode="fused"))

    if shape.kind == "prefill":
        batch = sp.input_specs(cfg, shape, mesh, rules)

        if cfg.is_encoder_only:
            def prefill_fn(params, batch):
                h, _ = model.forward(params, batch, None)
                return h
            args = (params, batch)
        elif use_aot:
            def prefill_fn(params, table, batch):
                peft = peft_mod.make(table, fopt)
                return model.prefill(params, batch, peft, max_len=shape.seq_len)
            args = (params, table, batch)
        else:
            def prefill_fn(params, batch):
                return model.prefill(params, batch, None, max_len=shape.seq_len)
            args = (params, batch)
        return prefill_fn, args, mesh, rules, model, meta

    # decode — the cache argument is donated (in-place ring/linear update;
    # no output copy in the step's memory footprint)
    cache = sp.cache_specs(model, shape.global_batch, shape.seq_len, mesh, rules)
    tokens = sp.input_specs(cfg, shape, mesh, rules)["tokens"]
    pos = sp.scalar_spec(mesh, rules)
    if use_aot:
        def serve_step(params, table, tokens, pos, cache):
            peft = peft_mod.make(table, fopt)
            return model.decode_step(params, tokens, pos, cache, peft)
        args = (params, table, tokens, pos, cache)
        meta["donate"] = (4,)
    else:
        def serve_step(params, tokens, pos, cache):
            return model.decode_step(params, tokens, pos, cache, None)
        args = (params, tokens, pos, cache)
        meta["donate"] = (3,)
    return serve_step, args, mesh, rules, model, meta


def _compile_one(arch, shape_name, *, multi_pod, verbose_tag=None, **kw):
    from repro.distrib import sharding as shlib
    from repro.roofline.analysis import collective_bytes_from_hlo

    fn, args, mesh, rules, model, meta = build_cell(
        arch, shape_name, multi_pod=multi_pod, **kw)
    if fn is None:
        return None, meta
    t0 = time.time()
    donate = meta.pop("donate", ())
    with mesh, shlib.use_rules(mesh, rules):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    out = {
        "lower_s": t_lower, "compile_s": t_compile,
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "model": model, "meta": meta,
    }
    return out, meta


def _extrapolate_coll(c1, c2, R):
    out = {}
    for op in set(c1) | set(c2):
        a = c1.get(op, {"count": 0, "bytes": 0.0, "wire_bytes": 0.0})
        b = c2.get(op, {"count": 0, "bytes": 0.0, "wire_bytes": 0.0})
        out[op] = {k: max(0.0, a[k] + (R - 1) * (b[k] - a[k])) for k in a}
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: Optional[str] = None, verbose: bool = True,
             **kw) -> dict:
    """Two-phase dry-run per cell:

    1. full-depth compile with scan-over-layers -> memory_analysis (realistic
       buffer liveness) and proof the production config compiles;
    2. depth-1 and depth-2 unrolled compiles -> cost extrapolation
       (cost = c1 + (R-1)(c2-c1)), because XLA's cost analysis counts
       while-loop bodies once.
    """
    from repro import configs

    full, meta = _compile_one(arch, shape_name, multi_pod=multi_pod,
                              scan_layers=True, **kw)
    if full is None:
        result = dict(meta)
        if out_dir:
            _write(out_dir, arch, shape_name, multi_pod, result)
        if verbose:
            print(f"SKIP {arch} x {shape_name}: {meta['skipped']}")
        return result

    cfg = configs.get(arch)
    R = expanded_repeats(cfg)
    v1, _ = _compile_one(arch, shape_name, multi_pod=multi_pod,
                         scan_layers=False, cfg_override=depth_variant(cfg, 1),
                         **kw)
    v2, _ = _compile_one(arch, shape_name, multi_pod=multi_pod,
                         scan_layers=False, cfg_override=depth_variant(cfg, 2),
                         **kw)
    flops = v1["flops_per_device"] + (R - 1) * (
        v2["flops_per_device"] - v1["flops_per_device"])
    bytes_ = v1["bytes_per_device"] + (R - 1) * (
        v2["bytes_per_device"] - v1["bytes_per_device"])
    coll = _extrapolate_coll(v1["collectives"], v2["collectives"], R)

    model = full["model"]
    n_params = sum(
        int(np_prod(s.shape)) for s in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.PRNGKey(0))))

    result = dict(meta)
    result.update({
        "lower_s": full["lower_s"],
        "compile_s": full["compile_s"],
        "depth_extrapolation": {"R": R,
                                "flops_d1": v1["flops_per_device"],
                                "flops_d2": v2["flops_per_device"]},
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "collectives": coll,
        "n_params_total": n_params,
        "memory": full["memory"],
    })
    if verbose:
        m = result["memory"]
        print(f"OK {arch} x {shape_name} mesh={meta['mesh']} "
              f"lower={full['lower_s']:.1f}s compile={full['compile_s']:.1f}s")
        print(f"   memory/device: args={m['argument_bytes']/1e9:.3f}GB "
              f"temp={m['temp_bytes']/1e9:.3f}GB out={m['output_bytes']/1e9:.3f}GB")
        coll_s = ", ".join(f"{k}:{int(v['count'])}"
                           for k, v in coll.items())
        print(f"   flops/device={flops:.3e} bytes/device={bytes_:.3e} "
              f"collectives={{{coll_s}}}")
    if out_dir:
        _write(out_dir, arch, shape_name, multi_pod, result)
    return result


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _write(out_dir, arch, shape_name, multi_pod, result):
    os.makedirs(out_dir, exist_ok=True)
    tag = "pod2" if multi_pod else "pod1"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{tag}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def all_cells():
    from repro import configs
    for cfg in configs.ASSIGNED:
        for s in cfg.shapes:
            yield cfg.name, s.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel activation sharding")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized config: EP MoE remat-save, "
                         "attn_mix remat-save, decode cache-seq sharding")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        jobs = []
        pending = []
        for arch, shape in all_cells():
            tag = "pod2" if args.multi_pod else "pod1"
            path = os.path.join(args.out_dir, f"{arch}__{shape}__{tag}.json")
            if os.path.exists(path) and not args.force:
                print(f"cached {arch} x {shape}")
                continue
            pending.append((arch, shape))
        for arch, shape in pending:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out-dir", args.out_dir]
            if args.multi_pod:
                cmd.append("--multi-pod")
            if args.no_sp:
                cmd.append("--no-sp")
            if args.opt:
                cmd.append("--opt")
            while len(jobs) >= args.jobs:
                for j, (c, p) in enumerate(jobs):
                    if p.poll() is not None:
                        print(f"done {c} rc={p.returncode}")
                        jobs.pop(j)
                        break
                else:
                    time.sleep(2.0)
            print("launch", arch, shape)
            jobs.append(((arch, shape),
                         subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                          stderr=subprocess.STDOUT)))
        for c, p in jobs:
            p.wait()
            print(f"done {c} rc={p.returncode}")
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    kw = {}
    if args.opt:
        kw = dict(remat_save=("attn_mix", "moe_out"), decode_cache_seq=True)
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
             out_dir=args.out_dir, sequence_parallel=not args.no_sp, **kw)


if __name__ == "__main__":
    main()

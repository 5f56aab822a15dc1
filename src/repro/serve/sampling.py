"""Stochastic sampling for the serving engine: params, masking, RNG streams.

Every request carries a :class:`SamplingParams`; the scheduler threads the
per-slot parameter vectors (temperature, top-k, top-p, RNG key, step) into
ONE jitted :func:`sample_tokens` call per decode step, so a mixed batch of
greedy and stochastic requests at heterogeneous settings still costs one
fused pass — no per-request dispatch, no recompilation as the batch
composition churns.

RNG contract (what makes preempt-and-recompute exact). Each *sample* owns a
counter-based key stream derived only from constants of the request:

    base_key  = fold_in(PRNGKey(seed), sample_idx)
    step_key  = fold_in(base_key, j)          # j = index of the output token

Token ``j`` is always drawn with ``step_key(j)`` — whether it is produced by
the prefill logits (j = 0), a mixed decode step, or a decode step *after*
the request was preempted and its KV recomputed. Nothing about the stream
depends on batch composition, slot assignment, page layout, or how many
times the request was evicted; replaying the same (seed, sample_idx, j)
triple replays the identical draw. Greedy decode (temperature 0) bypasses
the stream entirely via an exact ``argmax`` fast path, which is also why
all pre-existing greedy parity contracts keep holding bitwise.

Top-k/top-p follow the standard warper order: logits are temperature-scaled
first, then top-k keeps the k highest-scoring tokens, then top-p keeps the
smallest prefix of the descending-sorted distribution whose cumulative
probability reaches p (the first token always survives). Masked entries are
set to the dtype minimum before ``jax.random.categorical``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode controls.

    temperature: 0.0 = greedy (exact argmax fast path); > 0 scales logits.
    top_k: keep the k highest logits (0 = off).
    top_p: nucleus sampling — keep the smallest descending-probability
        prefix with cumulative mass >= top_p (1.0 = off).
    n: parallel samples per prompt. The scheduler prefills once and forks
        the request's KV pages copy-on-write, so n > 1 costs
        one prefill and only the divergent decode pages.
    seed: root of the request's counter-based RNG stream.
    max_tokens: overrides Request.max_new_tokens when set.
    stop: extra stop-token ids (any of them ends the sample, like eos).
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    n: int = 1
    seed: int = 0
    max_tokens: Optional[int] = None
    stop: Tuple[int, ...] = ()

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    def validate(self) -> None:
        # NaN fails every comparison, so range checks alone would wave a
        # NaN temperature straight into the jitted sampling step — check
        # finiteness explicitly
        if not math.isfinite(self.temperature):
            raise ValueError(
                f"temperature must be finite (got {self.temperature})")
        if not math.isfinite(self.top_p):
            raise ValueError(f"top_p must be finite (got {self.top_p})")
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0 (got {self.temperature})")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (got {self.top_k})")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1] (got {self.top_p})")
        if self.n < 1:
            raise ValueError(f"n must be >= 1 (got {self.n})")
        if self.max_tokens is not None and self.max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1 (got {self.max_tokens})")


GREEDY = SamplingParams()


@lru_cache(maxsize=4096)
def _base_key_cached(seed: int, sample_idx: int) -> Tuple[int, int]:
    k = jax.random.fold_in(jax.random.PRNGKey(seed), sample_idx)
    a, b = np.asarray(jax.device_get(k), np.uint32)
    return int(a), int(b)


def request_base_key(seed: int, sample_idx: int = 0) -> np.ndarray:
    """The (2,) uint32 root key of one sample's stream (host-side, cached)."""
    return np.asarray(_base_key_cached(int(seed), int(sample_idx)), np.uint32)


def masked_logits(logits, temps, top_ks, top_ps):
    """Temperature-scale then top-k/top-p mask a batch of logit rows.

    logits: (b, V) float; temps: (b,) float (0 rows are scaled by eps but
    never sampled — the caller's argmax path wins); top_ks: (b,) int
    (0 = off); top_ps: (b,) float (1.0 = off). Returns (b, V) logits with
    excluded tokens at the dtype minimum. Per-row heterogeneous settings,
    one fused computation — no python branching on traced values.

    Both filters keep a *prefix* of the descending-sorted row, so the kept
    set is fully described by one per-row cutoff VALUE plus a tie budget:
    sort values once, find the smallest kept logit, and compare the
    unsorted row against it. That replaces the old argsort → mask →
    inverse-argsort scatter (two O(V log V) index sorts plus two gathers)
    with a single value sort and one O(V) cumsum — the decode-path cost
    that made sampled serving drag behind greedy.

    Ties at the cutoff value break deterministically in index order
    (lowest vocab id first), matching a stable argsort oracle exactly: if
    the k-th value is duplicated, only enough of the tied tokens survive
    to fill the kept-prefix length — never all of them. Without the tie
    budget, a row of duplicated logits could keep far more than k tokens.
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    rank = jnp.arange(V)[None, :]
    k = jnp.where(top_ks <= 0, V, jnp.minimum(top_ks, V))[:, None]
    keep = rank < k
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs    # exclusive cumsum
    # p >= 1 disables nucleus filtering outright: float32 cumsum can round
    # to 1.0 before the tail, which would spuriously mask the last tokens
    keep &= (mass_before < top_ps[:, None]) | (top_ps[:, None] >= 1.0)
    keep = keep.at[:, 0].set(True)                      # never mask rank 0
    n_keep = keep.sum(axis=-1)[:, None]                 # kept set is a prefix
    cutoff = jnp.take_along_axis(sorted_desc, n_keep - 1, axis=-1)
    above = scaled > cutoff
    # tokens tied at the cutoff fill the remaining budget in index order
    # (a stable argsort ranks equal values lowest-index-first)
    tie = scaled == cutoff
    tie_budget = n_keep - above.sum(axis=-1, keepdims=True)
    tie_rank = jnp.cumsum(tie, axis=-1) - 1             # index-order rank
    neg = jnp.finfo(jnp.float32).min
    return jnp.where(above | (tie & (tie_rank < tie_budget)), scaled, neg)


def step_keys(base_keys, steps):
    """Per-row step keys: fold each sample's counter into its base key.

    base_keys: (b, 2) uint32; steps: (b,) int32 — the index of the output
    token being drawn. Pure function of (seed, sample_idx, step), which is
    the whole preemption-exactness argument.
    """
    return jax.vmap(jax.random.fold_in)(base_keys, steps)


def sample_tokens(logits, temps, top_ks, top_ps, base_keys, steps):
    """Draw one token per row from heterogeneous per-row sampling params.

    logits: (b, V); temps/top_ks/top_ps: (b,) per-row settings; base_keys:
    (b, 2) uint32 sample root keys; steps: (b,) int32 output-token indices.
    Rows with temperature 0 take an exact ``argmax`` fast path (bitwise
    identical to greedy decode); stochastic rows mask and draw with
    ``jax.random.categorical`` under their own ``fold_in(base, step)`` key.
    Returns (b,) int32 tokens. jit-friendly: all shapes static, no host
    sync, safe to fuse into the decode step.
    """
    logits = logits.astype(jnp.float32)
    greedy_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ml = masked_logits(logits, temps, top_ks, top_ps)
    keys = step_keys(base_keys, steps)
    drawn = jax.vmap(jax.random.categorical)(keys, ml).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy_toks, drawn)

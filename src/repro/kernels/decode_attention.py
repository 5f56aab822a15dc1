"""Pallas TPU flash-decode: single-token attention against a long KV cache.

Decode is bandwidth-bound (the whole cache is read once per token), so the
kernel streams the cache in ``block_k`` tiles with online-softmax state in
VMEM scratch. The KV sequence axis is the innermost (sequential) grid axis;
blocks past the row's ``cur_len`` are skipped with ``pl.when`` so a
part-full cache costs only the bytes actually resident — this is what the
decode_32k / long_500k roofline cells exercise.

``cur_len`` may be a scalar (homogeneous batch) or a per-row ``(b,)``
vector — the continuous-batching serve path, where every KV-pool slot holds
a request at a different depth. The lengths are scalar-prefetched so each
grid row masks/skips against its own length with no recompilation when the
batch composition changes.

``paged_decode_attention_kernel`` is the block-table variant for the paged
KV pool: K/V live in a global ``(num_blocks, block_size)`` page pool shared
by all requests, and each row's scalar-prefetched block-table slice routes
the BlockSpec index_map to that row's resident pages. Pages at or past the
row's depth are skipped entirely, so a request costs only the pages it has
actually mapped.

``ragged_paged_attention_kernel`` generalizes the paged kernel to RAGGED
per-slot query lengths: the batch is a PACKED token list — decode rows
contribute one token each, every in-flight prefill a chunk of its prompt
(several prompts' chunks pack into one launch), free slots zero — and
every token carries its owning slot (``token_rows``) and absolute
position (``token_pos``). Both vectors are scalar-prefetched next to the
block tables, so one launch serves a mixed multi-chunk + decode batch
(the single-device-call scheduler tick) with zero padding compute: chunk
tokens see kv ``<= token_pos`` through their OWN slot's table slice
(causal within a chunk, since chunk KV is scattered before the launch;
blind to other slots' chunks by construction), and dead padding tokens
(``token_pos < 0``) skip every page and output exact zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def round_kv_len(n: int, block_k: int = 256) -> int:
    """Round a KV allocation length up so the decode kernel never pads.

    ``decode_attention_kernel`` falls back to a full-cache ``jnp.pad`` copy
    when ``S % block_k != 0`` (with block_k capped at S) — a whole-cache
    read+write on EVERY decode step. Cache owners (serve KV pools, engines)
    allocate ``round_kv_len(max_len)`` rows instead; the extra rows stay
    masked by ``cur_len`` forever.
    """
    if n <= block_k:
        return n
    return -(-n // block_k) * block_k


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, block_k, nk, kvh):
    ki = pl.program_id(1)
    cur_len = len_ref[pl.program_id(0) // kvh]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < cur_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (g, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < cur_len, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, cur_len, *, sm_scale=None,
                            block_k=256, interpret=False):
    """q: (b, h, hd); caches: (b, S, kvh, hd); cur_len: scalar or (b,) int32.

    A per-row ``cur_len`` vector gives every batch row (KV-pool slot) its own
    valid length; rows with ``cur_len <= 0`` produce zeros.
    """
    b, h, hd = q.shape
    S, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    block_k = min(block_k, S)
    pad = (-S) % block_k
    kk = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k_cache
    vv = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v_cache
    Sp = S + pad
    nk = Sp // block_k

    qf = q.reshape(b, kvh, g, hd).reshape(b * kvh, g, hd)
    kf = kk.transpose(0, 2, 1, 3).reshape(b * kvh, Sp, hd)
    vf = vv.transpose(0, 2, 1, 3).reshape(b * kvh, Sp, hd)
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))

    kern = functools.partial(_kernel, sm_scale=scale, block_k=block_k, nk=nk,
                             kvh=kvh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kvh, nk),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bh, ki, lens: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, ki, lens: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, ki, lens: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, ki, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lens, qf, kf, vf)
    return out.reshape(b, kvh * g, hd)


# ---------------------------------------------------------------------------
# paged flash-decode (block-table KV pool)
# ---------------------------------------------------------------------------

def _paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, sm_scale, block_size, npages, kvh):
    pi = pl.program_id(1)
    cur_len = len_ref[pl.program_id(0) // kvh]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages at/past the row's depth are unmapped (block table holds 0 there);
    # skipping them means a request only ever streams its resident pages
    @pl.when(pi * block_size < cur_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        kpos = pi * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < cur_len, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == npages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_tables, cur_len,
                                  *, sm_scale=None, interpret=False):
    """Flash-decode over a paged KV pool.

    q: (b, h, hd); k_pages/v_pages: (num_blocks, block_size, kvh, hd) —
    the global page pool shared by every request; block_tables: (b, npages)
    int32 — per-row physical page ids (unmapped entries hold 0 and are never
    read past ``cur_len``); cur_len: (b,) int32 valid lengths.

    ``cur_len`` and the block tables are scalar-prefetched: each row's
    BlockSpec index_map dereferences its own table slice, so the kernel
    streams exactly that row's resident pages — no gather materialization,
    no recompilation as the pool mapping churns. Rows with ``cur_len <= 0``
    produce zeros.
    """
    b, h, hd = q.shape
    block_size, kvh = k_pages.shape[1], k_pages.shape[2]
    npages = block_tables.shape[1]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)

    qf = q.reshape(b, kvh, g, hd).reshape(b * kvh, g, hd)
    kf = k_pages.transpose(2, 0, 1, 3)          # (kvh, num_blocks, bs, hd)
    vf = v_pages.transpose(2, 0, 1, 3)
    lens = jnp.asarray(cur_len, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)

    kern = functools.partial(_paged_kernel, sm_scale=scale,
                             block_size=block_size, npages=npages, kvh=kvh)
    page_spec = pl.BlockSpec(
        (1, 1, block_size, hd),
        lambda bh, pi, lens, bt: (bh % kvh, bt[bh // kvh, pi], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kvh, npages),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bh, pi, lens, bt: (bh, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, pi, lens, bt: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(lens, bt, qf, kf, vf)
    return out.reshape(b, kvh * g, hd)


# ---------------------------------------------------------------------------
# ragged paged flash attention (packed mixed prefill-chunk + decode batches)
# ---------------------------------------------------------------------------

def _ragged_kernel(pos_ref, row_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale, block_size, npages,
                   kvh):
    pi = pl.program_id(1)
    tpos = pos_ref[pl.program_id(0) // kvh]
    total = tpos + 1        # kv rows this token may see (-1 = dead: none)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages past the token's own position are never streamed — a decode
    # token reads its slot's resident pages, a chunk token additionally its
    # chunk-mates at lower positions (scattered before the launch), and a
    # dead padding token (pos -1) skips everything, finalizing to zeros
    @pl.when(pi * block_size < total)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        kpos = pi * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < total, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == npages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def ragged_paged_attention_kernel(q, k_pages, v_pages, block_tables,
                                  token_rows, token_pos, *, sm_scale=None,
                                  interpret=False):
    """Ragged flash attention over a paged KV pool: one launch, one PACKED
    token list mixing any number of prefill chunks with decode work.

    q: (T, h, hd) — the tick's real tokens, packed: each decode row
    contributes one token, every in-flight prefill its chunk, free slots
    nothing. k_pages / v_pages: (num_blocks, block_size, kvh, hd) with this
    step's new KV already scattered in; block_tables: (num_slots, npages)
    int32; token_rows: (T,) int32 — each token's owning slot; token_pos:
    (T,) int32 — its absolute position (``-1`` marks a dead padding token).

    ``token_rows``/``token_pos`` are scalar-prefetched next to the block
    tables: each token's BlockSpec index_map dereferences ITS SLOT's table
    slice, attends over kv positions ``<= token_pos`` (causal within a
    chunk — lower-positioned chunk-mates were scattered before the launch —
    and blind to every other slot's chunk), and never streams pages past
    its position. Dead tokens skip every page and produce exact zeros.
    """
    T, h, hd = q.shape
    block_size, kvh = k_pages.shape[1], k_pages.shape[2]
    npages = block_tables.shape[1]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)

    qf = q.reshape(T, kvh, g, hd).reshape(T * kvh, g, hd)
    kf = k_pages.transpose(2, 0, 1, 3)          # (kvh, num_blocks, bs, hd)
    vf = v_pages.transpose(2, 0, 1, 3)
    pos = jnp.asarray(token_pos, jnp.int32)
    rows = jnp.asarray(token_rows, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)

    kern = functools.partial(_ragged_kernel, sm_scale=scale,
                             block_size=block_size, npages=npages, kvh=kvh)
    page_spec = pl.BlockSpec(
        (1, 1, block_size, hd),
        lambda th, pi, pos, rows, bt: (th % kvh, bt[rows[th // kvh], pi], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T * kvh, npages),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda th, pi, pos, rows, bt: (th, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, g, hd),
                               lambda th, pi, pos, rows, bt: (th, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * kvh, g, hd), q.dtype),
        interpret=interpret,
        # the device trace names the kernel's op after this (the
        # benchmark's ragged-kernel readings match it)
        name="ragged_paged_attention",
    )(pos, rows, bt, qf, kf, vf)
    return out.reshape(T, kvh * g, hd)

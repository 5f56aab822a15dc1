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
position (``token_pos``). Its grid is (KV heads, query blocks): a program
holds a block of consecutive packed tokens times the query heads of one
KV head, and walks each RUN of one slot's tokens in the block over only
that slot's live pages, by double-buffered DMA from the pool in HBM
(``ragged_plan`` counts the walk on the host). Chunk tokens see kv
``<= token_pos`` through their OWN slot's pages (causal within a chunk,
since chunk KV is scattered before the launch; blind to other slots'
chunks by construction), and dead padding tokens (``token_pos < 0``)
join no run and output exact zeros.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
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

# About this many query rows (tokens x the query heads of one KV head) per
# program, and this many rows of 128 lanes of K (whole pages) per KV step.
# Larger steps spread the fixed cost of a step and of its page DMAs: on a
# TPU v5e at smollm-360m's widths these ran a 263-token tick's 32 layers
# of attention 1.4x and an 8-token tick's 1.15x faster than (256, 128).
_Q_ROWS = 384
_KV_ROWS = 256


def _lane_pack(hd: int, block_size: int) -> int:
    """Keys per 128-lane row of a page: a page of ``block_size`` keys of
    ``hd < 128`` lanes is DMA'd as ``block_size // f`` full rows, f keys a
    row (Mosaic refuses a DMA slice of a memref whose minor dimension is
    narrower than its 128-lane tiling)."""
    if hd < 128 and 128 % hd == 0 and block_size % (128 // hd) == 0:
        return 128 // hd
    return 1


def _ragged_blocks(T: int, g: int, hd: int, block_size: int,
                   npages: int):
    """(tokens per query block, query blocks, pages per KV step).

    A packed width that fits one block is one block of exactly ``T``
    tokens; a wider one splits into balanced blocks of a multiple of 16
    tokens, so every block's rows start on a (16, 128) tile."""
    tq_max = max(16, _Q_ROWS // g // 16 * 16)
    if T <= tq_max:
        tq = T
    else:
        nqb = -(-T // tq_max)
        tq = -(-(-(-T // nqb)) // 16) * 16
    page_rows = block_size // _lane_pack(hd, block_size)
    ppk = max(1, min(npages, _KV_ROWS // page_rows))
    return tq, -(-T // tq), ppk


def _run_metadata(xp, token_rows, token_pos, tq: int, t_pad: int):
    """The runs of a packed token list, computed with the array module
    ``xp``: jax.numpy inside the kernel's jit, numpy on the host
    (``ragged_plan``).

    A run is a maximal stretch of consecutive live tokens of one slot
    inside one query block of ``tq`` tokens. Returns each of the
    ``t_pad`` padded tokens' run (-1 dead), each run's slot and depth (its
    deepest token's position + 1; entries past the last run hold zeros)
    and each query block's first run (one more entry: the run count)."""
    i32 = xp.int32
    pad = t_pad - token_pos.shape[0]
    pos = xp.concatenate([xp.asarray(token_pos, i32),
                          xp.full((pad,), -1, i32)])
    rows = xp.concatenate([xp.asarray(token_rows, i32),
                           xp.zeros((pad,), i32)])
    live = pos >= 0
    idx = xp.arange(t_pad, dtype=i32)
    prev_live = xp.concatenate([xp.zeros((1,), bool), live[:-1]])
    prev_row = xp.concatenate([xp.full((1,), -1, i32), rows[:-1]])
    new = live & ((idx % tq == 0) | ~prev_live | (rows != prev_row))
    csum = xp.cumsum(new.astype(i32), dtype=i32)
    run = xp.where(live, csum - 1, -1)
    own = run[None, :] == idx[:, None]          # (runs, tokens) membership
    run_slot = xp.max(xp.where(own, rows[None, :], 0), axis=1)
    run_depth = xp.max(xp.where(own, pos[None, :] + 1, 0), axis=1)
    block_first = xp.concatenate([xp.zeros((1,), i32), csum[tq - 1::tq]])
    return run, run_slot, run_depth, block_first


def ragged_plan(token_rows, token_pos, *, block_size: int, kv_heads: int,
                q_per_kv: int, head_dim: int,
                npages: int) -> Tuple[int, int, int]:
    """(runs, KV steps over all KV heads, query rows) that one launch of
    the ragged kernel (one layer) walks for a packed token list: each run
    walks its slot's pages up to its deepest token, several pages a KV
    step; the programs hold, over all KV heads, every query block's
    tokens (live or padding) times ``q_per_kv`` query heads."""
    pos = np.asarray(token_pos)
    tq, nqb, ppk = _ragged_blocks(len(pos), q_per_kv, head_dim, block_size,
                                  npages)
    _, _, depth, block_first = _run_metadata(np, np.asarray(token_rows),
                                             pos, tq, tq * nqb)
    runs = int(block_first[-1])
    pages = -(-depth[:runs] // block_size)
    return (runs, kv_heads * int((-(-pages // ppk)).sum()),
            kv_heads * nqb * tq * q_per_kv)


def _ragged_kernel(slot_ref, depth_ref, first_ref, bt_ref,
                   q_ref, run_ref, pos_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, m_scr, l_scr, acc_scr, *,
                   sm_scale, block_size, ppk, pack, hd):
    h, qb = pl.program_id(0), pl.program_id(1)
    keys = ppk * block_size
    page_rows = block_size // pack
    lanes = pack * hd
    r0, r1 = first_ref[qb], first_ref[qb + 1]
    m_scr[...] = jnp.full_like(m_scr, NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def n_pages(r):
        return (depth_ref[r] + block_size - 1) // block_size

    def copies(r, s, buf, act):
        """Start or wait (``act``) the K and V DMAs of run r's KV step s:
        its live pages only (pages past the run's depth are not fetched)."""
        slot = slot_ref[r]

        def page(i, carry):
            src_page = bt_ref[slot, s * ppk + i]
            rows = pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)
            for c, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                act(pltpu.make_async_copy(src.at[h, src_page],
                                          dst.at[buf, rows], sems.at[c, buf]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppk, n_pages(r) - s * ppk), page, 0)

    def start(r, s, buf):
        copies(r, s, buf, lambda cp: cp.start())

    def wait(r, s, buf):
        copies(r, s, buf, lambda cp: cp.wait())

    def step(carry):
        r, s, buf = carry
        steps = (n_pages(r) + ppk - 1) // ppk
        last = s + 1 >= steps
        nr = jnp.where(last, r + 1, r)
        ns = jnp.where(last, 0, s + 1)

        @pl.when(nr < r1)
        def _prefetch():            # the next step's pages, other buffer
            start(nr, ns, 1 - buf)

        wait(r, s, buf)
        k, v = kbuf[buf], vbuf[buf]           # (keys // pack, lanes)
        cdt = q_ref.dtype if q_ref.dtype == k.dtype else jnp.float32
        k = k.astype(cdt)
        # row m, lane group j of the buffer holds key m * pack + j; the
        # query's copy q_j carries q in lane group j and zeros elsewhere
        shape = (m_scr.shape[0], keys // pack)
        key0 = s * keys + jax.lax.broadcasted_iota(jnp.int32, shape, 1) * pack
        run_rows = run_ref[...] == r                          # (rows, 1)
        tpos = pos_ref[...]
        sc, seen = [], []
        for j in range(pack):
            qj = q_ref[0, :, j * lanes:(j + 1) * lanes].astype(cdt)
            sj = jax.lax.dot_general(
                qj, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            # each row sees its own slot's keys up to its own position,
            # and only while its own run walks
            ok = run_rows & (key0 + j <= tpos)
            sc.append(jnp.where(ok, sj, NEG))
            seen.append(ok)
        m_prev = m_scr[...]
        m_new = m_prev
        for sj in sc:
            m_new = jnp.maximum(m_new, sj.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # keys past the run's depth (unfetched pages included) hold stale
        # or uninitialised values: zero them so 0 * NaN cannot reach acc
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        vgrp = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) // hd
        v = jnp.where(s * keys + vrow * pack + vgrp < depth_ref[r],
                      v.astype(jnp.float32), 0.0)
        lsum, pv = 0.0, 0.0
        for j in range(pack):
            p = jnp.where(seen[j], jnp.exp(sc[j] - m_new), 0.0)
            lsum = lsum + p.sum(axis=1, keepdims=True)
            pj = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            pv = pv + (jnp.where(vgrp[:1] == j, pj, 0.0) if pack > 1 else pj)
        l_scr[...] = l_scr[...] * corr + lsum
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        return nr, ns, 1 - buf

    @pl.when(r0 < r1)
    def _walk():
        start(r0, 0, 0)
        jax.lax.while_loop(lambda c: c[0] < r1, step, (r0, 0, 0))

    acc = acc_scr[...]
    out = acc
    for j in range(1, pack):        # fold the lane groups: every group
        out = out + pltpu.roll(acc, j * hd, 1)      # ends with the sum
    o_ref[0] = (out / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


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

    The grid is (KV heads, query blocks). A program holds one block of
    consecutive packed tokens times the query heads of one KV head, and
    walks the runs that meet its block (``ragged_plan``): for each run,
    only its slot's live pages, several pages a step by double-buffered
    DMA from the pool in HBM. Each row attends over its slot's kv
    positions ``<= token_pos`` (causal within a chunk — lower-positioned
    chunk-mates were scattered before the launch — and blind to every
    other slot). Dead tokens join no run and produce exact zeros.
    ``interpret=True`` runs the TPU interpreter (DMAs and semaphores).
    """
    T, h, hd = q.shape
    block_size, kvh = k_pages.shape[1], k_pages.shape[2]
    nb, npages = k_pages.shape[0], block_tables.shape[1]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    tq, nqb, ppk = _ragged_blocks(T, g, hd, block_size, npages)
    pack = _lane_pack(hd, block_size)
    lanes = pack * hd
    t_pad, rows = tq * nqb, tq * g

    run, run_slot, run_depth, block_first = _run_metadata(
        jnp, jnp.asarray(token_rows), jnp.asarray(token_pos), tq, t_pad)
    pos = jnp.full((t_pad,), -1, jnp.int32).at[:T].set(
        jnp.asarray(token_pos, jnp.int32))
    # query rows token-major, the g heads of one KV head together; copy j
    # of a row holds q in lane group j of its own 128 lanes
    qf = jnp.pad(q, ((0, t_pad - T), (0, 0), (0, 0)))
    qf = qf.reshape(t_pad, kvh, g, hd).transpose(1, 0, 2, 3)
    qf = qf.reshape(kvh, t_pad * g, 1, 1, hd)
    qf = qf * jnp.eye(pack, dtype=q.dtype)[:, :, None]
    qf = qf.reshape(kvh, t_pad * g, pack * lanes)
    row_run = jnp.repeat(run, g)[:, None]                # (t_pad * g, 1)
    row_pos = jnp.repeat(pos, g)[:, None]
    # (kvh, num_blocks, page rows, lanes): one page of one head is whole
    # rows of 128 lanes. For hd < 128 this costs a copy of the pool per
    # layer (the reshape is no bitcast of (8, 128)-tiled pages); a pool
    # stored in this layout would not
    kf =k_pages.transpose(2, 0, 1, 3).reshape(kvh, nb, -1, lanes)
    vf = v_pages.transpose(2, 0, 1, 3).reshape(kvh, nb, -1, lanes)
    bt = jnp.asarray(block_tables, jnp.int32)

    kern = functools.partial(_ragged_kernel, sm_scale=scale,
                             block_size=block_size, ppk=ppk, pack=pack,
                             hd=hd)
    row_spec = pl.BlockSpec((rows, 1), lambda hh, qb, *_: (qb, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = (2, ppk * block_size // pack, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(kvh, nqb),
        in_specs=[pl.BlockSpec((1, rows, pack * lanes),
                               lambda hh, qb, *_: (hh, qb, 0)),
                  row_spec, row_spec, any_spec, any_spec],
        out_specs=pl.BlockSpec((1, rows, lanes),
                               lambda hh, qb, *_: (hh, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM(buf, k_pages.dtype),
            pltpu.VMEM(buf, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, t_pad * g, lanes), q.dtype),
        interpret=pltpu.InterpretParams() if interpret is True else interpret,
        # the device trace names the kernel's op after this (the
        # benchmark's ragged-kernel readings match it)
        name="ragged_paged_attention",
    )(run_slot, run_depth, block_first, bt, qf, row_run, row_pos, kf, vf)
    out = out[..., :hd].reshape(kvh, t_pad, g, hd).transpose(1, 0, 2, 3)
    return out.reshape(t_pad, h, hd)[:T]

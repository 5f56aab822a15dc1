"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each oracle is the semantic ground truth the TPU kernels must match in
``interpret=True`` mode (and on hardware). Tests sweep shapes/dtypes and
assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0, sm_scale=None):
    """q: (b, sq, h, hd); k/v: (b, skv, kvh, hd). GQA by head grouping."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    q5 = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q5, k).astype(jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, hd)


def decode_attention_ref(q, k_cache, v_cache, cur_len, *, sm_scale=None):
    """q: (b, h, hd); caches (b, S, kvh, hd); cur_len: scalar or (b,) valid lengths."""
    b, h, hd = q.shape
    S, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    q4 = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q4, k_cache).astype(jnp.float32) * scale
    lens = jnp.broadcast_to(jnp.asarray(cur_len), (b,))
    ok = jnp.arange(S)[None, :] < lens[:, None]
    s = jnp.where(ok[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, h, hd)


def ragged_paged_attention_ref(q, k_pages, v_pages, block_tables, token_rows,
                               token_pos, *, sm_scale=None):
    """q: (T, h, hd) packed tokens; pages: (num_blocks, block_size, kvh, hd);
    block_tables: (num_slots, npages) int32; token_rows / token_pos: (T,).

    The packed mixed multi-chunk + decode contract: token t belongs to
    slot ``token_rows[t]`` at absolute position ``token_pos[t]`` and
    attends causally (kv position <= its own) over its slot's gathered
    pages — which is exactly the contiguous decode oracle per token, after
    the per-token block-table gather. Any number of slots may contribute
    chunks to the same packed list; a token never sees another slot's
    pages. Dead padding tokens (``token_pos < 0``) output exact zeros.
    """
    T, h, hd = q.shape
    bs, kvh = k_pages.shape[1], k_pages.shape[2]
    bt = jnp.take(block_tables, token_rows, axis=0)           # (T, npages)
    k = jnp.take(k_pages, bt, axis=0).reshape(T, -1, kvh, hd)
    v = jnp.take(v_pages, bt, axis=0).reshape(T, -1, kvh, hd)
    o = decode_attention_ref(q, k, v, token_pos + 1, sm_scale=sm_scale)
    return jnp.where((token_pos >= 0)[:, None, None], o, 0.0).astype(q.dtype)


def aot_gather_add_ref(h, table, ids):
    """The paper's Eq. 1 hot path: H + P[x].

    h: (T, d); table: (V, d); ids: (T,) int32 -> (T, d).
    """
    return h + jnp.take(table, ids, axis=0).astype(h.dtype)


def aot_gather_add_multitask_ref(h, tables, task_ids, ids):
    """h: (T, d); tables: (n_tasks, V, d); task_ids/ids: (T,) -> (T, d)."""
    return h + tables[task_ids, ids].astype(h.dtype)

"""Pallas TPU flash attention (train/prefill): online-softmax block tiling.

Tiling: the (batch*kv_head*group) product is folded into the leading grid
axis; q blocks of ``block_q`` rows stream against kv blocks of ``block_k``
with the running (m, l, acc) kept in VMEM scratch across the innermost grid
axis (TPU grids iterate the last axis sequentially, so scratch carries).

Causal / sliding-window masking skips out-of-range kv blocks entirely
(``pl.when``) — the MXU never sees fully-masked tiles. Block shapes should
be multiples of 128 on hardware; tests use small blocks in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, causal, window, block_q, block_k, nk, seq_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = ki * block_k
    run = True
    if causal:
        run = k_lo <= q_lo + block_q - 1
    if window:
        run = jnp.logical_and(run, k_lo + block_k - 1 > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = kpos < seq_kv
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG)
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


def flash_attention_kernel(q, k, v, *, causal=True, window=0, sm_scale=None,
                           block_q=128, block_k=128, interpret=False):
    """q: (b, sq, h, hd); k/v: (b, skv, kvh, hd) -> (b, sq, h, hd).

    Pads sq/skv up to block multiples; GQA folded into the grid's lead axis.
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(skv, 8))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qq = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kk = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vv = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    sqp, skp = sq + pad_q, skv + pad_k

    # (b, s, h, hd) -> (b * kvh * g, s, hd) with kv index = lead // g
    qf = qq.transpose(0, 2, 1, 3).reshape(b * h, sqp, hd)
    kf = kk.transpose(0, 2, 1, 3).reshape(b * kvh, skp, hd)
    vf = vv.transpose(0, 2, 1, 3).reshape(b * kvh, skp, hd)

    nq = sqp // block_q
    nk = skp // block_k
    grid = (b * h, nq, nk)

    kern = functools.partial(
        _kernel, sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, seq_kv=skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    out = out.reshape(b, h, sqp, hd).transpose(0, 2, 1, 3)
    return out[:, :sq]

"""Core transformer building blocks: norms, RoPE, MLP, attention.

Everything is a pure function over explicit param pytrees (no flax). Compute
dtype policy: matmuls run in ``compute_dtype`` (bf16 on TPU), reductions
(norm statistics, softmax, logsumexp) in fp32.

Attention has three implementations:
  * ``ref``      — full-score einsum; oracle for tests, O(s^2) memory.
  * ``chunked``  — statically-unrolled q-chunks x online-softmax kv scan.
                   Sub-quadratic memory AND causal/SWA block skipping with
                   *static* bounds, so the HLO FLOPs stay honest (no 2x
                   causal waste). This is the dry-run / XLA production path.
  * ``pallas``   — the TPU kernel in repro.kernels (selected on real TPUs;
                   validated with interpret=True in tests).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distrib.sharding import constrain

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype=jnp.float32, scale: Optional[float] = None):
    fan_in = shape[0]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32, std: float = 0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg, dim: Optional[int] = None):
    d = dim if dim is not None else cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    if cfg.norm_type == "nonparametric":
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg, p, x):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    else:  # layernorm / nonparametric
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        if p:
            y = y * p["scale"] + p["bias"]
    return y.astype(dt)


def rms_head_norm(scale, x, eps=1e-6):
    """q/k per-head RMSNorm (qwen3). x: (..., head_dim)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(dt)


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (b, s, h, hd); positions: (b, s) or (s,) int32."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                       # (hd/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., None] * inv                        # (..., s, hd/2)
    if ang.ndim == 2:                                 # (s, hd/2) -> broadcast batch
        ang = ang[None]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff: Optional[int] = None, d_model: Optional[int] = None):
    d = d_model if d_model is not None else cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"wg": dense_init(ks[0], (d, f)),
                "wu": dense_init(ks[1], (d, f)),
                "wd": dense_init(ks[2], (f, d))}
    # plain gelu MLP (with biases, BERT-style)
    return {"w1": dense_init(ks[0], (d, f)), "b1": jnp.zeros((f,), jnp.float32),
            "w2": dense_init(ks[1], (f, d)), "b2": jnp.zeros((d,), jnp.float32)}


def apply_mlp(cfg, p, x, dtype):
    x = x.astype(dtype)
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = x @ p["wg"].astype(dtype)
        u = x @ p["wu"].astype(dtype)
        g = constrain(g, "batch", "seq", "mlp")
        u = constrain(u, "batch", "seq", "mlp")
        act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" else jax.nn.gelu(g)
        h = act * u
        out = h @ p["wd"].astype(dtype)
    else:
        h = x @ p["w1"].astype(dtype) + p["b1"].astype(dtype)
        h = constrain(h, "batch", "seq", "mlp")
        h = jax.nn.gelu(h)
        out = h @ p["w2"].astype(dtype) + p["b2"].astype(dtype)
    return constrain(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# attention core
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mask_bias(q_pos, kv_pos, *, causal, window, prefix_len, kv_valid_len=None):
    """(q, kv) additive mask in fp32. Positions are int32 arrays."""
    q = q_pos[:, None]
    k = kv_pos[None, :]
    ok = jnp.ones(q.shape[:1] + k.shape[1:], bool)
    if causal:
        c = k <= q
        if prefix_len:
            c = c | (k < prefix_len)
        ok &= c
    if window:
        ok &= k > q - window
        if not causal:          # symmetric local window for encoders
            ok &= k < q + window
    if kv_valid_len is not None:
        ok &= k < kv_valid_len
    return jnp.where(ok, 0.0, NEG_INF)


def _sdpa_block(q, k, v, bias, softcap: float):
    """q: (b, qc, KV, G, hd)  k/v: (b, kc, KV, hd)  bias: (qc, kc) -> (b,qc,KV,G,hd).

    Plain softmax over the given block (used by ref impl and single-block
    chunks). fp32 softmax.
    """
    hd = q.shape[-1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)


def attention_ref(q, k, v, *, causal, window=0, prefix_len=0, softcap=0.0,
                  q_offset=0, kv_valid_len=None):
    """Oracle attention. q: (b,sq,H,hd) k/v: (b,skv,KV,hd)."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    q5 = q.reshape(b, sq, kvh, g, hd)
    bias = _mask_bias(q_offset + jnp.arange(sq), jnp.arange(skv), causal=causal,
                      window=window, prefix_len=prefix_len, kv_valid_len=kv_valid_len)
    out = _sdpa_block(q5, k, v, bias, softcap)
    return out.reshape(b, sq, h, hd)


def _online_chunk_scan(q5, k_r, v_r, q_pos, kv_start, chunk_kv, *, causal,
                       window, prefix_len, softcap, kv_valid_len):
    """Online-softmax scan over kv chunks for one q chunk.

    q5: (b, qc, KV, G, hd); k_r/v_r: (b, L, KV, hd) with L % chunk_kv == 0.
    Returns (b, qc, KV, G, hd).
    """
    b, qc, kvh, g, hd = q5.shape
    L = k_r.shape[1]
    n = L // chunk_kv
    ks = k_r.reshape(b, n, chunk_kv, kvh, hd).transpose(1, 0, 2, 3, 4)
    vs = v_r.reshape(b, n, chunk_kv, kvh, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        (kc, vc, j) = xs
        kv_pos = kv_start + j * chunk_kv + jnp.arange(chunk_kv)
        bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                          prefix_len=prefix_len, kv_valid_len=kv_valid_len)
        s = jnp.einsum("bqkgh,bskh->bkgqs", q5, kc).astype(jnp.float32) / math.sqrt(hd)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = s + bias
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, qc), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, qc, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (ks, vs, jnp.arange(n)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q5.dtype)  # (b, qc, KV, G, hd)


def attention_chunked(q, k, v, *, causal, window=0, prefix_len=0, softcap=0.0,
                      chunk_q=1024, chunk_kv=1024, q_offset=0, kv_valid_len=None):
    """Blockwise attention with static causal/SWA block skipping.

    The q-chunk loop is a static python loop; each q chunk only ever touches
    the kv range its mask admits, so causal training carries no 2x FLOP
    waste and SWA is truly O(s * window).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    cq = min(chunk_q, sq)
    n_q = -(-sq // cq)
    outs = []
    for j in range(n_q):
        lo_q, hi_q = j * cq, min((j + 1) * cq, sq)
        qc = q[:, lo_q:hi_q].reshape(b, hi_q - lo_q, kvh, g, hd)
        q_pos = q_offset + jnp.arange(lo_q, hi_q)
        # static kv bounds for this q chunk
        if causal:
            hi_kv = min(skv, q_offset + hi_q)
            if prefix_len:
                hi_kv = max(hi_kv, min(skv, prefix_len))
            lo_kv = 0
            if window:
                lo_kv = max(0, q_offset + lo_q - window + 1)
                if prefix_len:
                    lo_kv = 0   # prefix always visible
        else:
            lo_kv, hi_kv = 0, skv
            if window:
                lo_kv = max(0, q_offset + lo_q - window + 1)
                hi_kv = min(skv, q_offset + hi_q - 1 + window)
        # align to chunk_kv
        ckv = min(chunk_kv, hi_kv - lo_kv) or 1
        lo_kv = (lo_kv // ckv) * ckv
        span = hi_kv - lo_kv
        n_kv = -(-span // ckv)
        hi_kv_pad = min(skv, lo_kv + n_kv * ckv)
        k_r = k[:, lo_kv:hi_kv_pad]
        v_r = v[:, lo_kv:hi_kv_pad]
        pad = n_kv * ckv - k_r.shape[1]
        valid = kv_valid_len if kv_valid_len is not None else (
            hi_kv if pad else None)
        if pad:  # pad to a whole number of kv chunks; mask handles the tail
            k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_r = jnp.pad(v_r, ((0, 0), (0, pad), (0, 0), (0, 0)))
            valid = hi_kv if kv_valid_len is None else kv_valid_len
        if n_kv <= 2:
            kv_pos = lo_kv + jnp.arange(k_r.shape[1])
            bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                              prefix_len=prefix_len, kv_valid_len=valid)
            o = _sdpa_block(qc, k_r, v_r, bias, softcap)
        else:
            o = _online_chunk_scan(qc, k_r, v_r, q_pos, lo_kv, ckv,
                                   causal=causal, window=window,
                                   prefix_len=prefix_len, softcap=softcap,
                                   kv_valid_len=valid)
        outs.append(o.reshape(b, hi_q - lo_q, h, hd))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def attention_decode(q, k_cache, v_cache, cur_len, *, window=0, softcap=0.0):
    """Single-token decode attention against a cache.

    q: (b, 1, H, hd); caches: (b, S, KV, hd); cur_len: scalar int32 — number
    of valid positions (the new token's kv already written at cur_len-1) —
    or a per-row (b,) vector for mixed-depth batches (the continuous-batching
    serve path, where every KV-pool slot is at a different depth).
    For ring-buffer SWA caches the whole buffer is valid once full; masking
    uses cur_len against the buffer size.
    """
    b, _, h, hd = q.shape
    S, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    q5 = q.reshape(b, 1, kvh, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q5, k_cache).astype(jnp.float32) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(S)
    cl = jnp.asarray(cur_len)
    if cl.ndim == 0:
        ok = pos < cl
        if window:
            ok &= pos > cl - 1 - window
        mask = ok[None, None, None, None, :]
    else:
        ok = pos[None, :] < cl[:, None]
        if window:
            ok &= pos[None, :] > (cl - 1 - window)[:, None]
        mask = ok[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, hd)


def ragged_paged_attention_decode(q, k_pages, v_pages, block_tables,
                                  token_rows, token_pos, *, softcap=0.0):
    """Packed ragged mixed-batch attention against a paged KV pool (XLA).

    q: (T, 1, H, hd) — the tick's packed tokens (decode rows one each,
    every in-flight prefill its chunk, free slots none);
    k_pages/v_pages: (num_blocks, block_size, KV, hd) with the step's new
    KV already scattered in; block_tables: (num_slots, npages) int32;
    token_rows: (T,) each token's owning slot; token_pos: (T,) its
    absolute position (-1 = dead padding token).

    Gathers each token's slot pages contiguous and defers to
    :func:`attention_decode` with per-token valid length ``token_pos + 1``
    — element-for-element the contiguous decode computation over the same
    KV rows. Dead tokens output zeros (matching the Pallas ragged
    kernel).
    """
    T = q.shape[0]
    bs, kvh, hd = k_pages.shape[1], k_pages.shape[2], k_pages.shape[3]
    bt = jnp.take(block_tables, token_rows, axis=0)           # (T, npages)
    k = jnp.take(k_pages, bt, axis=0).reshape(T, -1, kvh, hd)
    v = jnp.take(v_pages, bt, axis=0).reshape(T, -1, kvh, hd)
    out = attention_decode(q, k, v, token_pos + 1, softcap=softcap)
    return jnp.where((token_pos >= 0)[:, None, None, None], out, 0.0)


# ---------------------------------------------------------------------------
# attention module (projections + core)
# ---------------------------------------------------------------------------

def attn_init(key, cfg):
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {"wq": dense_init(ks[0], (d, h * hd)),
         "wk": dense_init(ks[1], (d, kvh * hd)),
         "wv": dense_init(ks[2], (d, kvh * hd)),
         "wo": dense_init(ks[3], (h * hd, d))}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kvh * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kvh * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def attn_project_qkv(cfg, p, x, positions, dtype, peft_qkv=None):
    """x: (b, s, d) -> q (b,s,H,hd), k,v (b,s,KV,hd) with rope applied."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = x.astype(dtype)
    q = x @ p["wq"].astype(dtype)
    k = x @ p["wk"].astype(dtype)
    v = x @ p["wv"].astype(dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if peft_qkv is not None:          # LoRA deltas / BitFit bias deltas
        dq, dk, dv = peft_qkv
        if dq is not None:
            q = q + dq.astype(dtype)
        if dk is not None:
            k = k + dk.astype(dtype)
        if dv is not None:
            v = v + dv.astype(dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def attn_output(cfg, p, o, dtype, peft_bias=None):
    b, s, h, hd = o.shape
    out = o.reshape(b, s, h * hd).astype(dtype) @ p["wo"].astype(dtype)
    if peft_bias is not None:
        out = out + peft_bias.astype(dtype)
    return constrain(out, "batch", "seq", "embed")

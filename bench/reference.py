"""Plain reference forward pass: straightforward jax.numpy in float32.

It imports nothing of the program. It reads the weights and AoT tables
that the benchmark drew from the seed (``bench/system.py``), in the
parameter tree the program is handed, and the sizes in the
configuration file. The architecture is a pre-norm decoder: per layer,
the task's AoT row ``P[layer][task][token]`` is added to the hidden state
(paper Eq. 1), then RMSNorm (or non-parametric LayerNorm) → attention
with rotary positions (half-split) and grouped KV heads under a causal
mask → residual, norm → SwiGLU MLP → residual; a final norm and the tied
embedding give the logits. Matrix products run at ``highest`` precision.

``quant="fp8"`` gives the control: the same pass with every matrix
product's operands rounded to float8 e4m3 (per-tensor scaled), the
nearest precision below the bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8_MAX = 448.0         # largest finite float8_e4m3fn


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _norm(x, p, arch):
    if "rms_norm_eps" in arch:
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + arch["rms_norm_eps"])
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + arch["layer_norm_eps"])
    if "scale" in p:
        y = y * p["scale"].astype(F32)
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    return y


def _rope(x, theta):
    """x: (S, heads, hd), rotated by position along the half split."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("arch_items", "quant"))
def logits_at(params, table, tokens, task, out_pos, *, arch_items,
              quant=None):
    """Logits (K, V) at positions ``out_pos`` (K,) of one sequence
    ``tokens`` (S,) served under ``task``. Padding past the real tokens
    does not reach earlier positions (causal mask)."""
    arch = dict(arch_items)
    q8 = _fp8 if quant == "fp8" else (lambda x: x)
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision="highest")

    heads, kvh = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["hidden_size"] // heads
    s = tokens.shape[0]
    emb = params["embed"]["tok"].astype(F32)
    h = q8(jnp.take(emb, tokens, axis=0))
    bias = q8(table[:, task][:, tokens].astype(F32))        # (L, S, d)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, xs):
        lp, b = xs
        h = h + b
        a = lp["attn"]
        x = _norm(h, lp.get("ln1", {}), arch)
        q = mm(x, a["wq"].astype(F32)).reshape(s, heads, hd)
        k = mm(x, a["wk"].astype(F32)).reshape(s, kvh, hd)
        v = mm(x, a["wv"].astype(F32)).reshape(s, kvh, hd)
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
        k = jnp.repeat(k, heads // kvh, axis=1)
        v = jnp.repeat(v, heads // kvh, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q8(q), q8(k),
                        precision="highest") / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q8(p), q8(v), precision="highest")
        h = h + mm(o.reshape(s, heads * hd), a["wo"].astype(F32))
        m = lp["mlp"]
        x = _norm(h, lp.get("ln2", {}), arch)
        g = mm(x, m["wg"].astype(F32))
        u = mm(x, m["wu"].astype(F32))
        h = h + mm(jax.nn.silu(g) * u, m["wd"].astype(F32))
        return h, None

    h, _ = jax.lax.scan(layer, h, (params["groups"][0]["b0"], bias))
    h = _norm(jnp.take(h, out_pos, axis=0), params["final_norm"], arch)
    head = (params["lm_head"]["w"].astype(F32) if "lm_head" in params
            else emb.T)
    return mm(h, head)


def arch_items(cfile: dict) -> tuple:
    """The configuration's scalar sizes as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfile["config"].items()
                        if isinstance(v, (int, float, str, bool))))

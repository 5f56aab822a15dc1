"""Ahead-of-Time P-Tuning — the paper's contribution (Gavrilov & Balagansky 2023).

For each transformer layer ``i`` a vocabulary-indexed bias table
``P^i in R^{|V| x d}`` modifies hidden states *before* the layer:

    H'^i = H^i + P^i[x]                                   (paper Eq. 1)

Training never materializes ``P``; two reparametrizations compute only the
rows the batch needs (paper §3.3):

  * FC:        P = f(E W1 + b1) W2 + b2                   (paper Eq. 3)
  * Kronecker: P = (W_L ⊗ W_M) W_R                        (paper Eq. 2)

After training, :func:`fuse` materializes the explicit per-layer tables so
inference is a single gather+add per layer (zero extra matmuls — the paper's
"zero-cost" property), and :func:`stack_tasks` builds the multi-task table
set a single frozen backbone serves from.

Initialization follows the paper §4.1: FC — W1 random, W2/b1/b2 zero;
Kronecker — W_L/W_M random, W_R zero. Both make the initial bias exactly 0,
so fine-tuning starts from the pre-trained model.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


@dataclass(frozen=True)
class AoTOptions:
    mode: str = "fc"            # "fc" | "kron" | "fused"
    rank: int = 64              # FC mapping rank / Kronecker factorization rank
    kron_a: int = 0             # 0 = auto-factorize |V| (paper picks a*b >= |V|)
    kron_b: int = 0
    nonlin: str = "gelu"        # f in Eq. 3
    dropout: float = 0.1        # paper: dropout on E (FC) / on P_x (Kron)


def _nonlin(name: str):
    return {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "tanh": jnp.tanh}[name]


def kron_factors(vocab: int, a: int = 0, b: int = 0) -> Tuple[int, int]:
    """Pick a*b >= |V| (paper footnote 1: slightly larger is fine)."""
    if a and b:
        assert a * b >= vocab, (a, b, vocab)
        return a, b
    a = 1 << max(1, (int(math.ceil(math.log2(max(vocab, 2)))) + 1) // 2)
    b = -(-vocab // a)
    return a, b


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key, cfg, opt: AoTOptions):
    """PEFT params for all ``cfg.num_layers`` layers, stacked on axis 0."""
    L, d, V, r = cfg.num_layers, cfg.d_model, cfg.vocab_size, opt.rank
    if opt.mode == "fc":
        w1 = jax.vmap(lambda k: dense_init(k, (d, r)))(jax.random.split(key, L))
        return {"w1": w1,
                "b1": jnp.zeros((L, r), jnp.float32),
                "w2": jnp.zeros((L, r, d), jnp.float32),
                "b2": jnp.zeros((L, d), jnp.float32)}
    if opt.mode == "kron":
        a, b = kron_factors(V, opt.kron_a, opt.kron_b)
        k1, k2 = jax.random.split(key)
        wl = jax.vmap(lambda k: dense_init(k, (a, r), scale=1.0 / math.sqrt(r)))(
            jax.random.split(k1, L))
        wm = jax.vmap(lambda k: dense_init(k, (b, r), scale=1.0 / math.sqrt(r)))(
            jax.random.split(k2, L))
        return {"wl": wl, "wm": wm,
                "wr": jnp.zeros((L, r * r, d), jnp.float32)}
    if opt.mode == "fused":
        return {"table": jnp.zeros((L, V, d), jnp.float32)}
    raise ValueError(opt.mode)


# ---------------------------------------------------------------------------
# row computation (training path: only rows for the batch's tokens, §3.3)
# ---------------------------------------------------------------------------

def rows_fc(layer_p, e_rows, opt: AoTOptions, dtype=jnp.float32,
            dropout_rng=None):
    """P rows from gathered embeddings. layer_p leaves unstacked: w1 (d, r)...

    e_rows: (..., d) = E[x] (gathered embedding rows for the batch tokens).
    """
    x = e_rows.astype(dtype)
    if dropout_rng is not None and opt.dropout > 0:    # paper: dropout on E
        keep = jax.random.bernoulli(dropout_rng, 1.0 - opt.dropout, x.shape)
        x = jnp.where(keep, x / (1.0 - opt.dropout), 0.0)
    h = _nonlin(opt.nonlin)(x @ layer_p["w1"].astype(dtype) + layer_p["b1"].astype(dtype))
    return h @ layer_p["w2"].astype(dtype) + layer_p["b2"].astype(dtype)


def rows_kron(layer_p, ids, opt: AoTOptions, vocab: int, dtype=jnp.float32,
              dropout_rng=None):
    """P rows by Kronecker lookup. Row v=(i,j) = vec(W_L[i] ⊗ W_M[j]) W_R."""
    b = layer_p["wm"].shape[0]
    i = ids // b
    j = ids % b
    wl = jnp.take(layer_p["wl"].astype(dtype), i, axis=0)      # (..., r)
    wm = jnp.take(layer_p["wm"].astype(dtype), j, axis=0)      # (..., r)
    r = wl.shape[-1]
    kr = (wl[..., :, None] * wm[..., None, :]).reshape(ids.shape + (r * r,))
    out = kr @ layer_p["wr"].astype(dtype)
    if dropout_rng is not None and opt.dropout > 0:    # paper: dropout on P_x
        keep = jax.random.bernoulli(dropout_rng, 1.0 - opt.dropout, out.shape)
        out = jnp.where(keep, out / (1.0 - opt.dropout), 0.0)
    return out


def rows_fused(layer_p, ids, dtype=jnp.float32):
    """Inference path: gather rows of the fused table. layer_p: {"table": (V, d)}."""
    return jnp.take(layer_p["table"].astype(dtype), ids, axis=0)


def rows_fused_multitask(table_layer, task_ids, ids, dtype=jnp.float32):
    """table_layer: (tasks, V, d); task_ids: (b,); ids: (b, s) -> (b, s, d).

    One combined gather — the multi-task batched lookup the paper's §3.2
    highlights ('performing look-up from P can be easily parallelized').
    """
    return table_layer[task_ids[:, None], ids].astype(dtype)


# ---------------------------------------------------------------------------
# fusion (paper §3.3: "P could be fused once training is complete")
# ---------------------------------------------------------------------------

def fused_layers(aot_params, cfg, opt: AoTOptions,
                 embed: Optional[jax.Array] = None, vocab_chunk: int = 8192,
                 dtype=jnp.float32):
    """Yield each layer's explicit (V, d) table in turn (FC or Kronecker
    reparametrization), so a caller can store one layer before the next
    is made."""
    L, V = cfg.num_layers, cfg.vocab_size
    if opt.mode == "fc":
        assert embed is not None, "FC fusion needs the embedding matrix E"
    for i in range(L):
        layer_p = jax.tree.map(lambda x: x[i], aot_params)
        chunks = []
        for lo in range(0, V, vocab_chunk):
            hi = min(V, lo + vocab_chunk)
            ids = jnp.arange(lo, hi)
            if opt.mode == "fc":
                rows = rows_fc(layer_p, jnp.take(embed, ids, axis=0), opt, dtype)
            else:
                rows = rows_kron(layer_p, ids, opt, V, dtype)
            chunks.append(rows)
        yield jnp.concatenate(chunks, axis=0)


def fuse(aot_params, cfg, opt: AoTOptions, embed: Optional[jax.Array] = None,
         vocab_chunk: int = 8192, dtype=jnp.float32):
    """Materialize explicit per-layer tables (L, V, d) from a reparametrization."""
    if opt.mode == "fused":
        return {"table": aot_params["table"].astype(dtype)}
    return {"table": jnp.stack(list(fused_layers(
        aot_params, cfg, opt, embed, vocab_chunk, dtype)))}


def random_fc_params(cfg, seed: int = 0, *, rank: int = 8,
                     scale: float = 0.05):
    """Scaled-normal FC reparametrization params (no training) and their
    options: what :func:`random_fused` fuses into a task table."""
    opt = AoTOptions(mode="fc", rank=rank, dropout=0.0)
    pp = init(jax.random.PRNGKey(seed), cfg, opt)
    pp = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(seed + 50),
                                    x.shape) * scale, pp)
    return pp, opt


def random_fused(cfg, embed, seed: int = 0, *, rank: int = 8,
                 scale: float = 0.05, vocab_chunk: int = 64):
    """Fabricate a plausibly-scaled fused task table {'table': (L, V, d)}.

    Shared by demos, benchmarks, and tests that need per-task tables without
    training: FC reparametrization params overwritten with scaled normals,
    then fused the same way a trained task would be.
    """
    pp, opt = random_fc_params(cfg, seed, rank=rank, scale=scale)
    return fuse(pp, cfg, opt, embed=embed, vocab_chunk=vocab_chunk)


def _write_block_impl(stack, block, layer, task):
    """Write ``block`` (k, V, d) into layers ``layer..layer+k`` of task
    ``task`` of ``stack`` (L, T, V, d). Traced offsets: one compilation
    per block shape."""
    return jax.lax.dynamic_update_slice(
        stack, block[:, None].astype(stack.dtype), (layer, task, 0, 0))


_WRITE_BLOCK = None


def write_block(stack, block, layer: int, task: int):
    """In-place (donated on TPU) write of one layer block of one task into
    a stacked table: building a stack never holds two copies of it."""
    global _WRITE_BLOCK
    if _WRITE_BLOCK is None:
        # CPU (tests) has no donation support, so skip it there
        donate = (0,) if jax.default_backend() == "tpu" else ()
        _WRITE_BLOCK = jax.jit(_write_block_impl, donate_argnums=donate)
    return _WRITE_BLOCK(stack, block, jnp.int32(layer), jnp.int32(task))


def stack_tasks(fused_list, dtype=None):
    """[{'table': (L, V, d)}, ...] per task -> {'table': (L, T, V, d)}.

    Layer-major so the model's per-layer scan slicing sees (T, V, d) slices.
    Each task is written into a preallocated stack (``dtype``, default the
    first table's), so beyond the caller's list the device holds the stack
    and nothing else; tables kept on the host (a loaded checkpoint) cost
    the device only the stack.
    """
    first = fused_list[0]["table"]
    stack = jnp.zeros((first.shape[0], len(fused_list)) + first.shape[1:],
                      dtype or first.dtype)
    for t, f in enumerate(fused_list):
        stack = write_block(stack, f["table"], 0, t)
    return {"table": stack}


def table_bytes(cfg, n_tasks: int = 1, bytes_per_el: int = 2) -> int:
    """RAM the paper trades for speed (§3.3: ~2.4GB/task for RoBERTa-Large fp16)."""
    return n_tasks * cfg.num_layers * cfg.vocab_size * cfg.d_model * bytes_per_el

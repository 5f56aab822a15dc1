"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels run with ``interpret=True`` for
correctness validation; on TPU they compile through Mosaic. ``INTERPRET``
flips automatically from the backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.aot_bias import (aot_gather_add_kernel,
                                    aot_gather_add_multitask_kernel)
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            ragged_paged_attention_kernel)
from repro.kernels.flash_attention import flash_attention_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "prefix_len",
                                   "softcap", "q_offset", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, prefix_len=0,
                    softcap=0.0, q_offset=0, block_q=128, block_k=128):
    """Model-facing signature (matches models.layers attention kwargs).

    prefix_len/softcap/q_offset are unsupported by the kernel fast path and
    fall back to the chunked XLA implementation.
    """
    if prefix_len or softcap or q_offset:
        from repro.models.layers import attention_chunked
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 prefix_len=prefix_len, softcap=softcap,
                                 q_offset=q_offset)
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, cur_len, *, block_k=256):
    return decode_attention_kernel(q, k_cache, v_cache, cur_len,
                                   block_k=block_k, interpret=_interpret())


@jax.jit
def ragged_paged_attention(q, k_pages, v_pages, block_tables, token_rows,
                           token_pos):
    """q: (T, h, hd) packed tokens; pages: (num_blocks, block_size, kvh,
    hd); block_tables: (num_slots, npages); token_rows/token_pos: (T,).
    The unified serve-path mixed multi-chunk + decode attention — every
    in-flight prefill's chunk and all decode rows in one launch per tick,
    zero padding compute."""
    return ragged_paged_attention_kernel(q, k_pages, v_pages, block_tables,
                                         token_rows, token_pos,
                                         interpret=_interpret())


@jax.jit
def aot_gather_add(h, table, ids):
    """h: (b, s, d) or (T, d); table: (V, d); ids matching h's leading dims."""
    if h.ndim == 3:
        b, s, d = h.shape
        out = aot_gather_add_kernel(h.reshape(b * s, d), table,
                                    ids.reshape(b * s), interpret=_interpret())
        return out.reshape(b, s, d)
    return aot_gather_add_kernel(h, table, ids, interpret=_interpret())


@jax.jit
def aot_gather_add_multitask(h, tables, task_ids, ids):
    """h: (b, s, d); tables: (n_tasks, V, d); task_ids: (b,); ids: (b, s)."""
    b, s, d = h.shape
    tids = jnp.broadcast_to(task_ids[:, None], (b, s)).reshape(b * s)
    out = aot_gather_add_multitask_kernel(
        h.reshape(b * s, d), tables, tids, ids.reshape(b * s),
        interpret=_interpret())
    return out.reshape(b, s, d)

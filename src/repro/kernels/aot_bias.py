"""Pallas TPU kernel for the paper's hot path: fused gather + add (Eq. 1).

``H + P[x]`` — the naive XLA lowering materializes the gathered rows
``P[x]`` (T x d) in HBM before the add (2 extra HBM round-trips of the
activation size). This kernel uses **scalar prefetch**: the token ids are
prefetched into SMEM, the table stays in HBM, and each grid step DMAs the
rows its tokens need straight into VMEM and adds them in-register — one
pass over ``H``, zero intermediate HBM traffic. This is the TPU-native
version of the paper's "only rows of P are placed in GPU memory".

Mosaic tiles the last two dimensions of every block by 8 rows (float32,
and bfloat16 in pairs), so a one-row block of ``H`` or of ``P`` does not
compile, and a manual row DMA out of a table whose width is not a
multiple of 128 (d = 960) is refused too. So the grid is ``(T / block_t,
block_t)``: the outer axis walks ``block_t``-token blocks of ``H`` and the
output (``T`` is padded up to a multiple; pad rows are cut off), the inner
axis walks the block's tokens, and each step's table block is the aligned
``GROUP``-row group that holds its token's row. A one-hot select picks the
row out exactly and adds it into that token's output row.

A multi-task variant indexes ``(task_id, token_id)`` — the paper's
multi-task batched inference with one fused kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 16      # table rows per block: a multiple of every dtype's tiling


def _add_row(h_ref, rows, off, o_ref):
    """Output row ``program_id(1)`` = its ``H`` row + row ``off`` of the
    (group, d) table block ``rows``; the other rows keep their value."""
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == off
    row = jnp.where(sel, rows.astype(jnp.float32), 0.0).sum(axis=0,
                                                            keepdims=True)
    mine = (jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
            == pl.program_id(1))
    o_ref[...] = jnp.where(mine, h_ref[...] + row.astype(o_ref.dtype),
                           o_ref[...])


def _kernel(ids_ref, h_ref, p_ref, o_ref, *, block_t, group):
    tok = pl.program_id(0) * block_t + pl.program_id(1)
    _add_row(h_ref, p_ref[...], ids_ref[tok] % group, o_ref)


def _kernel_mt(sc_ref, h_ref, p_ref, o_ref, *, block_t, group):
    tok = pl.program_id(0) * block_t + pl.program_id(1)
    _add_row(h_ref, p_ref[...], sc_ref[1, tok] % group, o_ref)


def _call(kernel, scalars, h, table, table_index, *, block_t, interpret,
          name):
    T, d = h.shape
    group = min(GROUP, table.shape[-2])
    pad = (-T) % block_t
    hp = jnp.pad(h, ((0, pad), (0, 0))) if pad else h
    sp = (jnp.pad(scalars, [(0, 0)] * (scalars.ndim - 1) + [(0, pad)])
          if pad else scalars)
    tok_block = pl.BlockSpec((block_t, d), lambda b, i, s: (b, 0))
    table_block = pl.BlockSpec(
        (None,) * (table.ndim - 2) + (group, d),
        lambda b, i, s: table_index(s, b * block_t + i, group))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((T + pad) // block_t, block_t),
        in_specs=[tok_block, table_block],
        out_specs=tok_block,
    )
    out = pl.pallas_call(
        functools.partial(kernel, block_t=block_t, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + pad, d), h.dtype),
        interpret=interpret,
        name=name,
    )(sp, hp, table)
    return out[:T] if pad else out


def aot_gather_add_kernel(h, table, ids, *, block_t: int = 16,
                          interpret=False):
    """h: (T, d); table: (V, d); ids: (T,) int32 -> (T, d).

    ids are scalar-prefetched so each step's index_map names the row group
    that holds ``P[ids[t]]``.
    """
    return _call(_kernel, ids.astype(jnp.int32), h, table,
                 lambda s, tok, group: (s[tok] // group, 0),
                 block_t=block_t, interpret=interpret, name="aot_gather_add")


def aot_gather_add_multitask_kernel(h, tables, task_ids, ids, *,
                                    block_t: int = 16, interpret=False):
    """h: (T, d); tables: (n_tasks, V, d); task_ids/ids: (T,) -> (T, d).

    One scalar-prefetch array carries (task, token) pairs; the table
    index_map picks the (task, row group) block per step.
    """
    sc = jnp.stack([task_ids.astype(jnp.int32), ids.astype(jnp.int32)], axis=0)
    return _call(_kernel_mt, sc, h, tables,
                 lambda s, tok, group: (s[0, tok], s[1, tok] // group, 0),
                 block_t=block_t, interpret=interpret,
                 name="aot_gather_add_multitask")

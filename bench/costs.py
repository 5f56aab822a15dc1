"""Operations and bytes that the serve path's work needs, from shapes.

These count what the algorithm needs, not what a given kernel moves, so
a better kernel never makes them stale:

* the ragged paged attention of one tick reads each slot's live K/V
  pages once per layer, plus the live tokens' queries and outputs, and
  spends 4·heads·head_dim FLOPs per (token, visible key) pair;
* the model step spends 2 FLOPs per weight per live token in every
  projection and MLP matrix, the attention above, and the tied head for
  each slot whose logits the tick reports. Dead padding needs nothing.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    block_size: int
    kv_bytes: int = 2           # bytes per K/V/q element as served (bf16)

    @classmethod
    def of(cls, cfg, block_size: int, kv_bytes: int = 2) -> "Dims":
        return cls(cfg.num_layers, cfg.d_model, cfg.num_heads,
                   cfg.num_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size,
                   block_size, kv_bytes)


def ragged_attention_need(dims: Dims, token_rows, token_pos):
    """(FLOPs, bytes) the tick's ragged attention needs over all layers."""
    pos = np.asarray(token_pos)
    live = pos >= 0
    rows = np.asarray(token_rows)[live]
    pos = pos[live].astype(np.int64)
    qk = dims.heads * dims.head_dim
    flops = 4 * qk * int((pos + 1).sum())
    kv_rows = 0
    for slot in np.unique(rows):
        ctx = int(pos[rows == slot].max()) + 1
        kv_rows += -(-ctx // dims.block_size) * dims.block_size
    kv = 2 * kv_rows * dims.kv_heads * dims.head_dim * dims.kv_bytes
    q_out = 2 * len(pos) * qk * dims.kv_bytes
    return dims.layers * flops, dims.layers * (kv + q_out)


def reporting_slots(token_rows, token_pos, logit_idx) -> int:
    """Slots whose reported logits row is their own last live token this
    tick: decode rows, and prefills whose final chunk lands."""
    rows, pos = np.asarray(token_rows), np.asarray(token_pos)
    n = 0
    for slot, t in enumerate(np.asarray(logit_idx)):
        if pos[t] < 0 or rows[t] != slot:
            continue
        mine = np.nonzero((rows == slot) & (pos >= 0))[0]
        n += int(mine[-1] == t)
    return n


def step_flops(dims: Dims, token_rows, token_pos, logit_idx) -> int:
    """Model FLOPs the tick's live tokens need (dead padding excluded,
    attention over each token's live context included)."""
    n_live = int(np.count_nonzero(np.asarray(token_pos) >= 0))
    d, qk = dims.d_model, dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    per_token_layer = 2 * (d * qk + 2 * d * kv + qk * d + 3 * d * dims.d_ff)
    attn, _ = ragged_attention_need(dims, token_rows, token_pos)
    head = 2 * d * dims.vocab * reporting_slots(token_rows, token_pos,
                                                logit_idx)
    return dims.layers * per_token_layer * n_live + attn + head

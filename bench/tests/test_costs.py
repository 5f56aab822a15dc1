"""The cost functions against a count made by hand at a tiny shape."""
import numpy as np

from bench.costs import (Dims, ragged_attention_need, reporting_slots,
                         step_flops)

DIMS = Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
            vocab=32, block_size=4)
# slot 0 decodes at position 5; slot 1 runs a 3-token chunk at 0..2; one
# dead padding token
ROWS = np.array([0, 1, 1, 1, 0])
POS = np.array([5, 0, 1, 2, -1])
LOGIT_IDX = np.array([0, 3, 0])


def test_ragged_attention_need_by_hand():
    flops, byts = ragged_attention_need(DIMS, ROWS, POS)
    # 4 * heads * head_dim per visible key: keys seen 6 + 1 + 2 + 3 = 12
    assert flops == 2 * (4 * 2 * 4 * 12)
    # slot 0 reads 6 rows -> 2 pages of 4; slot 1 reads 3 rows -> 1 page:
    # 12 rows of K and V, 1 head of 4, bf16; plus q and out of 4 tokens
    assert byts == 2 * (2 * 12 * 1 * 4 * 2 + 2 * 4 * 2 * 4 * 2)


def test_reporting_slots():
    # slot 0's decode token and slot 1's last chunk token report; slot 2
    # points at row 0, which is slot 0's
    assert reporting_slots(ROWS, POS, LOGIT_IDX) == 2
    # a prefill mid-prompt reports nothing: its row is not its last token
    assert reporting_slots(np.array([1, 1]), np.array([0, 1]),
                           np.array([0, 0])) == 0


def test_step_flops_by_hand():
    per_token_layer = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    attn = 2 * (4 * 2 * 4 * 12)
    head = 2 * 8 * 32 * 2
    assert step_flops(DIMS, ROWS, POS, LOGIT_IDX) == (
        2 * 4 * per_token_layer + attn + head)


def test_dead_tokens_need_nothing():
    pos = np.full(4, -1)
    assert ragged_attention_need(DIMS, np.zeros(4, int), pos) == (0, 0)
    assert step_flops(DIMS, np.zeros(4, int), pos, np.zeros(3, int)) == 0

"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program's serve step for the test only (the
harness has no switch for it); the harness then runs as usual, and the
reference comparison must catch it. A cell on one chip has no exchange
between chips to leave out, so that fault is not among them.
"""
import numpy as np
import pytest

import tiny
from repro.serve.engine import ServeEngine

SOUND = ServeEngine.serve_step


def state_unchanged(self, tokens, rows, pos, idx, cache, *rest):
    """The step returns the pool it was given: no new K/V is kept."""
    toks, logits, _, finite = SOUND(self, tokens, rows, pos, idx, cache,
                                    *rest)
    return toks, logits, cache, finite


def half_batch(self, tokens, rows, pos, idx, cache, *rest):
    """The second half of the packed batch is left out (dead)."""
    pos = np.array(pos)
    pos[len(pos) // 2:] = -1
    return SOUND(self, tokens, rows, pos, idx, cache, *rest)


def token_altered(self, *args):
    """Every slot's token is changed where the step produces it."""
    toks, logits, cache, finite = SOUND(self, *args)
    vocab = logits.shape[-1]
    return (toks + 1) % vocab, logits, cache, finite


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("cell", ["tiny.tiny-chat", "tiny.tiny-backlog"])
def test_fault_is_not_correct(root, monkeypatch, fault, cell):
    monkeypatch.setattr(ServeEngine, "serve_step", fault)
    res = tiny.run_cell(root, cell, 21, 2.0)
    assert res["correct"] is False, res["checks"]

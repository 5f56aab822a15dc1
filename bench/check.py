"""The comparison that decides ``correct``.

After the window has closed (and the program's pool is freed), a sample
of the greedy requests the window finished, drawn from the seed and
always holding the longest, is run through the plain reference once
each: prompt plus served tokens, with the logits read at every position
that produced a served token. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position (``max_gap``). Greedy decoding at the configuration's
precision puts it near 0; a wrong token, a wrong task's bias, a lost KV
page or a lower precision puts it far above. Sampled requests are not
compared: a sampled token need not be the best.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench import reference


def sample(served: dict, n: int, seed: int) -> List[object]:
    """Up to ``n`` finished greedy requests: the longest (prompt plus
    output) first, then the rest drawn from the seed, alternating tasks
    so both tasks' tables are compared."""
    done = [s for s in served.values()
            if s.spec.temperature == 0.0 and len(s.req.out) == s.spec.max_new
            and s.req.state == "finished"]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.spec.prompt) + s.spec.max_new,
                                       s.spec.rid))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    by_task = {}
    for s in rest:
        by_task.setdefault(s.spec.task, []).append(s)
    picked = [longest]
    queues = [by_task[t] for t in sorted(by_task)]
    while len(picked) < n and any(queues):
        for q in queues:
            if q and len(picked) < n:
                picked.append(q.pop(0))
    return picked


def _inputs(s, max_len: int, k: int):
    prompt = np.asarray(s.spec.prompt, np.int32)
    out = np.asarray(s.req.out, np.int32)
    seq = np.zeros(max_len, np.int32)
    full = np.concatenate([prompt, out[:-1]])
    seq[:len(full)] = full
    pos = np.zeros(k, np.int32)
    pos[:len(out)] = len(prompt) - 1 + np.arange(len(out))
    return seq, pos, out


def gaps(params, table, cfile: dict, picked, k: int, quant=None):
    """Per request, the widest gap below the reference's best: of the
    served token, or (``quant`` set) of the token the control puts
    first at each position."""
    import jax.numpy as jnp
    arch = reference.arch_items(cfile)
    max_len = cfile["serve"]["max_len"]
    out = []
    for s in picked:
        seq, pos, toks = _inputs(s, max_len, k)
        args = (params, table, jnp.asarray(seq), jnp.int32(s.spec.task),
                jnp.asarray(pos))
        ref = np.asarray(reference.logits_at(*args, arch_items=arch),
                         np.float64)[:len(toks)]
        if quant is not None:
            ctl = np.asarray(reference.logits_at(*args, arch_items=arch,
                                                 quant=quant))[:len(toks)]
            toks = ctl.argmax(-1)
        got = ref[np.arange(len(toks)), toks]
        out.append(float((ref.max(-1) - got).max()))
    return out

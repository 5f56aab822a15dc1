"""Unified ragged prefill+decode: kernel parity and the one-call tick.

Contracts under test:

* the Pallas ``ragged_paged_attention_kernel`` matches the pure-jnp
  ``ragged_paged_attention_ref`` oracle in interpret mode across every
  batch composition a scheduler tick can pack — decode-only, prefill-only,
  mixed, dead padding tokens, chunks straddling page boundaries;
* the XLA fallback (``layers.ragged_paged_attention_decode``) obeys the
  same oracle, and collapses to the paged decode computation per token;
* ``model.mixed_step`` with decode tokens is BITWISE the paged
  ``decode_step``, and a chunked ragged prefill reproduces the
  whole-prompt prefill logits;
* a scheduler tick with prefill chunks and decode rows in flight issues
  exactly ONE jitted device call — including with SEVERAL prompts
  chunking concurrently (multi-prefill packing) — and the unified tick's
  token streams are identical to the whole-prompt two-call path, to
  serial single-prefill admission, and to static per-request decode;
* the per-tick chunk budget splits shortest-remaining-first, so a short
  prompt overtakes a long one mid-prefill (no prefill head-of-line
  blocking).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aot as A
from repro.kernels import ref as R
from repro.kernels.decode_attention import (_ragged_blocks, _run_metadata,
                                            ragged_paged_attention_kernel,
                                            ragged_plan)
from repro.models.layers import ragged_paged_attention_decode
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)


def _tables_for(rng, ns, bs, nb, depths):
    """Non-overlapping random page assignment covering each slot's depth."""
    npages = max(1, max(-(-int(d) // bs) for d in depths))
    bt = np.zeros((ns, npages), np.int32)
    avail = list(rng.permutation(np.arange(1, nb)))
    for i in range(ns):
        for j in range(-(-int(depths[i]) // bs)):
            bt[i, j] = avail.pop()
    return jnp.asarray(bt)


# every composition a tick can pack: (token_rows, token_pos) over 4 slots
# (a token at pos p attends to its slot's kv [0, p]; -1 = dead padding),
# and the widths it runs at: (heads, kv heads, head_dim, block_size,
# pool pages)
SMALL = (4, 2, 16, 8, 40)
SMOLLM = (6, 2, 64, 16, 96)       # smollm-360m's g=3, hd 64, 16-token pages
OLMO = (2, 2, 128, 16, 40)        # olmo-1b's g=1, hd 128
COMPOSITIONS = {
    "decode_only": ([0, 1, 2, 3], [13, 5, 0, 26], SMALL),
    "prefill_only": ([1, 1, 1, 1, 1, 1], [0, 1, 2, 3, 4, 5], SMALL),
    "mixed": ([0, 2, 1, 1, 1, 1, 3], [13, 3, 5, 6, 7, 8, 0], SMALL),
    "dead_tokens": ([1, 0, 0, 0], [9, -1, -1, -1], SMALL),
    "straddle_pages": ([0, 2, 2, 2, 2, 2, 2, 3], [7, 5, 6, 7, 8, 9, 10, 30],
                       SMALL),
    # several prefills' chunks packed in one tick (the multi-prefill
    # scheduler), sharing the budget around decode rows and dead padding
    "two_chunks": ([0, 1, 1, 1, 2, 2, 3], [13, 0, 1, 2, 4, 5, 26], SMALL),
    "three_chunks_dead": ([1, 1, 0, 2, 2, 3, 3, 0],
                          [3, 4, 9, 0, 1, 16, 17, -1], SMALL),
    # a 200-token chunk that starts mid-block (after a decode row) and
    # runs on past the first of two 112-token query blocks
    "run_over_blocks": ([0] + [1] * 200, [40] + list(range(20, 220)),
                        SMOLLM),
    # depths of 700 and 693: two KV steps, of 32 pages and of 12 pages
    # whose last is partial, beside a shallow run
    "deep_steps": ([0, 1, 1, 1, 2], [699, 690, 691, 692, 40], SMOLLM),
    # decode runs of four slots in one query block
    "decode_shared_block": ([3, 1, 0, 2], [33, 250, 17, 5], SMOLLM),
    "all_dead": ([0, 0, 0, 0], [-1, -1, -1, -1], SMOLLM),
    # g=1: slot 0's 19 pages take two KV steps of 16 pages
    "olmo_mixed": ([0, 1, 2, 2, 2, 3, 0], [299, 3, 30, 31, 32, 0, -1], OLMO),
}


@pytest.mark.parametrize("comp", sorted(COMPOSITIONS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_kernel_matches_oracle(rng, comp, dtype):
    rows, pos, (h, kvh, hd, bs, nb) = COMPOSITIONS[comp]
    ns, T = 4, len(rows)
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    q, kp, vp = t(T, h, hd), t(nb, bs, kvh, hd), t(nb, bs, kvh, hd)
    rows_j = jnp.asarray(rows, jnp.int32)
    pos_j = jnp.asarray(pos, jnp.int32)
    depths = np.zeros(ns, np.int64)
    for r, p in zip(rows, pos):
        depths[r] = max(depths[r], p + 1)
    bt = _tables_for(rng, ns, bs, nb, depths)
    ref = R.ragged_paged_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), bt, rows_j, pos_j)
    out = ragged_paged_attention_kernel(q, kp, vp, bt, rows_j, pos_j,
                                        interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out, np.float32),
                               atol=tol, rtol=tol,
                               err_msg=f"ragged kernel diverged ({comp})")
    dead = np.asarray(pos) < 0
    assert np.all(np.asarray(out)[dead] == 0), "dead tokens must be zeros"


def test_ragged_xla_fallback_matches_oracle(rng):
    ns, h, kvh, hd, bs, nb = 4, 4, 2, 16, 8, 40
    rows = [0, 1, 1, 1, 2, 0]
    pos = [17, 3, 4, 5, 11, -1]
    T = len(rows)
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    q, kp, vp = t(T, 1, h, hd), t(nb, bs, kvh, hd), t(nb, bs, kvh, hd)
    rows_j, pos_j = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
    depths = np.zeros(ns, np.int64)
    for r, p in zip(rows, pos):
        depths[r] = max(depths[r], p + 1)
    bt = _tables_for(rng, ns, bs, nb, depths)
    ref = R.ragged_paged_attention_ref(q[:, 0], kp, vp, bt, rows_j, pos_j)
    out = ragged_paged_attention_decode(q, kp, vp, bt, rows_j, pos_j)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out[:, 0]),
                               atol=2e-5, rtol=2e-5)


def test_ragged_decode_token_equals_paged_decode(rng):
    """A decode token (one per slot, pos = depth - 1) reproduces the paged
    flash-decode oracle at cur_len = pos + 1 — the ragged kernel strictly
    generalizes the paged decode contract."""
    ns, h, kvh, hd, bs, nb = 3, 4, 2, 16, 8, 24
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    q, kp, vp = t(ns, h, hd), t(nb, bs, kvh, hd), t(nb, bs, kvh, hd)
    pos = jnp.asarray([14, 7, 0], jnp.int32)
    rows = jnp.arange(ns, dtype=jnp.int32)
    bt = _tables_for(rng, ns, bs, nb, np.asarray(pos) + 1)
    ragged = R.ragged_paged_attention_ref(q, kp, vp, bt, rows, pos)
    paged = R.paged_decode_attention_ref(q, kp, vp, bt, pos + 1)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(paged),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# ragged_plan: what the kernel walks
# ---------------------------------------------------------------------------

# smollm-360m as the benchmark serves it: 15 heads over 5 KV heads of 64,
# 16-token pages, 128 page slots (max_len 2048)
SMOLLM_PLAN = dict(block_size=16, kv_heads=5, q_per_kv=3, head_dim=64,
                   npages=128)


def test_ragged_plan_counts_decode_tick():
    """T=8, one decode token per slot: eight one-token runs in one query
    block. A 64-lane page is 8 rows of 128 lanes, so a KV step is 32
    pages (512 keys): depths 300 ... 1900 take 19, 29, 38, 50, 63, 82,
    100, 119 pages, i.e. 1+1+2+2+2+3+4+4 = 19 steps per KV head, where
    the page-slot grid it replaces took 8 * 5 * 128 = 5120 steps."""
    depths = [300, 450, 600, 800, 1000, 1300, 1600, 1900]
    assert _ragged_blocks(8, 3, 64, 16, 128) == (8, 1, 32)
    runs, steps, q_rows = ragged_plan(list(range(8)),
                                      [d - 1 for d in depths], **SMOLLM_PLAN)
    assert runs == 8
    assert steps == 5 * 19
    assert q_rows == 5 * 8 * 3


def test_ragged_plan_counts_chunk_tick():
    """T=263: 7 decode rows, then a 256-token chunk at positions
    1024-1279. Three balanced query blocks of 96 tokens (288 query rows):
    the seven decode runs plus the chunk's first 89 tokens, then 96 and
    71 chunk tokens: 10 runs. The chunk's pieces reach depths 1113, 1209
    and 1280 (70, 76, 80 pages: 3 steps each); the decode rows at depths
    300 ... 1600 take 1+1+2+2+2+3+4 = 15 steps. The page-slot grid it
    replaces took 263 * 5 * 128 = 168,320 steps."""
    dec = [300, 450, 600, 800, 1000, 1300, 1600]
    rows = list(range(7)) + [7] * 256
    pos = [d - 1 for d in dec] + list(range(1024, 1280))
    assert _ragged_blocks(263, 3, 64, 16, 128)[:2] == (96, 3)
    runs, steps, q_rows = ragged_plan(rows, pos, **SMOLLM_PLAN)
    assert runs == 10
    assert q_rows == 5 * 288 * 3
    _, _, depth, first = _run_metadata(np, np.asarray(rows),
                                       np.asarray(pos), 96, 288)
    np.testing.assert_array_equal(first, [0, 8, 9, 10])
    np.testing.assert_array_equal(depth[7:runs], [1113, 1209, 1280])
    assert steps == 5 * (15 + 3 * 3)


def test_ragged_plan_all_dead_walks_nothing():
    # the programs still hold the block's three dead tokens' query rows
    assert ragged_plan([0, 1, 2], [-1, -1, -1], **SMOLLM_PLAN) == (
        0, 0, 5 * 3 * 3)


# olmo-1b as the benchmark serves it: 16 heads over 16 KV heads of 128
# (g=1: up to 384-token query blocks; a 128-lane page is 16 rows, so a
# KV step is 16 pages), 16-token pages, 128 page slots
OLMO_PLAN = dict(block_size=16, kv_heads=16, q_per_kv=1, head_dim=128,
                 npages=128)
DECODE_DEPTHS = [300, 450, 600, 800, 1000, 1300, 1600, 1900]


def _tick(n_decode, chunk_at=None, chunk=0, dead=0):
    """A packed tick: n_decode decode rows at DECODE_DEPTHS, then a chunk
    of slot 7 at positions chunk_at.., then dead padding."""
    rows = list(range(n_decode)) + [7] * chunk + [0] * dead
    pos = ([d - 1 for d in DECODE_DEPTHS[:n_decode]]
           + list(range(chunk_at or 0, (chunk_at or 0) + chunk))
           + [-1] * dead)
    return rows, pos


# (plan, tick, (tokens per block, blocks), runs, KV steps per KV head,
# query rows by hand = KV heads x blocks x block tokens x q_per_kv)
QUERY_ROWS = {
    # 8 decode rows: one block of exactly 8 tokens, 8 rows of 3 heads
    "smollm_decode": (SMOLLM_PLAN, _tick(8), (8, 1), 8, 19, 5 * 8 * 3),
    # 5 decode rows and 3 free slots: the dead tokens are held too
    "smollm_decode_free_slots": (SMOLLM_PLAN, _tick(5, dead=3), (8, 1), 5,
                                 1 + 1 + 2 + 2 + 2, 5 * 8 * 3),
    # 7 decode + 256 chunk: three blocks of 96 (288 tokens, 25 padding)
    "smollm_chunk": (SMOLLM_PLAN, _tick(7, 1024, 256), (96, 3), 10, 24,
                     5 * 288 * 3),
    # g=1: the same decode tick is one block of 8 one-head rows per KV
    # head; depths take 19 ... 119 pages, 2+2+3+4+4+6+7+8 steps of 16
    "olmo_decode": (OLMO_PLAN, _tick(8), (8, 1), 8, 36, 16 * 8 * 1),
    # 7 decode + 256 chunk: 263 tokens fit one 384-token block, no
    # rounding; the chunk reaches depth 1280 (80 pages, 5 steps)
    "olmo_chunk": (OLMO_PLAN, _tick(7, 1024, 256), (263, 1), 8, 28 + 5,
                   16 * 263 * 1),
    # 400 tokens: two balanced blocks of 208 (416 tokens, 16 padding);
    # the chunk splits at token 208, its pieces reach depths 201 and 393
    "olmo_two_blocks": (OLMO_PLAN, _tick(7, 0, 393), (208, 2), 9,
                        28 + 1 + 2, 16 * 416 * 1),
}


@pytest.mark.parametrize("case", sorted(QUERY_ROWS))
def test_ragged_plan_counts_query_rows(case):
    """The query rows the kernel's programs hold in one layer, counted
    by hand for smollm-360m's (g=3, hd 64) and olmo-1b's (g=1, hd 128)
    blocks, with the runs and KV steps of the same ticks."""
    plan, (rows, pos), blocks, want_runs, steps_per_head, want_rows = \
        QUERY_ROWS[case]
    assert _ragged_blocks(len(pos), plan["q_per_kv"], plan["head_dim"],
                          plan["block_size"], plan["npages"])[:2] == blocks
    runs, steps, q_rows = ragged_plan(rows, pos, **plan)
    assert (runs, steps, q_rows) == (
        want_runs, plan["kv_heads"] * steps_per_head, want_rows)
    live = sum(p >= 0 for p in pos)
    assert live * plan["kv_heads"] * plan["q_per_kv"] <= q_rows


def _runs_by_hand(rows, pos, tq, t_pad):
    """Each token's run, each run's slot and depth, each block's first
    run: a token starts a run unless it follows a live token of its own
    slot in the same query block."""
    run, slot, depth = [], [], []
    for t in range(t_pad):
        p = pos[t] if t < len(pos) else -1
        if p < 0:
            run.append(-1)
            continue
        if t % tq and run[-1] >= 0 and rows[t - 1] == rows[t]:
            depth[-1] = max(depth[-1], p + 1)
        else:
            slot.append(rows[t])
            depth.append(p + 1)
        run.append(len(slot) - 1)
    first = [len({r for r in run[:b * tq] if r >= 0})
             for b in range(t_pad // tq + 1)]
    return run, slot, depth, first


@pytest.mark.parametrize("comp", sorted(COMPOSITIONS))
def test_ragged_plan_matches_kernel_metadata(comp):
    """The runs the host plan counts (numpy) are the ones the kernel's
    wrapper derives inside the jit (jax.numpy), and the ones found by
    hand: each token's run, each run's slot and depth, and each query
    block's first run."""
    rows, pos, (h, kvh, hd, bs, _) = COMPOSITIONS[comp]
    tq, nqb, _ = _ragged_blocks(len(rows), h // kvh, hd, bs, 8)
    t_pad = tq * nqb
    run, slot, depth, first = _runs_by_hand(rows, pos, tq, t_pad)
    host = _run_metadata(np, np.asarray(rows), np.asarray(pos), tq, t_pad)
    dev = jax.jit(_run_metadata, static_argnums=(0, 3, 4))(
        jnp, jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32),
        tq, t_pad)
    n = len(slot)
    for got in (host, dev):
        np.testing.assert_array_equal(np.asarray(got[0]), run)
        np.testing.assert_array_equal(np.asarray(got[1])[:n], slot)
        np.testing.assert_array_equal(np.asarray(got[2])[:n], depth)
        np.testing.assert_array_equal(np.asarray(got[3]), first)
    assert ragged_plan(rows, pos, block_size=bs, kv_heads=kvh,
                       q_per_kv=h // kvh, head_dim=hd, npages=8)[0] == n


# ---------------------------------------------------------------------------
# model.mixed_step parity
# ---------------------------------------------------------------------------

def _paged_from_contiguous(rng, model, cache, depths, bs_page, nblocks,
                           max_len=16):
    """Scatter a contiguous prefill cache into scrambled pool pages."""
    b = len(depths)
    npages = max_len // bs_page
    bt = np.zeros((b, npages), np.int32)
    avail = list(rng.permutation(np.arange(1, nblocks)))
    paged = model.init_paged_cache(nblocks, bs_page)
    for i in range(b):
        for j in range(-(-int(depths[i]) // bs_page)):
            bt[i, j] = avail.pop()
    for gi in range(len(paged)):
        for u in paged[gi]:
            for nm in ("k", "v"):
                pool = np.array(paged[gi][u][nm])
                src = np.asarray(cache[gi][u][nm])
                for i in range(b):
                    for j in range(-(-int(depths[i]) // bs_page)):
                        lo = j * bs_page
                        hi = min(lo + bs_page, int(depths[i]))
                        pool[:, bt[i, j], :hi - lo] = src[:, i, lo:hi]
                paged[gi][u][nm] = jnp.asarray(pool)
    return paged, jnp.asarray(bt)


def test_mixed_step_decode_tokens_bitwise_decode_step(rng, tiny_lm):
    """Decode-token mixed_step logits == paged decode_step logits, bitwise."""
    cfg, model, params = tiny_lm
    b, s, bs_page, nblocks = 3, 8, 4, 14
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    _, cache, _ = model.prefill(params, {"tokens": toks}, max_len=16)
    depths = np.asarray([8, 5, 2], np.int32)
    paged, bt = _paged_from_contiguous(rng, model, cache, depths, bs_page,
                                       nblocks)
    step_tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    pos = jnp.asarray(depths)
    lg_dec, _ = model.decode_step(params, step_tok, pos, paged,
                                  block_tables=bt)
    lg_mix, _ = model.mixed_step(params, step_tok,
                                 jnp.arange(b, dtype=jnp.int32), pos, paged,
                                 block_tables=bt,
                                 logit_idx=jnp.arange(b, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_dec[:, -1]),
                                  np.asarray(lg_mix))


def test_mixed_step_chunked_prefill_matches_whole_prefill(rng, tiny_lm):
    """Streaming a prompt through mixed_step in packed chunks (including a
    page-straddling final chunk) reproduces the whole-prompt prefill's
    last-token logits."""
    cfg, model, params = tiny_lm
    bs_page, nblocks, qw, plen = 4, 14, 8, 11
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, plen)), jnp.int32)
    lg_full, _, _ = model.prefill(params, {"tokens": prompt}, max_len=16)
    paged = model.init_paged_cache(nblocks, bs_page)
    npages = -(-plen // bs_page)
    bt = np.zeros((1, 16 // bs_page), np.int32)
    bt[0, :npages] = 1 + rng.permutation(npages)
    btj = jnp.asarray(bt)
    lg = None
    for lo in range(0, plen, qw):
        n = min(lo + qw, plen) - lo
        tk = np.zeros((qw, 1), np.int32)
        tk[:n, 0] = np.asarray(prompt)[0, lo:lo + n]
        pos = np.full(qw, -1, np.int32)
        pos[:n] = np.arange(lo, lo + n)
        lg, paged = model.mixed_step(
            params, jnp.asarray(tk), jnp.zeros(qw, jnp.int32),
            jnp.asarray(pos), paged, block_tables=btj,
            logit_idx=jnp.asarray([n - 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_full[0, -1]), np.asarray(lg[0]),
                               atol=2e-5, rtol=2e-5)


def test_mixed_step_pallas_matches_xla(rng, tiny_lm):
    """attn_impl='pallas' (ragged kernel, interpret on CPU) and the XLA
    gather fallback agree on a genuinely mixed packed batch."""
    from repro.models.model import Model, ModelOptions
    cfg, model, params = tiny_lm
    pmodel = Model(cfg, ModelOptions(chunk_q=8, chunk_kv=8,
                                     attn_impl="pallas"))
    b, s, bs_page, nblocks = 3, 8, 4, 20
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    _, cache, _ = model.prefill(params, {"tokens": toks}, max_len=16)
    depths = np.asarray([8, 4, 6], np.int32)
    paged, bt = _paged_from_contiguous(rng, model, cache, depths, bs_page,
                                       nblocks)
    # slot 0 decodes at depth 8; slot 1 runs a 4-token chunk on top of 4
    # resident; slot 2 idles; one dead padding token rides along
    tokens = np.zeros((6, 1), np.int32)
    tokens[:5, 0] = rng.integers(0, cfg.vocab_size, 5)
    rows = jnp.asarray([0, 1, 1, 1, 1, 0], jnp.int32)
    pos = jnp.asarray([8, 4, 5, 6, 7, -1], jnp.int32)
    lidx = jnp.asarray([0, 4, 0], jnp.int32)
    args = (params, jnp.asarray(tokens), rows, pos, paged)
    lg_x, _ = model.mixed_step(*args, block_tables=bt, logit_idx=lidx)
    lg_p, _ = pmodel.mixed_step(*args, block_tables=bt, logit_idx=lidx)
    np.testing.assert_allclose(np.asarray(lg_x), np.asarray(lg_p),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# scheduler: the one-call tick
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def test_unified_tick_is_one_dispatch(rng, mt_engine):
    """ACCEPTANCE: a tick with BOTH a prefill chunk and decode rows in
    flight costs exactly one jitted device call."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8))
    short = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 4)
                    .astype(np.int32), task_id=0, max_new_tokens=12)
    long = Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, 30)
                   .astype(np.int32), task_id=1, max_new_tokens=4)
    sched.submit(short)
    sched.step()                # short's whole prompt is one chunk
    sched.submit(long)
    sched.step()                # long starts chunking; short decodes
    assert sched._prefills and sched.running, (
        "setup failed: need a chunk and decode rows in the same tick")
    mixed_ticks = 0
    while sched._prefills and sched.running:
        before = eng.dispatches
        sched.step()
        assert eng.dispatches - before == 1, (
            "a mixed prefill-chunk + decode tick must be ONE device call")
        mixed_ticks += 1
    assert mixed_ticks >= 2, "workload never mixed chunk and decode work"
    sched.run()
    sched.pool.check_no_leaks()
    # and the streams stayed exact
    for req in (short, long):
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(np.asarray(req.out), ref)


def test_decode_only_tick_is_one_dispatch(rng, mt_engine):
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8))
    for i in range(2):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
            task_id=i, max_new_tokens=6))
    while sched.queue or sched._prefills:
        sched.step()
    before = eng.dispatches
    sched.step()                # pure decode tick
    assert eng.dispatches - before == 1
    sched.run()


def test_unified_vs_whole_prompt_token_parity(rng, mt_engine):
    """The unified chunked tick and the whole-prompt (separate prefill
    dispatch) paged path produce identical token streams — the old
    two-call tick's outputs survive the merge."""
    cfg, eng = mt_engine

    def mk():
        rr = np.random.default_rng(11)
        return [Request(
            rid=i,
            prompt=rr.integers(0, cfg.vocab_size,
                               int(rr.integers(3, 17))).astype(np.int32),
            task_id=int(rr.integers(0, 3)),
            max_new_tokens=int(rr.integers(1, 9))) for i in range(6)]

    outs = []
    for kw in (dict(prefill_chunk=8), dict()):
        reqs = mk()
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=3, bucket_min=8, kv_layout="paged", block_size=8, **kw))
        for r in reqs:
            sched.submit(r)
        sched.run()
        sched.pool.check_no_leaks()
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1], (
        "unified chunked tick diverged from whole-prompt admission")


def test_multi_prefill_one_dispatch_per_tick(rng, mt_engine):
    """ACCEPTANCE: with >= 2 prompts chunking concurrently (plus decode
    rows), every tick is still exactly ONE jitted device call —
    dispatches/ticks == 1.0 over the whole greedy workload."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=6, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8, max_prefills=3))
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 20 + 4 * i)
                    .astype(np.int32),
                    task_id=i % 3, max_new_tokens=4 + i) for i in range(4)]
    d0, t0 = eng.dispatches, sched.ticks
    for r in reqs:
        sched.submit(r)
    sched.step()
    assert len(sched._prefills) >= 2, (
        "setup failed: need >= 2 prefills in flight")
    sched.run()
    sched.pool.check_no_leaks()
    assert sched.peak_prefills >= 2
    ticks = sched.ticks - t0
    assert (eng.dispatches - d0) / ticks == 1.0, (
        f"{eng.dispatches - d0} dispatches over {ticks} ticks: "
        "multi-prefill packing must stay one device call per tick")
    for req in reqs:
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(np.asarray(req.out), ref)


def test_multi_prefill_bitwise_matches_serial_admission(rng, mt_engine):
    """ACCEPTANCE: packing several prefills per tick produces bitwise the
    token streams of serial admission (max_prefills=1, the old
    one-prefill-at-a-time scheduler)."""
    cfg, eng = mt_engine

    def mk():
        rr = np.random.default_rng(23)
        return [Request(
            rid=i,
            prompt=rr.integers(0, cfg.vocab_size,
                               int(rr.integers(3, 33))).astype(np.int32),
            task_id=int(rr.integers(0, 3)),
            max_new_tokens=int(rr.integers(1, 9))) for i in range(7)]

    outs = []
    for k in (4, 1):
        reqs = mk()
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=4, bucket_min=8, kv_layout="paged", block_size=8,
            prefill_chunk=8, max_prefills=k))
        for r in reqs:
            sched.submit(r)
        sched.run()
        sched.pool.check_no_leaks()
        if k > 1:
            assert sched.peak_prefills >= 2, (
                "setup failed: prefills never overlapped")
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1], (
        "multi-prefill packing diverged from serial single-prefill "
        "admission")


def test_budget_split_shortest_remaining_first(rng, mt_engine):
    """A short prompt arriving while a long prompt is mid-chunking takes
    the budget first and reaches its first token ahead of the long one —
    the head-of-line-blocking fix this PR exists for."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8, max_prefills=2))
    first_tick = {}

    def note(req, tok):
        first_tick.setdefault(req.rid, sched.ticks)

    long = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 40)
                   .astype(np.int32), task_id=0, max_new_tokens=4,
                   on_token=note)
    short = Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, 6)
                    .astype(np.int32), task_id=1, max_new_tokens=4,
                    on_token=note)
    sched.submit(long)
    sched.step()                # long starts chunking (5 ticks of work)
    sched.submit(short)
    sched.run()
    sched.pool.check_no_leaks()
    assert first_tick[1] < first_tick[0], (
        f"short prompt TTFT tick {first_tick[1]} not ahead of the long "
        f"prompt's {first_tick[0]}: budget split is not "
        "shortest-remaining-first")


def test_oldest_prefill_never_starved_by_short_stream(rng, mt_engine):
    """REGRESSION: a sustained stream of short prompts must not zero out
    a long in-flight prefill's budget share forever (it holds its claimed
    pages the whole time). The oldest prefill's guaranteed
    budget/max_prefills slice bounds its prefill at
    max_prefills * prompt / budget ticks regardless of arrival load."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=6, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8, max_prefills=2))
    long = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 40)
                   .astype(np.int32), task_id=0, max_new_tokens=2)
    sched.submit(long)
    sched.step()                    # long starts chunking (oldest prefill)
    # guaranteed slice = 8 // 2 = 4 tokens/tick -> <= 10 chunking ticks
    rid = 1
    for tick in range(14):
        sched.submit(Request(     # keep a short prompt always in flight
            rid=rid, prompt=rng.integers(0, cfg.vocab_size, 4)
            .astype(np.int32), task_id=rid % 3, max_new_tokens=2))
        rid += 1
        sched.step()
        if long.out:
            break
    assert long.out, (
        "long prefill starved: 14 ticks of short-prompt pressure and no "
        "first token (guaranteed budget slice not applied)")
    sched.run()
    sched.pool.check_no_leaks()
    ref = eng.generate(long.prompt[None], 2, np.asarray([0], np.int32))[0]
    np.testing.assert_array_equal(np.asarray(long.out), ref)


def test_chunked_prefill_no_temp_cache_copies(rng, mt_engine):
    """The chunked path must not route through write_prefill (the install
    copy) — chunk KV lands in the pool pages directly."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=2, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8))
    calls = []
    orig = sched.pool.write_prefill
    sched.pool.write_prefill = lambda *a, **k: (calls.append(1), orig(*a, **k))
    sched.submit(Request(
        rid=0, prompt=rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
        task_id=0, max_new_tokens=3))
    sched.run()
    assert not calls, "chunked prefill still copies through write_prefill"
    assert sched.prefill_chunks_run == 3    # 20 tokens / 8-chunk = 3 chunks

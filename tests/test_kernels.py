"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.aot_bias import (aot_gather_add_kernel,
                                    aot_gather_add_multitask_kernel)
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            round_kv_len)
from repro.kernels.flash_attention import flash_attention_kernel

SHAPES = [(2, 64, 4, 2, 16), (1, 48, 3, 1, 8), (2, 128, 2, 2, 32),
          (1, 32, 8, 8, 8)]


@pytest.mark.parametrize("b,s,h,kvh,hd", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=False),
                                dict(causal=True, window=20)],
                         ids=["causal", "full", "swa"])
def test_flash_attention(rng, b, s, h, kvh, hd, dtype, kw):
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    q, k, v = t(b, s, h, hd), t(b, s, kvh, hd), t(b, s, kvh, hd)
    ref = R.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32), **kw)
    out = flash_attention_kernel(q, k, v, block_q=16, block_k=16,
                                 interpret=True, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,h,kvh,hd,S,cur",
                         [(2, 4, 2, 16, 64, 37), (1, 8, 1, 32, 128, 128),
                          (3, 2, 2, 8, 40, 1), (1, 4, 4, 16, 96, 50)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(rng, b, h, kvh, hd, S, cur, dtype):
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    q, kc, vc = t(b, h, hd), t(b, S, kvh, hd), t(b, S, kvh, hd)
    ref = R.decode_attention_ref(q.astype(jnp.float32), kc.astype(jnp.float32),
                                 vc.astype(jnp.float32), cur)
    out = decode_attention_kernel(q, kc, vc, jnp.int32(cur), block_k=16,
                                  interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,h,kvh,hd,S", [(4, 4, 2, 16, 64), (3, 2, 2, 8, 40),
                                          (2, 8, 1, 32, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_ragged_lens(rng, b, h, kvh, hd, S, dtype):
    """Per-row cur_len vector (the continuous-batching serve path)."""
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    q, kc, vc = t(b, h, hd), t(b, S, kvh, hd), t(b, S, kvh, hd)
    lens = jnp.asarray(rng.integers(1, S + 1, (b,)), jnp.int32)
    ref = R.decode_attention_ref(q.astype(jnp.float32), kc.astype(jnp.float32),
                                 vc.astype(jnp.float32), lens)
    out = decode_attention_kernel(q, kc, vc, lens, block_k=16, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out, np.float32),
                               atol=tol, rtol=tol)


def test_round_kv_len_no_pad():
    """Satellite: pool allocations rounded by round_kv_len never trigger the
    decode kernel's pad-and-copy fallback (S % block_k == 0 or S <= block_k,
    where block_k is capped at S)."""
    for n in (7, 48, 255, 256, 300, 1000, 4095, 33000):
        S = round_kv_len(n)
        assert n <= S < n + 256
        assert S % 256 == 0 or S <= 256
    assert round_kv_len(48) == 48          # small caches untouched
    assert round_kv_len(300) == 512


@pytest.mark.parametrize("T,V,d", [(16, 50, 32), (7, 13, 8), (64, 100, 128),
                                   (128, 1000, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aot_gather_add(rng, T, V, d, dtype):
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    h, tbl = t(T, d), t(V, d)
    ids = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    ref = R.aot_gather_add_ref(h, tbl, ids)
    out = aot_gather_add_kernel(h, tbl, ids, interpret=True)
    # gather+add is exact: same arithmetic, same dtype
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_aot_gather_add_multitask(rng):
    T, V, d, nt = 24, 40, 16, 3
    t = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    h, tbls = t(T, d), t(nt, V, d)
    ids = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    tids = jnp.asarray(rng.integers(0, nt, (T,)), jnp.int32)
    ref = R.aot_gather_add_multitask_ref(h, tbls, tids, ids)
    out = aot_gather_add_multitask_kernel(h, tbls, tids, ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_ops_wrappers(rng):
    from repro.kernels import ops
    h = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    tbl = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 40, (2, 8)), jnp.int32)
    out = ops.aot_gather_add(h, tbl, ids)
    ref = h + jnp.take(tbl, ids, axis=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

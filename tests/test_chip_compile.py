"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside the CPU backend, so the Pallas kernels
and the serve step can be compiled for a ``v5e:2x2`` topology that is only
described: Mosaic then refuses what interpret mode accepts (block shapes
off the (8, 128) tiling, too much VMEM), and XLA refuses a program that
does not fit in HBM. Nothing runs, so nothing here says anything about
results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file. All such compiles stay in this one file for that reason.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.aot_bias import (aot_gather_add_kernel,
                                    aot_gather_add_multitask_kernel)
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            ragged_paged_attention_kernel)
from repro.kernels.flash_attention import flash_attention_kernel

CFG = configs.get("smollm-360m")      # published widths
H, KVH, HD, D, V = (CFG.num_heads, CFG.num_kv_heads, CFG.head_dim,
                    CFG.d_model, CFG.vocab_size)
# what chip_smoke.py serves: 8 slots, max_len 2048 in 16-token pages,
# a 256-token prefill budget per tick
SLOTS, BLOCK, MAX_LEN, CHUNK = 8, 16, 2048, 256
NPAGES = MAX_LEN // BLOCK
NUM_BLOCKS = SLOTS * NPAGES + 1
# HBM one v5e program may use: 15.75 GiB, the chip's bytes_limit and the
# "of 15.75G hbm" of the compiler's RESOURCE_EXHAUSTED message
V5E_HBM = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Persistent cache off around these compiles: a described chip's
    programs are written to it but can never be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _compile(fn, sharding, *specs):
    compiled = jax.jit(fn).lower(*_shapes(sharding, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("width", [SLOTS, SLOTS - 1 + CHUNK])
def test_ragged_kernel_compiles(one_chip, width):
    bf16, i32 = jnp.bfloat16, jnp.int32
    page = ((NUM_BLOCKS, BLOCK, KVH, HD), bf16)
    _compile(ragged_paged_attention_kernel, one_chip,
             ((width, H, HD), bf16), page, page, ((SLOTS, NPAGES), i32),
             ((width,), i32), ((width,), i32))


def test_ragged_kernel_keeps_its_trace_name(one_chip):
    """A device trace names each op by its HLO instruction: the ragged
    kernel's stays ``%ragged_paged_attention.<n>``, the name the chip
    benchmark's kernel readings match."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    page = ((NUM_BLOCKS, BLOCK, KVH, HD), bf16)
    compiled = _compile(ragged_paged_attention_kernel, one_chip,
                        ((SLOTS, H, HD), bf16), page, page,
                        ((SLOTS, NPAGES), i32), ((SLOTS,), i32),
                        ((SLOTS,), i32))
    calls = [line.strip() for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(re.match(r"%ragged_paged_attention\.\d+ = ", c)
                         for c in calls), calls


@pytest.mark.parametrize("width", [SLOTS, SLOTS - 1 + CHUNK])
@pytest.mark.parametrize("arch", ["smollm-360m", "olmo-1b"])
def test_ragged_kernel_walk_compiles_at_model_widths(one_chip, arch, width):
    """The query-block kernel at each configuration's widths (smollm:
    15 heads over 5 KV heads of 64, pages packed two keys a 128-lane row;
    olmo: 16 KV heads of 128, one key a row): Mosaic takes its DMAs and
    its VMEM (it refuses a kernel over the default scoped VMEM limit,
    which the kernel does not raise), and the only TPU kernel in the
    program is ``%ragged_paged_attention.<n>``."""
    cfg = configs.get(arch)
    bf16, i32 = jnp.bfloat16, jnp.int32
    page = ((NUM_BLOCKS, BLOCK, cfg.num_kv_heads, cfg.head_dim), bf16)
    compiled = _compile(ragged_paged_attention_kernel, one_chip,
                        ((width, cfg.num_heads, cfg.head_dim), bf16), page,
                        page, ((SLOTS, NPAGES), i32), ((width,), i32),
                        ((width,), i32))
    calls = [line.strip() for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert re.match(r"%ragged_paged_attention\.\d+ = ", calls[0]), calls


def test_flash_kernel_compiles(one_chip):
    bf16 = jnp.bfloat16
    _compile(flash_attention_kernel, one_chip, ((1, 1024, H, HD), bf16),
             ((1, 1024, KVH, HD), bf16), ((1, 1024, KVH, HD), bf16))


def test_decode_kernel_compiles(one_chip):
    bf16 = jnp.bfloat16
    cache = ((SLOTS, MAX_LEN, KVH, HD), bf16)
    _compile(decode_attention_kernel, one_chip, ((SLOTS, H, HD), bf16),
             cache, cache, ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tokens", [SLOTS, SLOTS - 1 + CHUNK])
def test_aot_kernels_compile(one_chip, dtype, tokens):
    i32 = jnp.int32
    _compile(aot_gather_add_kernel, one_chip, ((tokens, D), dtype),
             ((V, D), dtype), ((tokens,), i32))
    _compile(aot_gather_add_multitask_kernel, one_chip, ((tokens, D), dtype),
             ((2, V, D), dtype), ((tokens,), i32), ((tokens,), i32))


@pytest.mark.parametrize("width", [SLOTS, SLOTS - 1 + CHUNK])
@pytest.mark.parametrize("arch", ["smollm-360m", "olmo-1b"])
def test_serve_step_compiles_and_fits(one_chip, monkeypatch, arch, width):
    """The engine's own greedy serve step, as the chip benchmark serves
    it: bf16 weights, KV pages and two tasks' fused tables, the ragged
    kernel through Mosaic, in one chip's HBM. The AoT bias rows are
    gathered from the stacked table ahead of the layer scan, so no op
    yields a layer's (tasks, V, d) table slice. smollm's whole-table
    relayout (d = 960 is not lane-aligned, so the table's default layout
    is vocab-minor) is the known remaining table cost and is not checked
    here; olmo-1b, lane-aligned, keeps no table-sized temporary."""
    from repro.kernels import ops
    from repro.models.model import Model, ModelOptions
    from repro.serve.engine import ServeConfig, ServeEngine
    # the wrappers pick interpret mode off the TPU, and this process's
    # backend is the CPU: steer them to Mosaic for this compile
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = configs.get(arch)
    bf16, tasks = jnp.bfloat16, 2
    model = Model(cfg, ModelOptions(compute_dtype=bf16, param_dtype=bf16,
                                    attn_impl="pallas", chunk_kv=MAX_LEN))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tables = {"table": jax.ShapeDtypeStruct(
        (cfg.num_layers, tasks, cfg.vocab_size, cfg.d_model), bf16)}
    eng = ServeEngine(model, params, ServeConfig(max_len=MAX_LEN),
                      fused_tasks=tables)
    shapes = eng.serve_step_shapes(model.paged_cache_specs(NUM_BLOCKS, BLOCK),
                                   SLOTS, NPAGES, width)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = eng._serve_greedy.lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_table = f"bf16[{tasks},{cfg.vocab_size},{cfg.d_model}]"
    assert layer_table not in text, [
        line.strip() for line in text.splitlines() if layer_table in line][:3]
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM, used
    if arch == "olmo-1b":
        assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes

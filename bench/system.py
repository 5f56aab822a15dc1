"""The system under test, built from a configuration file and a seed.

The benchmark makes the inputs the program serves: random weights and
fabricated AoT task tables, drawn from the seed on the device in one
jitted call, in the dtype they are served in. The program gets them
through its normal entry (``ServeEngine`` as ``build_engine`` builds it,
then ``ContinuousScheduler``); the plain reference (``bench/reference.py``)
reads the same arrays and nothing the program made.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

# the configuration file's keys (Hugging Face names) -> ArchConfig fields
_ARCH_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps",
}
# std of a fabricated AoT table entry: the scale of the rows that
# repro.launch.serve.demo_tasks fuses (their per-layer bias b2 is drawn
# at 0.03), here drawn per (layer, task, token) so that every row differs
TABLE_STD = 0.03
EMBED_STD = 0.02


def arch_config(cfile: dict):
    """The program's ArchConfig for a configuration file: the registry
    entry named ``registry``, with every size the file states."""
    from repro import configs
    base = configs.get(cfile["registry"])
    kw = {_ARCH_KEYS[k]: v for k, v in cfile["config"].items()
          if k in _ARCH_KEYS}
    kw["head_dim"] = kw["d_model"] // kw["num_heads"]
    kw["tie_embeddings"] = bool(cfile["config"].get("tie_word_embeddings",
                                                    True))
    return base.replace(**kw)


def seed_key(seed: int, stream: int):
    """A JAX key from a seed of any size: ``SeedSequence`` folds the whole
    integer, where ``PRNGKey`` would need it to fit 32 bits."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf_value(path, shape, key, dtype):
    import jax
    import jax.numpy as jnp
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    std = EMBED_STD if name == "tok" else 1.0 / np.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(model, n_tasks: int, seed: int):
    """Params (in the program's tree, ``model.opts.param_dtype``) and the
    stacked (L, tasks, V, d) AoT table, in one jitted call on the device.
    The table is drawn layer by layer inside the call, so no float32 copy
    of it is ever held."""
    import jax
    import jax.numpy as jnp
    cfg, dtype = model.cfg, model.opts.param_dtype
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)

    def make(key):
        kp, kt = jax.random.split(key)
        leaves = [_leaf_value(path, s.shape, jax.random.fold_in(kp, i), dtype)
                  for i, (path, s) in enumerate(paths)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)

        def layer(_, k):
            rows = jax.random.normal(
                k, (n_tasks, cfg.vocab_size, cfg.d_model), jnp.float32)
            return None, (rows * TABLE_STD).astype(dtype)
        _, table = jax.lax.scan(layer, None,
                                jax.random.split(kt, cfg.num_layers))
        return params, table

    params, table = jax.jit(make)(seed_key(seed, 0))
    return params, table


@dataclass
class System:
    cfile: dict
    cfg: Any            # ArchConfig
    model: Any          # repro Model
    params: Any
    table: Any          # (L, tasks, V, d)
    engine: Any         # ServeEngine
    sched_cfg: Any      # SchedulerConfig

    @property
    def slots(self) -> int:
        return self.sched_cfg.num_slots

    @property
    def chunk(self) -> int:
        return self.sched_cfg.prefill_chunk

    def widths(self):
        """The two packed widths a tick compiles to: decode-only, and
        decode plus the shared prefill budget."""
        return self.slots, self.slots - 1 + self.chunk

    def scheduler(self, obs=None):
        from repro.serve.scheduler import ContinuousScheduler
        return ContinuousScheduler(self.engine, self.sched_cfg, obs=obs)


def build(cfile: dict, seed: int) -> System:
    import jax
    import jax.numpy as jnp
    from repro.models.model import Model, ModelOptions
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.scheduler import SchedulerConfig
    serve = cfile["serve"]
    dtype = jnp.dtype(serve["dtype"])
    cfg = arch_config(cfile)
    opts = ModelOptions(compute_dtype=dtype, param_dtype=dtype,
                        attn_impl="pallas", chunk_q=64,
                        chunk_kv=serve["max_len"])
    model = Model(cfg, opts)
    params, table = make_weights(model, serve["tasks"], seed)
    jax.block_until_ready((params, table))
    engine = ServeEngine(model, params, ServeConfig(max_len=serve["max_len"]),
                         fused_tasks={"table": table})
    sched_cfg = SchedulerConfig(num_slots=serve["slots"],
                                block_size=serve["block_size"],
                                prefill_chunk=serve["prefill_chunk"])
    return System(cfile, cfg, model, params, table, engine, sched_cfg)


def warm_up(system: System, sched, sampled: bool) -> None:
    """Run every serve_step program the cell's traffic uses once, with
    dead tokens only (they write the scratch page; the step's new pool is
    dropped), through the engine's own entry: greedy at both packed
    widths, and sampled too where the mix samples, with the host-side
    key derivation a sampled request needs. A program the persistent
    cache holds is loaded, not compiled."""
    import jax
    from repro.serve.sampling import request_base_key
    pool, ns = sched.pool, system.slots
    modes = (0.0, 0.8) if sampled else (0.0,)
    if sampled:
        # a sampled request's stream key is made on the host by eager
        # jax.random calls; make them once here, not inside the window
        request_base_key(0, 0)
    for temp in modes:
        for width in system.widths():
            sample = (np.full(ns, temp, np.float32), np.zeros(ns, np.int32),
                      np.ones(ns, np.float32), np.zeros((ns, 2), np.uint32),
                      np.zeros(ns, np.int32))
            out = system.engine.serve_step(
                np.zeros((width, 1), np.int32), np.zeros(width, np.int32),
                np.full(width, -1, np.int32), np.zeros(ns, np.int32),
                pool.cache, pool.block_tables, np.zeros(width, np.int32),
                sample)
            jax.block_until_ready(out[2])

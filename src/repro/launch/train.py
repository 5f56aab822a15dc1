"""Production training launcher.

On a real TPU slice each host runs this under its own process id; here it
demonstrates the full wiring on whatever devices exist (CPU: 1 device, or
any mesh via --mesh). PEFT method, architecture, and shapes come from the
same registry the dry-run uses, so the path that compiles in the dry-run is
the path that trains.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --steps 50 --method aot
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.core import aot as aot_mod
from repro.core import peft as peft_mod
from repro.data.pipeline import LMStream
from repro.distrib import axes as axlib
from repro.distrib import sharding as shlib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import Model, ModelOptions
from repro.train.loop import TrainLoop
from repro.train.step import TrainConfig, make_train_step, split_train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--method", default="aot",
                    choices=["aot", "bitfit", "lora", "adapters", "ptv1",
                             "ptv2", "ft"])
    ap.add_argument("--aot-mode", default="fc", choices=["fc", "kron"])
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x4 => (data=2, model=4); empty = no mesh")
    ap.add_argument("--ckpt-dir", default="results/launch_train")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg, repeats=2)
    model = Model(cfg, ModelOptions(chunk_q=max(64, args.seq // 4),
                                    chunk_kv=args.seq))
    params = model.init(jax.random.PRNGKey(0))

    popt = peft_mod.PEFTOptions(
        method=args.method,
        aot=aot_mod.AoTOptions(mode=args.aot_mode, rank=args.rank, dropout=0.0))
    pp = peft_mod.init(jax.random.PRNGKey(1), cfg, popt)
    tcfg = TrainConfig(peft=popt, lr=args.lr, loss_chunk=args.seq // 4)
    init_state, train_step = make_train_step(model, tcfg)
    trainable, frozen = split_train(params, pp, args.method)
    state = init_state(trainable)

    mesh = rules = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)])
        rules = shlib.tp_dp_rules()

        def put(tree, names_fn):
            from jax.sharding import NamedSharding

            def one(kp, x):
                names = names_fn(axlib.path_strings(kp), tuple(x.shape))
                return jax.device_put(x, NamedSharding(
                    mesh, shlib.spec_for(names, x.shape, mesh, rules)))
            return jax.tree_util.tree_map_with_path(one, tree)
        state = put(state, axlib.logical_axes_for)
        frozen = put(frozen, axlib.logical_axes_for)

    stream = LMStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      batch_size=args.batch, seed=0)
    ckpt = CheckpointManager(f"{args.ckpt_dir}/{args.arch}", keep=2)
    step = jax.jit(train_step, donate_argnums=0)
    loop = TrainLoop(train_step=step, frozen=frozen, stream=stream, ckpt=ckpt,
                     ckpt_every=max(20, args.steps // 5), log_every=10)

    ctx = (mesh, shlib.use_rules(mesh, rules)) if mesh else None
    if mesh:
        with mesh, shlib.use_rules(mesh, rules):
            state, start = loop.resume(state)
            state = loop.run(state, args.steps, start_step=start)
    else:
        state, start = loop.resume(state)
        state = loop.run(state, args.steps, start_step=start)
    for h in loop.history[-3:]:
        print({k: round(v, 4) if isinstance(v, float) else v for k, v in h.items()})
    print("events:", loop.events)


if __name__ == "__main__":
    main()

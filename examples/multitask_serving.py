"""Multi-task serving — the paper's deployment headline.

Fine-tunes THREE tasks with AoT P-Tuning against one frozen backbone, fuses
each task's P tables, stacks them, and serves a mixed batch where every
request picks its task by id — one backbone pass, zero per-task overhead.
Finishes with the continuous-batching scheduler: the same three tasks
served as an online stream (staggered arrivals, per-request lengths) from
one slotted KV pool, with outputs identical to dedicated decoding.

    PYTHONPATH=src python examples/multitask_serving.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import aot as A
from repro.core import peft as P
from repro.data.pipeline import LMStream
from repro.data.tasks import ClassificationTask
from repro.models.model import Model, ModelOptions
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import ContinuousScheduler, Request, SchedulerConfig
from repro.train.step import TrainConfig, make_train_step, split_train


def pretrain(cfg, model, params):
    popt = P.PEFTOptions(method="ft")
    init_state, train_step = make_train_step(model, TrainConfig(peft=popt, lr=3e-3))
    trainable, frozen = split_train(params, P.init(jax.random.PRNGKey(1), cfg, popt), "ft")
    state, step = init_state(trainable), jax.jit(train_step)
    stream = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8, seed=0)
    for i in range(50):
        b = stream.next()
        state, _ = step(state, frozen, {k: jnp.asarray(v) for k, v in b.items()},
                        jax.random.PRNGKey(i))
    return state["trainable"]["backbone"]


def finetune_task(cfg, model, params, task):
    popt = P.PEFTOptions(method="aot", num_classes=task.num_classes,
                         aot=A.AoTOptions(mode="fc", rank=16, dropout=0.0))
    pp = P.init(jax.random.PRNGKey(task.seed), cfg, popt)
    init_state, train_step = make_train_step(
        model, TrainConfig(peft=popt, lr=8e-3), classify=True)
    trainable, frozen = split_train(params, pp, "aot")
    state, step = init_state(trainable), jax.jit(train_step)
    for i in range(100):
        b = task.batch(16, step=i)
        state, _ = step(state, frozen, {k: jnp.asarray(v) for k, v in b.items()},
                        jax.random.PRNGKey(i))
    peft_params = state["trainable"]["peft"]
    fused = A.fuse(peft_params["aot"], cfg, popt.aot,
                   embed=params["embed"]["tok"], vocab_chunk=64)
    return fused, peft_params["head"]


def main():
    cfg = configs.reduced(configs.get("smollm-360m"), repeats=2)
    model = Model(cfg, ModelOptions(chunk_q=16, chunk_kv=16))
    params = pretrain(cfg, model, model.init(jax.random.PRNGKey(0)))

    tasks = [ClassificationTask(f"task{i}", vocab_size=cfg.vocab_size,
                                seq_len=32, num_classes=2, seed=i)
             for i in range(3)]
    fused, heads = zip(*(finetune_task(cfg, model, params, t) for t in tasks))
    print(f"fused {len(tasks)} task table sets "
          f"({A.table_bytes(cfg, len(tasks), 2) / 1e6:.1f} MB total)")

    # mixed batch: every row picks its own task
    rng = np.random.default_rng(0)
    rows, labels, task_ids = [], [], []
    for i in range(9):
        t = i % 3
        b = tasks[t].batch(1, step=7_000 + i)
        rows.append(b["tokens"][0])
        labels.append(int(b["labels"][0]))
        task_ids.append(t)
    toks = jnp.asarray(np.stack(rows))
    tids = jnp.asarray(task_ids, jnp.int32)

    stacked = A.stack_tasks(list(fused))
    fopt = P.PEFTOptions(method="aot", aot=A.AoTOptions(mode="fused"))
    peft = P.make({"aot": stacked}, fopt)
    peft["task_ids"] = tids
    h, _ = model.forward(params, {"tokens": toks}, peft)   # ONE backbone pass
    correct = 0
    for i in range(9):
        head = heads[task_ids[i]]
        pred = int(jnp.argmax(h[i, -1] @ head["w"] + head["b"]))
        correct += int(pred == labels[i])
        print(f"request {i}: task={task_ids[i]} pred={pred} gold={labels[i]}")
    print(f"mixed-batch accuracy: {correct}/9")

    # and generation with per-request task conditioning
    eng = ServeEngine(model, params, ServeConfig(max_len=64),
                      fused_tasks=list(fused))
    prompts = rng.integers(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    out = eng.generate(prompts, steps=6, task_ids=np.asarray([0, 1, 2], np.int32))
    print("generated (per-task continuations):")
    print(out)

    # continuous serving: the three tasks as an online stream — requests
    # arrive staggered with their own prompt/output lengths and share the
    # paged KV pool; one serve step a tick advances everything in flight
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=2,
                                                     prefill_chunk=8))
    arrivals = []
    for i in range(6):
        plen = int(rng.integers(4, 13))
        arrivals.append((i, Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            task_id=i % 3, max_new_tokens=int(rng.integers(2, 7)))))
    finished = sched.run_stream(arrivals)
    print(f"continuous stream: {len(finished)} requests over 2 slots in "
          f"{sched.ticks} serve steps")
    for rid in sorted(finished):
        req = finished[rid]
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        tag = "ok" if np.array_equal(np.asarray(req.out), ref) else "MISMATCH"
        print(f"  req {rid} task={req.task_id}: {req.out} [{tag} vs dedicated]")

    # stochastic sampling with COW-forked parallel samples: one prompt,
    # n=3 temperature/top-p continuations from ONE prefill — the forked
    # samples share the prompt's KV pages and only pay for divergent tails
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=4,
                                                     block_size=8,
                                                     prefill_chunk=8))
    req = Request(rid=0,
                  prompt=rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                  task_id=0, max_new_tokens=6,
                  sampling=SamplingParams(temperature=0.8, top_p=0.9, seed=17,
                                          n=3))
    sched.submit(req)
    sched.run()
    pool = sched.pool
    print(f"sampled n=3 (temp 0.8, top-p 0.9): {pool.forks} forks, "
          f"{pool.cow_copies} COW copies, {pool.blocks_in_use()} pages at end")
    for i, s in enumerate(req.samples):
        print(f"  sample {i}: {s}")


if __name__ == "__main__":
    main()

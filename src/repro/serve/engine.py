"""Multi-task serving engine — the paper's headline deployment story.

One frozen backbone serves many fine-tuned tasks in the same batch: each
request carries a ``task_id``; the fused AoT tables (stacked (L, T, V, d))
are indexed per (task, token) during both prefill and decode, at gather+add
cost. No extra sequence length (vs P-Tuning), no extra matmuls (vs
LoRA-unfused/Adapters) — the zero-cost property of Table 1.

Two serving modes share the same model:

  * ``generate``: static batch over a contiguous cache — every request
    arrives together, shares one prompt length, finishes together (the
    paper's benchmark setting).
  * :meth:`ServeEngine.serve_step`, the continuous path driven by
    :mod:`repro.serve.scheduler`: a whole tick is ONE jitted call over a
    ragged PACKED token list, where each decode row contributes one token
    and every in-flight prefill its next chunk (several prompts chunk
    concurrently, every token tagged with its owning slot and position).
    Each token's KV scatters straight into its slot's block-table-mapped
    pages of the :class:`repro.serve.kv_pool.PagedKVPool`, and per-slot
    sampling vectors fold the token draw into the same dispatch. Because
    the AoT bias is a per-(task, token) gather, a mixed-task batch costs
    exactly what a single-task batch costs.

The engine also serves the baselines for the overhead benchmarks
(Fig. 3): ptv2 (longer effective KV), lora-unfused (extra matmuls),
bitfit, and plain backbone.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aot as aot_mod
from repro.core import peft as peft_mod
from repro.kernels.decode_attention import round_kv_len
from repro.models.model import Model
from repro.obs.tracing import NULL_TRACER
from repro.serve.sampling import sample_tokens


@dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    greedy: bool = True


class DispatchFault(RuntimeError):
    """A serve_step dispatch failed before producing usable results.

    Raised by the engine when an injected (or real) dispatch-level fault
    fires; the scheduler's self-healing tick loop catches it, repacks,
    and retries (``SchedulerConfig.tick_retries``) instead of letting one
    bad dispatch kill every in-flight request."""


class ServeEngine:
    def __init__(self, model: Model, params, cfg: ServeConfig = ServeConfig(),
                 fused_tasks=None, peft=None):
        """``fused_tasks``: a list of {'table': (L, V, d)}, one per task, or
        those tables already stacked as {'table': (L, T, V, d)}
        (:func:`repro.core.aot.stack_tasks`) — the engine then holds the
        only device copy. ``peft``: alternatively a ready peft bundle
        (baseline methods). The PEFT parameters enter every jitted step as
        arguments, never as closed-over constants, so a multi-GB table is
        neither baked into the programs nor copied beside itself."""
        self.model = model
        self.params = params
        self.cfg = cfg
        if fused_tasks is not None:
            stacked = (fused_tasks if isinstance(fused_tasks, dict)
                       else aot_mod.stack_tasks(fused_tasks))
            opt = peft_mod.PEFTOptions(
                method="aot", aot=aot_mod.AoTOptions(mode="fused"))
            self.peft = peft_mod.make({"aot": stacked}, opt)
            self.multitask = True
            # task-id validity bound: the scheduler rejects submissions
            # whose task_id a fused-table gather would silently clamp/wrap
            self.num_tasks: Optional[int] = stacked["table"].shape[1]
        else:
            self.peft = peft
            self.multitask = False
            self.num_tasks = None
        self._pp = self.peft["params"] if self.peft is not None else None
        # KV allocations round up so the Pallas decode kernel never hits its
        # pad-and-copy fallback (S % block_k != 0); rows past cfg.max_len
        # stay masked by cur_len forever.
        self.cache_len = round_kv_len(cfg.max_len)
        self._decode = jax.jit(self._decode_impl)
        self._prefill = jax.jit(self._prefill_impl)
        self._sample_row = jax.jit(self._sample_row_impl)
        # the unified ragged prefill+decode step: two traces (greedy batches
        # keep the exact-argmax path), each still ONE dispatch per tick.
        # Named functions, so the device trace names the programs
        # jit_serve_step_greedy / jit_serve_step_sampled

        def serve_step_greedy(*args):
            return self._serve_step_impl(*args, stochastic=False)

        def serve_step_sampled(*args):
            return self._serve_step_impl(*args, stochastic=True)
        self._serve_greedy = jax.jit(serve_step_greedy)
        self._serve_sampled = jax.jit(serve_step_sampled)
        # host-visible device-dispatch counter (serve-path calls only):
        # the scheduler asserts one dispatch per unified tick and the
        # launcher reports dispatches/tick
        self.dispatches = 0
        # the watchdog's finite_rows program, launched after every
        # serve_step: a tick runs two programs until finiteness comes
        # back from serve_step itself
        self.finite_rows_dispatches = 0
        self._m = None                  # optional obs per-kind counters
        self.tracer = NULL_TRACER       # the scheduler's, once attached
        # one-shot injected dispatch fault (see inject_fault)
        self._pending_fault: Optional[Tuple[str, int]] = None

    def attach_metrics(self, registry) -> None:
        """Per-kind dispatch counters on an obs registry. Incremented on
        the host around the jitted calls, never inside them — a tick's
        dispatch anatomy (serve_step, n>1 first-token draws, and the
        watchdog's finite_rows) becomes visible without touching traces."""
        self._m = {kind: registry.counter(
            f"engine_dispatch_{kind}_total",
            f"device dispatches via {kind}")
            for kind in ("serve_step", "sample_first", "finite_rows")}

    def attach_tracer(self, tracer) -> None:
        """Record the engine's host phases (``engine.inputs``,
        ``engine.launch``, ``engine.outputs``) on ``tracer``, nested in
        the scheduler's ``dispatch`` span. The scheduler attaches its own
        tracer, the null one included, so an engine reused under an
        untraced scheduler records nothing."""
        self.tracer = tracer

    def _count(self, kind: str) -> None:
        self.dispatches += 1
        if self._m is not None:
            self._m[kind].inc()

    def inject_fault(self, kind: str, slot: int = -1) -> None:
        """Arm a ONE-SHOT dispatch fault consumed by the next
        :meth:`serve_step` (fault-injection harness only — see
        ``serve.faults``). ``"alloc_failure"`` raises :class:`DispatchFault`
        before the device dispatch; ``"nan"`` poisons slot ``slot``'s
        logits row with NaN *after* the jitted call and before the
        watchdog's finiteness check — exactly where a real numerical fault
        (bad page, overflowed accumulation) would surface."""
        if kind not in ("nan", "alloc_failure"):
            raise ValueError(f"unknown injected fault kind: {kind!r}")
        self._pending_fault = (kind, slot)

    # ------------------------------------------------------------------
    def _peft_for(self, pp, task_ids):
        """Rebuild the peft bundle around the traced parameters ``pp``."""
        if self.peft is None:
            return None
        p = peft_mod.make(pp, self.peft["opt"])
        if self.multitask:
            p["task_ids"] = task_ids
        return p

    def _prefill_impl(self, params, pp, tokens, task_ids, extra=None):
        batch = {"tokens": tokens}
        if extra:
            batch.update(extra)
        peft = self._peft_for(pp, task_ids)
        return self.model.prefill(params, batch, peft, max_len=self.cache_len)

    def _decode_impl(self, params, pp, tokens, pos, cache, task_ids):
        peft = self._peft_for(pp, task_ids)
        return self.model.decode_step(params, tokens, pos, cache, peft)

    def _serve_step_impl(self, params, pp, tokens, token_rows, token_pos,
                         logit_idx, cache, token_tasks, block_tables, temps,
                         top_ks, top_ps, base_keys, steps, *, stochastic):
        """The whole paged tick in one jit: unified ragged model step over
        the packed token list + per-slot token draw. Greedy batches trace
        with ``stochastic=False`` (pure argmax, the bitwise-parity fast
        path); the masking/draw work only exists in the stochastic trace."""
        peft = self._peft_for(pp, token_tasks)
        logits, cache = self.model.mixed_step(
            params, tokens, token_rows, token_pos, cache, peft,
            block_tables=block_tables, logit_idx=logit_idx)
        with jax.named_scope("sampling"):
            if stochastic:
                toks = sample_tokens(logits, temps, top_ks, top_ps,
                                     base_keys, steps)
            else:
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return toks, logits, cache

    def serve_step_shapes(self, cache, num_slots: int, npages: int,
                          width: int):
        """Abstract arguments of the jitted serve step (``_serve_greedy`` /
        ``_serve_sampled``) for a tick of packed width ``width`` over a
        paged pool ``cache`` of ``num_slots`` block tables of ``npages``:
        lowering with them compiles the program such a tick runs."""
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)
        shape_of = lambda x: sds(x.shape, x.dtype)      # noqa: E731
        i32, f32 = jnp.int32, jnp.float32
        return (jax.tree.map(shape_of, self.params),
                jax.tree.map(shape_of, self._pp),
                sds((width, 1), i32), sds((width,), i32), sds((width,), i32),
                sds((num_slots,), i32), jax.tree.map(shape_of, cache),
                sds((width,), i32), sds((num_slots, npages), i32),
                sds((num_slots,), f32), sds((num_slots,), i32),
                sds((num_slots,), f32), sds((num_slots, 2), jnp.uint32),
                sds((num_slots,), i32))

    def _sample_row_impl(self, logits_row, temps, top_ks, top_ps, base_keys,
                         steps):
        """Draw ``n`` first tokens from ONE prefill logits row — one draw
        per parallel sample, each under its own stream (n = len(temps))."""
        rows = jnp.broadcast_to(logits_row[None, :],
                                (temps.shape[0], logits_row.shape[-1]))
        return sample_tokens(rows, temps, top_ks, top_ps, base_keys, steps)

    # ------------------------------------------------------------------
    # static-batch serving (the paper's benchmark setting)
    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, steps: int,
                 task_ids: Optional[np.ndarray] = None) -> np.ndarray:
        """prompts: (b, s) int32; task_ids: (b,) int32. Greedy decode."""
        b, s = prompts.shape
        tids = jnp.asarray(task_ids if task_ids is not None
                           else np.zeros(b, np.int32))
        logits, cache, pos = self._prefill(self.params, self._pp,
                                          jnp.asarray(prompts), tids)
        out = []
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        for i in range(steps):
            out.append(tok)
            logits, cache = self._decode(self.params, self._pp, tok, pos + i,
                                         cache, tids)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return np.asarray(jnp.concatenate(out, axis=1))

    # ------------------------------------------------------------------
    # continuous-batching primitives (driven by serve.scheduler)
    # ------------------------------------------------------------------
    @staticmethod
    def _sample_vecs(sample):
        """Host sample spec (temps, top_ks, top_ps, base_keys, steps) —
        np arrays — to device args."""
        temps, top_ks, top_ps, base_keys, steps = sample
        return (jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_ks, jnp.int32),
                jnp.asarray(top_ps, jnp.float32),
                jnp.asarray(base_keys, jnp.uint32),
                jnp.asarray(steps, jnp.int32))

    def sample_first(self, logits_row, sample) -> list:
        """Draw the spec's first tokens from ONE logits row — the n>1
        parallel-samples path, where every sample's token 0 comes from the
        same prefill row under its own stream."""
        tr = self.tracer
        with tr.span("engine.inputs"):
            vecs = self._sample_vecs(sample)
        with tr.span("engine.launch"):
            toks = self._sample_row(logits_row, *vecs)
            self._count("sample_first")
        with tr.span("engine.outputs"):
            return [int(t) for t in np.asarray(jax.device_get(toks))]

    def serve_step(self, tokens: np.ndarray, token_rows: np.ndarray,
                   token_pos: np.ndarray, logit_idx: np.ndarray, cache,
                   block_tables: np.ndarray, token_tasks: np.ndarray, sample):
        """The unified ragged prefill+decode tick — ONE jitted device call
        regardless of batch composition.

        tokens: (T, 1) the tick's packed token list (each decode row one
        fed-back token, every in-flight prefill its chunk, free slots
        nothing); token_rows / token_pos / token_tasks: (T,) each token's
        owning slot, absolute position (-1 = dead padding), and task id;
        logit_idx: (num_slots,) per-slot index into the packed axis whose
        logits the slot reports; block_tables: (num_slots, npages);
        ``sample``: the per-slot (temps, top_ks, top_ps, base_keys, steps)
        vectors — always threaded, all-greedy batches take the exact-argmax
        trace. The packed width T is whatever the scheduler builds (one
        compilation per distinct T per greedy/sampled trace — the
        scheduler's two tick shapes make that at most four, however many
        prefills share the chunk budget).
        Returns (next token per slot (num_slots,) np, per-slot logits
        (num_slots, V) still on device, new pool cache, per-slot finite
        flags (num_slots,) bool np — the watchdog input: False means that
        slot's reported logits row contains NaN/inf and its token must not
        be trusted; a second program, :func:`finite_rows`, computes them).
        The host work opens ``engine.inputs``, ``engine.launch`` and
        ``engine.outputs`` spans on the attached tracer."""
        tr = self.tracer
        with tr.span("engine.inputs"):
            fault, self._pending_fault = self._pending_fault, None
            if fault is not None and fault[0] == "alloc_failure":
                raise DispatchFault(
                    "injected allocation failure before dispatch (fault plan)")
            temps = np.asarray(sample[0])
            fn = (self._serve_sampled if np.any(temps > 0.0)
                  else self._serve_greedy)
            args = (jnp.asarray(tokens), jnp.asarray(token_rows, np.int32),
                    jnp.asarray(token_pos, np.int32),
                    jnp.asarray(logit_idx, np.int32), cache,
                    jnp.asarray(token_tasks, np.int32),
                    jnp.asarray(block_tables, np.int32),
                    *self._sample_vecs(sample))
        with tr.span("engine.launch"):
            toks, logits, cache = fn(self.params, self._pp, *args)
            if fault is not None:       # kind == "nan": poison post-jit,
                logits = logits.at[fault[1]].set(jnp.nan)   # pre-watchdog
            self._count("serve_step")
        with tr.span("engine.outputs"):
            finite = np.asarray(jax.device_get(finite_rows(logits)))
            self.finite_rows_dispatches += 1
            if self._m is not None:
                self._m["finite_rows"].inc()
            return np.asarray(jax.device_get(toks)), logits, cache, finite


@jax.jit
def finite_rows(logits):
    """The watchdog's per-slot check: False where a slot's logits row
    holds NaN or inf."""
    return jnp.all(jnp.isfinite(logits), axis=-1)

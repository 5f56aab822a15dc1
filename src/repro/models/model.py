"""Model assembly: configs -> init / forward / prefill / decode.

Layers are grouped by *pattern unit* (configs.base); each group is a
homogeneous stack scanned with ``jax.lax.scan`` (stacked params on axis 0),
optionally rematerialized per unit. PEFT hooks (AoT P-Tuning + baselines)
are threaded through the scan as per-layer slices; a fused AoT table as its
bias rows, gathered for all layers of a group ahead of the scan.

Caches: every block kind owns a decode cache (attention KV — ring-buffered
for SWA so a 512k-token decode holds only the window; RG-LRU conv+state;
m/sLSTM conv+matrix state).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, BLOCK_ATTN, BLOCK_MLSTM,
                                BLOCK_RGLRU, BLOCK_SLSTM)
from repro.core import aot as aot_mod
from repro.core import peft as peft_mod
from repro.distrib.sharding import constrain
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xl_mod


@dataclass(frozen=True)
class ModelOptions:
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "chunked"       # ref | chunked | pallas
    chunk_q: int = 1024
    chunk_kv: int = 1024
    mlstm_chunk: int = 64
    remat: bool = True               # checkpoint each scan body
    remat_save_names: Tuple[str, ...] = ()   # checkpoint_name'd values to save
    remat_policy_name: str = ""      # "" | "dots" (checkpoint_dots_with_no_batch_dims)
    scan_layers: bool = True
    unroll_scans: bool = False       # python-loop inner scans (dry-run costing)
    swa_ring_cache: bool = True      # window-bounded KV cache for SWA layers
    max_learned_pos: int = 0         # 0 = derive from shapes


@dataclass(frozen=True)
class GroupPlan:
    kinds: Tuple[str, ...]
    moe_flags: Tuple[bool, ...]
    repeats: int
    start: int                       # first global layer index


def layer_plan(cfg: ArchConfig) -> List[GroupPlan]:
    unit = cfg.pattern_unit
    moemask = cfg.moe_layer_mask()
    ulen = len(unit)
    if cfg.moe is not None and cfg.moe.interleave > 1:
        m = math.lcm(ulen, cfg.moe.interleave)
        unit = unit * (m // ulen)
        ulen = m
    covered = cfg.pattern_repeats * len(cfg.pattern_unit)
    assert covered % ulen == 0, (cfg.name, ulen, covered)
    repeats = covered // ulen
    groups = [GroupPlan(tuple(unit), tuple(moemask[u] for u in range(ulen)),
                        repeats, 0)]
    if cfg.pattern_remainder:
        st = covered
        groups.append(GroupPlan(tuple(cfg.pattern_remainder),
                                tuple(moemask[st + u] for u in range(len(cfg.pattern_remainder))),
                                1, st))
    return groups


def _regroup(leaf, start: int, repeats: int, ulen: int):
    """(L, ...) stacked-per-layer leaf -> (R, U, ...) slice for a group."""
    sl = leaf[start:start + repeats * ulen]
    return sl.reshape((repeats, ulen) + leaf.shape[1:])


class Model:
    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        self.cfg = cfg
        self.opts = opts
        self.plan = layer_plan(cfg)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _block_init(self, key, kind: str, moe_flag: bool):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        if kind == BLOCK_ATTN:
            p = {"ln1": L.norm_init(cfg), "attn": L.attn_init(k1, cfg)}
            if moe_flag:
                p["ln2"] = L.norm_init(cfg)
                p["moe"] = moe_mod.moe_init(k2, cfg)
            elif cfg.d_ff > 0:
                p["ln2"] = L.norm_init(cfg)
                p["mlp"] = L.mlp_init(k2, cfg)
            return p
        if kind == BLOCK_RGLRU:
            p = {"ln1": L.norm_init(cfg), "rglru": rec_mod.rglru_init(k1, cfg)}
            if cfg.d_ff > 0:
                p["ln2"] = L.norm_init(cfg)
                p["mlp"] = L.mlp_init(k2, cfg)
            return p
        if kind == BLOCK_MLSTM:
            return {"ln1": L.norm_init(cfg), "core": xl_mod.mlstm_block_init(k1, cfg)}
        if kind == BLOCK_SLSTM:
            return {"ln1": L.norm_init(cfg), "core": xl_mod.slstm_block_init(k1, cfg)}
        raise ValueError(kind)

    def max_pos(self) -> int:
        if self.opts.max_learned_pos:
            return self.opts.max_learned_pos
        return max(s.seq_len for s in self.cfg.shapes) + 128

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 4 + len(self.plan))
        params: Dict[str, Any] = {}
        emb: Dict[str, Any] = {}
        if cfg.frontend != "audio_frames":
            emb["tok"] = L.embed_init(keys[0], (cfg.vocab_size, cfg.d_model))
        if cfg.pos_type == "learned":
            emb["pos"] = L.embed_init(keys[1], (self.max_pos(), cfg.d_model))
        params["embed"] = emb
        if cfg.frontend:
            params["frontend"] = {
                "proj": L.dense_init(keys[2], (cfg.frontend_dim, cfg.d_model))}
        groups = []
        for gi, plan in enumerate(self.plan):
            gkey = keys[4 + gi]
            gp = {}
            for u, kind in enumerate(plan.kinds):
                ukeys = jax.random.split(jax.random.fold_in(gkey, u), plan.repeats)
                gp[f"b{u}"] = jax.vmap(
                    lambda k, kind=kind, mf=plan.moe_flags[u]:
                        self._block_init(k, kind, mf))(ukeys)
            groups.append(gp)
        params["groups"] = groups
        params["final_norm"] = L.norm_init(cfg)
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": L.dense_init(keys[3], (cfg.d_model, cfg.vocab_size))}
        # drawn in float32, then stored as opts.param_dtype: the same seed
        # gives the same weights, rounded, in every dtype
        return jax.tree.map(lambda x: x.astype(self.opts.param_dtype), params)

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    # ------------------------------------------------------------------
    # embedding & heads
    # ------------------------------------------------------------------
    def _embed(self, params, batch, peft):
        """Returns (h0, aot_ids, e_rows, positions, prompt_len)."""
        cfg = self.cfg
        dt = self.opts.compute_dtype
        method = peft["method"] if peft else "none"
        if cfg.frontend == "audio_frames":
            frames = batch["frames"]
            h = frames.astype(dt) @ params["frontend"]["proj"].astype(dt)
            ids = batch.get("aot_ids")       # optional unit-AoT extension
            e_rows = None
        else:
            ids = batch["tokens"]
            E = params["embed"]["tok"]
            e_rows = jnp.take(E, ids, axis=0)
            h = e_rows.astype(dt)
            if cfg.frontend == "vision_patches" and "patches" in batch:
                pe = batch["patches"].astype(dt) @ params["frontend"]["proj"].astype(dt)
                n = pe.shape[1]
                h = jnp.concatenate([pe, h[:, n:]], axis=1)
            if cfg.embed_scale:
                h = h * jnp.asarray(math.sqrt(cfg.d_model), dt)
        positions = jnp.arange(h.shape[1])
        prompt_len = 0
        if method == "ptv1":
            prompt = peft["params"]["ptv1"]["prompt"].astype(dt)
            p = prompt.shape[0]
            h = jnp.concatenate([jnp.tile(prompt[None], (h.shape[0], 1, 1)), h], axis=1)
            positions = jnp.arange(h.shape[1])
            prompt_len = p
            if ids is not None:   # pad ids so per-layer hooks stay aligned
                ids = jnp.concatenate(
                    [jnp.zeros((ids.shape[0], p), ids.dtype), ids], axis=1)
                e_rows = jnp.concatenate(
                    [jnp.zeros((e_rows.shape[0], p, e_rows.shape[2]), e_rows.dtype),
                     e_rows], axis=1) if e_rows is not None else None
        if cfg.pos_type == "learned":
            h = h + jnp.take(params["embed"]["pos"], positions, axis=0).astype(dt)[None]
        h = constrain(h, "batch", "seq", "embed")
        return h, ids, e_rows, positions, prompt_len

    def unembed(self, params, h):
        dt = self.opts.compute_dtype
        cfg = self.cfg
        if cfg.tie_embeddings:
            w = params["embed"]["tok"].astype(dt).T
        else:
            w = params["lm_head"]["w"].astype(dt)
        logits = h.astype(dt) @ w
        # vocab (not seq) owns the model axis here — see train.step.chunked_ce
        return constrain(logits, "batch", None, "vocab")

    # ------------------------------------------------------------------
    # PEFT per-layer machinery
    # ------------------------------------------------------------------
    def _peft_group_xs(self, peft, plan: GroupPlan, ids):
        """Slice per-layer PEFT leaves into (R, U, ...) for the scan. A fused
        AoT table gives the group's bias rows instead, gathered here for all
        its layers at once: the scan never sees the table."""
        if peft is None:
            return None
        method = peft["method"]
        pp = peft["params"]
        take = None
        if method == "aot":
            if peft["opt"].aot.mode == "fused":
                return self._aot_group_rows(peft, plan, ids)
            take = pp["aot"]
        elif method == "bitfit":
            take = {k: v for k, v in pp["bitfit"].items() if k != "final"}
        elif method == "lora":
            take = pp["lora"]
        elif method == "adapters":
            take = pp["adapters"]
        elif method == "ptv2":
            take = pp["ptv2"]
        if take is None:
            return None
        return jax.tree.map(
            lambda x: _regroup(x, plan.start, plan.repeats, len(plan.kinds)), take)

    def _aot_group_rows(self, peft, plan: GroupPlan, ids):
        """Every layer's fused-table bias rows of one group, (R, U, b, s, d),
        in one gather ahead of its layers (the rows depend on layer, task
        and token only), or None where there are no token ids."""
        if ids is None:
            return None
        ulen = len(plan.kinds)
        with jax.named_scope("aot_bias"):
            rows = aot_mod.rows_fused_layers(
                peft["params"]["aot"]["table"], ids, peft.get("task_ids"),
                start=plan.start, count=plan.repeats * ulen,
                dtype=self.opts.compute_dtype)
        return rows.reshape((plan.repeats, ulen) + rows.shape[1:])

    def aot_rows(self, peft, width: int) -> Optional[int]:
        """Bias rows a step over ``width`` packed tokens gathers ahead of
        its layers (layers x width), or None where it runs no fused AoT
        table."""
        if (peft is None or peft["method"] != "aot"
                or peft["opt"].aot.mode != "fused"):
            return None
        return sum(p.repeats * len(p.kinds) for p in self.plan) * width

    def _aot_bias(self, peft, peft_u, ids, e_rows, rng_layer):
        """The paper's P^i rows for this layer, (b, s, d), or None. A fused
        table's rows were gathered before the layer scan
        (``_aot_group_rows``) and arrive as ``peft_u``."""
        if ids is None and e_rows is None:
            return None
        opt: peft_mod.PEFTOptions = peft["opt"]
        ao = opt.aot
        dt = self.opts.compute_dtype
        if ao.mode == "fc":
            return aot_mod.rows_fc(peft_u, e_rows, ao, dt, rng_layer)
        if ao.mode == "kron":
            return aot_mod.rows_kron(peft_u, ids, ao, self.cfg.vocab_size, dt, rng_layer)
        if ao.mode == "fused":
            return peft_u
        raise ValueError(ao.mode)

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------
    def _ragged_on_pallas(self) -> bool:
        """Whether mixed_step's attention runs the Pallas ragged kernel
        (else ``layers.ragged_paged_attention_decode``, in XLA)."""
        return self.opts.attn_impl == "pallas" and not self.cfg.logit_softcap

    def ragged_walk(self, token_rows, token_pos, *, block_size: int,
                    npages: int):
        """(runs, KV steps, query rows) that the Pallas ragged kernel
        walks in each attention layer of mixed_step for this packed token
        list (``ragged_plan``), or None where mixed_step does not run it."""
        if not self._ragged_on_pallas():
            return None
        from repro.kernels.decode_attention import ragged_plan
        cfg = self.cfg
        return ragged_plan(token_rows, token_pos, block_size=block_size,
                           kv_heads=cfg.num_kv_heads,
                           q_per_kv=cfg.num_heads // cfg.num_kv_heads,
                           head_dim=cfg.head_dim, npages=npages)

    def _attention(self, bp, h_in, positions, peft, peft_u, cache_u, decode_pos,
                   prompt_len, block_tables=None, token_rows=None):
        cfg, opts = self.cfg, self.opts
        dt = opts.compute_dtype
        method = peft["method"] if peft else "none"
        b, s, _ = h_in.shape

        peft_qkv = None
        if method == "lora":
            sc = peft_mod.lora_scale(peft["opt"])
            xq = h_in.astype(dt)
            dq = (xq @ peft_u["qa"].astype(dt)) @ peft_u["qb"].astype(dt) * sc
            dv = (xq @ peft_u["va"].astype(dt)) @ peft_u["vb"].astype(dt) * sc
            peft_qkv = (dq, None, dv)

        q, k, v = L.attn_project_qkv(cfg, bp["attn"], h_in, positions, dt, peft_qkv)

        window = cfg.sliding_window if cfg.attn_kind == "swa" else 0
        softcap = cfg.logit_softcap
        new_cache = cache_u

        if token_rows is not None:
            # ---- unified ragged mixed step: the batch axis is a PACKED
            # token list (decode rows one token each, every in-flight
            # prefill its chunk, zero padding compute). decode_pos carries
            # each token's absolute position (-1 = dead padding token);
            # its K/V scatters straight into its slot's mapped pool pages
            # — no temp cache — and attention runs the ragged kernel over
            # that slot's resident pages ----
            if window:
                raise NotImplementedError(
                    "paged serving has no sliding-window masking yet; serve "
                    "SWA models statically with ServeEngine.generate")
            bs_page = cache_u["k"].shape[1]
            live = decode_pos >= 0
            pos = jnp.maximum(decode_pos, 0)
            # dead tokens scatter to scratch page 0 (never read unmasked)
            page = jnp.where(live,
                             block_tables[token_rows, pos // bs_page], 0)
            off = pos % bs_page
            kc = cache_u["k"].at[page, off].set(k[:, 0].astype(cache_u["k"].dtype))
            vc = cache_u["v"].at[page, off].set(v[:, 0].astype(cache_u["v"].dtype))
            if self._ragged_on_pallas():
                from repro.kernels import ops as kops
                o = kops.ragged_paged_attention(q[:, 0], kc, vc, block_tables,
                                                token_rows, decode_pos)[:, None]
            else:
                o = L.ragged_paged_attention_decode(q, kc, vc, block_tables,
                                                    token_rows, decode_pos,
                                                    softcap=softcap)
            new_cache = {"k": kc, "v": vc}
        elif cache_u is not None and decode_pos is not None:
            # ---- decode: write new kv, attend over cache ----
            S_c = cache_u["k"].shape[1]
            is_ring = (cfg.attn_kind == "swa" and opts.swa_ring_cache
                       and cfg.sliding_window and S_c == cfg.sliding_window)
            slot = decode_pos % S_c if is_ring else decode_pos
            if jnp.ndim(decode_pos) == 0:
                kc = jax.lax.dynamic_update_slice(cache_u["k"], k.astype(cache_u["k"].dtype),
                                                  (0, slot, 0, 0))
                vc = jax.lax.dynamic_update_slice(cache_u["v"], v.astype(cache_u["v"].dtype),
                                                  (0, slot, 0, 0))
            else:
                # per-row positions (KV-pool slots at mixed depths): scatter
                # each row's new kv at its own slot.
                rows = jnp.arange(b)
                kc = cache_u["k"].at[rows, slot].set(k[:, 0].astype(cache_u["k"].dtype))
                vc = cache_u["v"].at[rows, slot].set(v[:, 0].astype(cache_u["v"].dtype))
            cur = decode_pos + 1
            if is_ring:     # buffer IS the window: every resident entry valid
                valid, eff_window = jnp.minimum(cur, S_c), 0
            else:
                valid, eff_window = cur, window
            if opts.attn_impl == "pallas" and not eff_window and not softcap:
                from repro.kernels import ops as kops
                o = kops.decode_attention(q[:, 0], kc, vc, valid)[:, None]
            else:
                o = L.attention_decode(q, kc, vc, valid, window=eff_window,
                                       softcap=softcap)
            new_cache = {"k": kc, "v": vc}
        else:
            # ---- full / prefill ----
            if method == "ptv2":
                p = peft_u["pk"].shape[0]
                pk = jnp.tile(peft_u["pk"].astype(k.dtype)[None], (b, 1, 1, 1))
                pv = jnp.tile(peft_u["pv"].astype(v.dtype)[None], (b, 1, 1, 1))
                k = jnp.concatenate([pk, k], axis=1)
                v = jnp.concatenate([pv, v], axis=1)
                q_off = p
            else:
                q_off = 0
            kwargs = dict(causal=cfg.causal, window=window,
                          prefix_len=(cfg.prefix_lm_len + prompt_len + q_off
                                      if cfg.prefix_lm_len or prompt_len else 0),
                          softcap=softcap, q_offset=q_off)
            if opts.attn_impl == "ref":
                o = L.attention_ref(q, k, v, **kwargs)
            elif opts.attn_impl == "pallas":
                from repro.kernels import ops as kops
                o = kops.flash_attention(q, k, v, **kwargs)
            else:
                o = L.attention_chunked(q, k, v, chunk_q=opts.chunk_q,
                                        chunk_kv=opts.chunk_kv, **kwargs)
            if cache_u is not None:   # prefill: persist kv (incl. ptv2 prefix)
                new_cache = self._write_prefill_cache(cache_u, k, v)
        peft_bias = None
        if method == "bitfit":
            peft_bias = peft_u["attn_out"]
        out = L.attn_output(cfg, bp["attn"], o, dt, peft_bias)
        if method == "adapters":
            a = peft_u["attn"]
            z = jax.nn.gelu(out @ a["down"].astype(dt) + a["b1"].astype(dt))
            out = out + z @ a["up"].astype(dt) + a["b2"].astype(dt)
        return out, new_cache

    def _write_prefill_cache(self, cache_u, k, v, skip: int = 0):
        if skip:
            k, v = k[:, skip:], v[:, skip:]
        S_c = cache_u["k"].shape[1]
        s = k.shape[1]
        if s >= S_c:        # keep last S_c entries at ring positions
            kk = jnp.roll(k[:, -S_c:], s % S_c, axis=1)
            vv = jnp.roll(v[:, -S_c:], s % S_c, axis=1)
            return {"k": kk.astype(cache_u["k"].dtype),
                    "v": vv.astype(cache_u["v"].dtype)}
        kc = jax.lax.dynamic_update_slice(
            cache_u["k"], k.astype(cache_u["k"].dtype), (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache_u["v"], v.astype(cache_u["v"].dtype), (0, 0, 0, 0))
        return {"k": kc, "v": vc}

    def _ffn(self, bp, h_norm, peft, peft_u, moe_flag):
        dt = self.opts.compute_dtype
        method = peft["method"] if peft else "none"
        aux = {}
        if moe_flag:
            out, aux = moe_mod.apply_moe(self.cfg, bp["moe"], h_norm, dt)
        else:
            out = L.apply_mlp(self.cfg, bp["mlp"], h_norm, dt)
        if method == "bitfit":
            out = out + peft_u["mlp_out"].astype(dt)
        if method == "adapters":
            a = peft_u["mlp"]
            z = jax.nn.gelu(out @ a["down"].astype(dt) + a["b1"].astype(dt))
            out = out + z @ a["up"].astype(dt) + a["b2"].astype(dt)
        return out, aux

    def _block_apply(self, kind, moe_flag, bp, h, *, ids, e_rows, positions,
                     peft, peft_u, rng_layer, cache_u, decode_pos, prompt_len,
                     block_tables=None, token_rows=None):
        """One block. Returns (h, aux, new_cache_u)."""
        cfg, opts = self.cfg, self.opts
        dt = opts.compute_dtype
        method = peft["method"] if peft else "none"
        aux: Dict[str, Any] = {}

        # --- the paper's mechanism: input-dependent bias BEFORE the layer ---
        if method == "aot":
            with jax.named_scope("aot_bias"):
                bias = self._aot_bias(peft, peft_u, ids, e_rows, rng_layer)
                if bias is not None:
                    h = h + bias.astype(dt)

        new_cache = cache_u
        if kind == BLOCK_ATTN:
            from jax.ad_checkpoint import checkpoint_name
            if cfg.post_ln:
                with jax.named_scope("attention"):
                    att, new_cache = self._attention(
                        bp, h, positions, peft, peft_u, cache_u, decode_pos,
                        prompt_len, block_tables, token_rows)
                h = L.apply_norm(cfg, bp["ln1"], h + att)
                with jax.named_scope("mlp"):
                    ffn, aux = self._ffn(bp, h, peft, peft_u, moe_flag)
                h = L.apply_norm(cfg, bp["ln2"], h + ffn)
            else:
                with jax.named_scope("attention"):
                    att, new_cache = self._attention(
                        bp, L.apply_norm(cfg, bp["ln1"], h), positions, peft,
                        peft_u, cache_u, decode_pos, prompt_len, block_tables,
                        token_rows)
                # SP-sharded, (b, s/TP, d)-sized: cheap to save so the remat
                # policy can skip recomputing attention in the backward pass
                att = checkpoint_name(att, "attn_mix")
                h = h + att
                if "mlp" in bp or moe_flag:
                    with jax.named_scope("mlp"):
                        ffn, aux = self._ffn(bp, L.apply_norm(cfg, bp["ln2"], h),
                                             peft, peft_u, moe_flag)
                    h = h + ffn
        elif kind == BLOCK_RGLRU:
            mix, new_cache = rec_mod.apply_rglru(cfg, bp["rglru"],
                                                 L.apply_norm(cfg, bp["ln1"], h),
                                                 dt, cache_u)
            h = h + mix
            if "mlp" in bp:
                ffn, aux = self._ffn(bp, L.apply_norm(cfg, bp["ln2"], h),
                                     peft, peft_u, False)
                h = h + ffn
        elif kind == BLOCK_MLSTM:
            mix, new_cache = xl_mod.apply_mlstm_block(
                cfg, bp["core"], L.apply_norm(cfg, bp["ln1"], h), dt, cache_u,
                chunk=opts.mlstm_chunk, unroll=opts.unroll_scans)
            h = h + mix
        elif kind == BLOCK_SLSTM:
            mix, new_cache = xl_mod.apply_slstm_block(
                cfg, bp["core"], L.apply_norm(cfg, bp["ln1"], h), dt, cache_u)
            h = h + mix
        else:
            raise ValueError(kind)
        h = constrain(h, "batch", "seq", "embed")
        return h, aux, new_cache

    # ------------------------------------------------------------------
    # group (scan) application
    # ------------------------------------------------------------------
    def _remat_policy(self):
        pols = []
        if self.opts.remat_policy_name == "dots":
            pols.append(jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        if self.opts.remat_save_names:
            pols.append(jax.checkpoint_policies.save_only_these_names(
                *self.opts.remat_save_names))
        if not pols:
            return None
        if len(pols) == 1:
            return pols[0]
        return jax.checkpoint_policies.save_from_both_policies(*pols)

    def _group_apply(self, gparams, plan: GroupPlan, h, *, ids, e_rows,
                     positions, peft, rng, gcache, decode_pos, prompt_len,
                     block_tables=None, token_rows=None):
        opts = self.opts
        U = len(plan.kinds)
        peft_xs = self._peft_group_xs(peft, plan, ids)     # (R, U, ...) or None

        def unit_body(h, bp_r, peft_r, cache_r, layer_base):
            auxs = []
            new_caches = []
            for u, kind in enumerate(plan.kinds):
                bp = bp_r[f"b{u}"]
                peft_u = (jax.tree.map(lambda x: x[u], peft_r)
                          if peft_r is not None else None)
                rng_layer = (jax.random.fold_in(rng, layer_base * U + u)
                             if rng is not None else None)
                cache_u = cache_r[f"b{u}"] if cache_r is not None else None
                h, aux, nc = self._block_apply(
                    kind, plan.moe_flags[u], bp, h, ids=ids, e_rows=e_rows,
                    positions=positions, peft=peft, peft_u=peft_u,
                    rng_layer=rng_layer, cache_u=cache_u,
                    decode_pos=decode_pos, prompt_len=prompt_len,
                    block_tables=block_tables, token_rows=token_rows)
                auxs.append(aux)
                new_caches.append(nc)
            aux_sum = {}
            for a in auxs:
                for k, v in a.items():
                    aux_sum[k] = aux_sum.get(k, 0.0) + v
            ncache = (_stack_unit(new_caches) if cache_r is not None else None)
            return h, aux_sum, ncache

        if opts.scan_layers and plan.repeats > 1:
            def body(carry, xs):
                h = carry
                bp_r = xs["p"]
                peft_r = xs.get("peft")
                cache_r = xs.get("cache")
                r = xs["r"]
                h, aux, ncache = unit_body(h, bp_r, peft_r, cache_r, r)
                ys = {"aux": aux}
                if ncache is not None:
                    ys["cache"] = ncache
                return h, ys
            if opts.remat:
                body = jax.checkpoint(body, policy=self._remat_policy())
            xs = {"p": gparams, "r": jnp.arange(plan.repeats)}
            if peft_xs is not None:
                xs["peft"] = peft_xs
            if gcache is not None:
                xs["cache"] = gcache
            h, ys = jax.lax.scan(body, h, xs)
            aux = jax.tree.map(lambda x: x.sum(0) if hasattr(x, "sum") else x,
                               ys["aux"])
            new_gcache = ys.get("cache")
        else:
            aux = {}
            new_cache_rows = []
            body = unit_body
            if opts.remat:
                body = jax.checkpoint(
                    lambda h, bp_r, peft_r, cache_r, r: unit_body(h, bp_r, peft_r, cache_r, r),
                    static_argnums=(4,), policy=self._remat_policy())
            for r in range(plan.repeats):
                bp_r = jax.tree.map(lambda x: x[r], gparams)
                peft_r = (jax.tree.map(lambda x: x[r], peft_xs)
                          if peft_xs is not None else None)
                cache_r = (jax.tree.map(lambda x: x[r], gcache)
                           if gcache is not None else None)
                h, a, ncache = body(h, bp_r, peft_r, cache_r, r)
                for k, v in a.items():
                    aux[k] = aux.get(k, 0.0) + v
                new_cache_rows.append(ncache)
            new_gcache = (jax.tree.map(lambda *x: jnp.stack(x), *new_cache_rows)
                          if gcache is not None else None)
        return h, aux, new_gcache

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------
    def forward(self, params, batch, peft=None, rng=None):
        """Full-sequence forward. Returns (hidden (b,s,d), aux)."""
        h, ids, e_rows, positions, prompt_len = self._embed(params, batch, peft)
        aux: Dict[str, Any] = {}
        for gi, plan in enumerate(self.plan):
            h, ga, _ = self._group_apply(
                params["groups"][gi], plan, h, ids=ids, e_rows=e_rows,
                positions=positions, peft=peft, rng=rng, gcache=None,
                decode_pos=None, prompt_len=prompt_len)
            for k, v in ga.items():
                aux[k] = aux.get(k, 0.0) + v
        h = L.apply_norm(self.cfg, params["final_norm"], h)
        if peft and peft["method"] == "bitfit":
            h = h + peft["params"]["bitfit"]["final"].astype(h.dtype)
        if prompt_len:
            h = h[:, prompt_len:]
        return h, aux

    def logits(self, params, batch, peft=None, rng=None):
        h, aux = self.forward(params, batch, peft, rng)
        return self.unembed(params, h), aux

    def classify(self, params, batch, peft, rng=None):
        """Paper setting: pooled representation -> trainable classification head."""
        h, aux = self.forward(params, batch, peft, rng)
        pooled = h.mean(axis=1) if self.cfg.is_encoder_only else h[:, -1]
        head = peft["params"]["head"]
        dt = self.opts.compute_dtype
        return pooled.astype(dt) @ head["w"].astype(dt) + head["b"].astype(dt), aux

    # ------------------------------------------------------------------
    # caches / serving
    # ------------------------------------------------------------------
    def _cache_len(self, max_len: int) -> int:
        cfg = self.cfg
        if (cfg.attn_kind == "swa" and self.opts.swa_ring_cache
                and cfg.sliding_window and cfg.sliding_window < max_len):
            return cfg.sliding_window
        return max_len

    def _block_cache_spec(self, kind: str, batch: int, max_len: int):
        cfg = self.cfg
        dt = self.opts.compute_dtype
        if kind == BLOCK_ATTN:
            S_c = self._cache_len(max_len)
            sh = (batch, S_c, cfg.num_kv_heads, cfg.head_dim)
            return {"k": jax.ShapeDtypeStruct(sh, dt),
                    "v": jax.ShapeDtypeStruct(sh, dt)}
        if kind == BLOCK_RGLRU:
            w = cfg.lru_width or cfg.d_model
            return {"conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, w), dt),
                    "h": jax.ShapeDtypeStruct((batch, w), dt)}
        if kind == BLOCK_MLSTM:
            di = 2 * cfg.d_model
            H = cfg.num_heads
            hd = di // H
            return {"conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, di), dt),
                    "state": (jax.ShapeDtypeStruct((batch, H, hd, hd), jnp.float32),
                              jax.ShapeDtypeStruct((batch, H, hd), jnp.float32),
                              jax.ShapeDtypeStruct((batch, H), jnp.float32))}
        if kind == BLOCK_SLSTM:
            d = cfg.d_model
            f32 = jnp.float32
            return {"conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, d), dt),
                    "state": {n: jax.ShapeDtypeStruct((batch, d), f32)
                              for n in ("h", "c", "n", "m")}}
        raise ValueError(kind)

    def cache_specs(self, batch: int, max_len: int):
        """ShapeDtypeStruct cache pytree (for AOT lowering of serve_step)."""
        out = []
        for plan in self.plan:
            g = {}
            for u, kind in enumerate(plan.kinds):
                spec = self._block_cache_spec(kind, batch, max_len)
                g[f"b{u}"] = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((plan.repeats,) + s.shape, s.dtype),
                    spec)
            out.append(g)
        return out

    def init_cache(self, batch: int, max_len: int):
        specs = self.cache_specs(batch, max_len)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
        # mLSTM stabilizer m must start at -inf-ish
        for gi, plan in enumerate(self.plan):
            for u, kind in enumerate(plan.kinds):
                if kind == BLOCK_MLSTM:
                    c, n, m = cache[gi][f"b{u}"]["state"]
                    cache[gi][f"b{u}"]["state"] = (c, n, jnp.full(m.shape, -1e30, m.dtype))
                if kind == BLOCK_SLSTM:
                    st = cache[gi][f"b{u}"]["state"]
                    st["m"] = jnp.full(st["m"].shape, -1e30, st["m"].dtype)
        return cache

    def paged_cache_specs(self, num_blocks: int, block_size: int):
        """ShapeDtypeStruct pytree for the paged KV pool: per attention unit
        a global (R, num_blocks, block_size, kvh, hd) K/V page pool shared
        by every request. Attention-only stacks (recurrent state has no
        paged layout)."""
        cfg = self.cfg
        dt = self.opts.compute_dtype
        out = []
        for plan in self.plan:
            g = {}
            for u, kind in enumerate(plan.kinds):
                assert kind == BLOCK_ATTN, (
                    f"paged KV pool is attention-only, got {kind}")
                sh = (plan.repeats, num_blocks, block_size,
                      cfg.num_kv_heads, cfg.head_dim)
                g[f"b{u}"] = {"k": jax.ShapeDtypeStruct(sh, dt),
                              "v": jax.ShapeDtypeStruct(sh, dt)}
            out.append(g)
        return out

    def init_paged_cache(self, num_blocks: int, block_size: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.paged_cache_specs(num_blocks, block_size))

    def prefill(self, params, batch, peft=None, *, max_len: int):
        """Run the prompt, build the cache. Returns (last_logits, cache, pos)."""
        cache = self.init_cache(_batch_size(batch), max_len)
        h, ids, e_rows, positions, prompt_len = self._embed(params, batch, peft)
        new_cache = []
        for gi, plan in enumerate(self.plan):
            h, _, gc = self._group_apply(
                params["groups"][gi], plan, h, ids=ids, e_rows=e_rows,
                positions=positions, peft=peft, rng=None, gcache=cache[gi],
                decode_pos=None, prompt_len=prompt_len)
            new_cache.append(gc)
        h = L.apply_norm(self.cfg, params["final_norm"], h)
        logits = self.unembed(params, h[:, -1:])
        n = batch_len(batch)
        if peft and peft["method"] == "ptv2":   # prefix kv occupies cache slots
            n += peft["opt"].prompt_len
        pos = jnp.asarray(n, jnp.int32)
        return logits, new_cache, pos

    def decode_step(self, params, tokens, pos, cache, peft=None,
                    rope_pos=None, extra: Optional[dict] = None):
        """One decode step over a contiguous cache (``init_cache``).
        tokens: (b, 1); pos: scalar int32 — cache slot of the new token —
        or a per-row (b,) vector when every row sits at its own depth.
        ``rope_pos`` overrides the positional index when they differ, e.g.
        ptv2 prefixes occupy cache slots but not rope positions.
        Returns (logits (b,1,V), new_cache)."""
        cfg = self.cfg
        dt = self.opts.compute_dtype
        batch = {"tokens": tokens}
        if extra:
            batch.update(extra)
        ids = tokens
        E = params["embed"].get("tok")
        e_rows = jnp.take(E, ids, axis=0) if E is not None else None
        h = e_rows.astype(dt) if e_rows is not None else batch["frames"].astype(dt)
        if cfg.embed_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), dt)
        rp = rope_pos if rope_pos is not None else pos
        if rp.ndim == 0:
            positions = rp[None]            # (1,): shared across the batch
        elif jnp.ndim(pos) == 1 and rp.shape[0] == tokens.shape[0]:
            positions = rp[:, None]         # (b, 1): per-row positions
        else:
            positions = rp
        if cfg.pos_type == "learned":
            pe = jnp.take(params["embed"]["pos"], positions, axis=0).astype(dt)
            h = h + (pe if pe.ndim == 3 else pe[None])
        new_cache = []
        for gi, plan in enumerate(self.plan):
            h, _, gc = self._group_apply(
                params["groups"][gi], plan, h, ids=ids, e_rows=e_rows,
                positions=positions, peft=peft, rng=None, gcache=cache[gi],
                decode_pos=pos, prompt_len=0)
            new_cache.append(gc)
        h = L.apply_norm(cfg, params["final_norm"], h)
        return self.unembed(params, h), new_cache

    def mixed_step(self, params, tokens, token_rows, token_pos, cache,
                   peft=None, block_tables=None, logit_idx=None):
        """One unified ragged prefill+decode step against a paged KV pool —
        the serve path's single device call per scheduler tick, replacing
        the old ``extend_step`` (prefill chunk) / ``decode_step`` (append)
        pair.

        tokens: (T, 1) — the tick's PACKED token list: each decode row
        contributes its one fed-back token, every in-flight prefill its
        next prompt chunk (several prompts' chunks pack into one call,
        each chunk a contiguous span of the list), free slots nothing
        (zero padding compute beyond the static T). token_rows: (T,) each
        token's owning pool slot; token_pos: (T,) its absolute position,
        ``-1`` marking a dead padding token (outputs zeros, KV lands on
        the scratch page). Every token's new KV scatters directly into
        its slot's block-table-mapped pool pages (``init_paged_cache``
        layout) in ONE launch — chunks from different slots land in their
        own tables' pages — and attends causally over its slot's resident
        kv ``<= token_pos``: chunk tokens see their lower-positioned
        chunk-mates because the whole scatter precedes attention, and
        never another slot's chunk. ``logit_idx``: (num_slots,) per-SLOT
        index into the packed axis whose logits to report (a decode row's
        token; a final prefill chunk's last prompt token; slots without a
        report position may point anywhere). Causal attention-only
        stacks. Returns (logits (num_slots, V), new_cache).
        """
        cfg = self.cfg
        kinds = {k for plan in self.plan for k in plan.kinds}
        assert kinds <= {BLOCK_ATTN}, (
            f"the unified mixed step needs attention-only stacks, got {kinds}")
        assert cfg.causal and not cfg.prefix_lm_len, (
            "the unified mixed step relies on causal masking")
        assert block_tables is not None, "mixed_step serves paged pools only"
        dt = self.opts.compute_dtype
        ids = tokens
        e_rows = jnp.take(params["embed"]["tok"], ids, axis=0)
        h = e_rows.astype(dt)
        if cfg.embed_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), dt)
        positions = jnp.maximum(token_pos, 0)[:, None]          # (T, 1)
        if cfg.pos_type == "learned":
            h = h + jnp.take(params["embed"]["pos"], positions, axis=0).astype(dt)
        new_cache = []
        for gi, plan in enumerate(self.plan):
            h, _, gc = self._group_apply(
                params["groups"][gi], plan, h, ids=ids, e_rows=e_rows,
                positions=positions, peft=peft, rng=None, gcache=cache[gi],
                decode_pos=token_pos, prompt_len=0,
                block_tables=block_tables, token_rows=token_rows)
            new_cache.append(gc)
        with jax.named_scope("logits"):
            h = L.apply_norm(cfg, params["final_norm"], h)
            if logit_idx is None:
                logit_idx = jnp.arange(h.shape[0], dtype=jnp.int32)
            h_sel = jnp.take(h[:, 0], logit_idx, axis=0)        # (slots, d)
            logits = self.unembed(params, h_sel[:, None])[:, 0]
        return logits, new_cache


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _stack_unit(dicts):
    """[{...}, {...}] per unit position -> {"b0": ..., "b1": ...} for ys."""
    return {f"b{u}": d for u, d in enumerate(dicts)}


def _batch_size(batch) -> int:
    for v in batch.values():
        return v.shape[0]
    raise ValueError("empty batch")


def batch_len(batch) -> int:
    key = "tokens" if "tokens" in batch else "frames"
    return batch[key].shape[1]

"""Architecture assembler: every assigned config becomes one of these.

Layers repeat in ``cfg.block_pattern`` units; repeated units are stacked and
executed with ``jax.lax.scan`` (keeps HLO size and compile time independent
of depth — essential for the 512-device dry-run of 80-layer models), with
optional per-unit activation rematerialization. Remainder layers
(n_layers % len(pattern)) are instantiated unstacked.

Supports: decoder-only LM (dense/MoE), VLM (stub patch-embedding prefix),
encoder-decoder (stub audio frames), recurrent/hybrid families; training
forward, prefill, and single-token decode with per-kind caches.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.precision import as_dtype
from repro.engine import Engine, LayerWeight, as_engine
from repro.kernels.tuning import LANE
from repro.models import attention, common, ffn, moe, rglru, xlstm
from repro.models.attention import AttnConfig
from repro.obs import WeightPrep, count_weight_prep, weight_prep_tally

Params = dict[str, Any]


class CBProfile(NamedTuple):
    """What the continuous-batching StateStore must provision for a model.

    needs_kv_pages: any attention layer present — KV pages get reserved per
        request; attention-free (pure-recurrent) archs reserve zero pages.
    kv_window: set when EVERY attention layer is sliding-window — pages
        whose positions fall out of the window can be recycled mid-request
        and admission reserves only a window's worth of pages.
    has_state_rows: any recurrent layer present — the serving layer must
        disable prefix caching (shared KV pages cannot stand in for the
        skipped positions' recurrent state updates).
    """

    needs_kv_pages: bool
    kv_window: int | None
    has_state_rows: bool = False


def _keys(path) -> tuple:
    return tuple(getattr(k, "key", None) for k in path)


def _dense_weight(path) -> bool:
    """A dense layer's ``w``, which ``common.dense_apply`` feeds to the
    engine; the MoE router's ``w`` goes to ``jnp.matmul`` in float32."""
    keys = _keys(path)
    return keys[-1] == "w" and keys[-2:-1] != ("router",)


def _gemm_weight(path) -> bool:
    """A leaf the engine reads as a GEMM weight: a dense ``w``, or an MoE
    layer's expert stacks."""
    keys = _keys(path)
    return _dense_weight(path) or (
        keys[-2:-1] == ("moe",) and keys[-1] in ("up", "gate", "down")
    )


def _row_mask(mask, leaf):
    """Broadcast a (B,) mask over a (B, ...) state leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def _slot_mappings(page_table, starts, lengths, active, slots, t: int, *,
                   page_size: int) -> attention.PagedInfo:
    """Slot mappings of one StateStore step over P rows of ``t`` tokens.

    page_table: (P, Pps) page ids in position order; starts: (P,) position
    of each row's first fresh token (= tokens already committed); lengths:
    (P,) valid tokens per right-padded row, or None on a decode step;
    active: (P,) rows taking part — the others write the null page;
    slots: (P,) each row's state row.

    It makes two shapes. ``t == 1`` is the decode form: the fresh token is
    written, then the row attends over its pages up to and including it,
    which is what the page-walk kernel reads; a width-1 row is valid iff
    active. ``t > 1`` is the chunked form: the row attends over its
    committed history gathered through the page table, followed by its
    fresh keys (gathered entries at positions >= start are masked, so no
    key counts twice).
    """
    rows, pps = page_table.shape
    n_tok = pps * page_size
    lpos = jnp.arange(n_tok, dtype=jnp.int32)[None]
    if t == 1:
        page = jnp.take_along_axis(
            page_table, (starts // page_size)[:, None], axis=1)[:, 0]
        write_idx = jnp.where(active, page * page_size + starts % page_size, 0)
        k_pos = jnp.where(lpos <= starts[:, None], lpos, attention.POS_SENTINEL)
    else:
        tok = jnp.arange(t, dtype=jnp.int32)
        pos = starts[:, None] + tok[None, :]
        valid = (tok[None, :] < lengths[:, None]) & active[:, None]
        page = jnp.take_along_axis(
            page_table, jnp.clip(pos // page_size, 0, pps - 1), axis=1)
        write_idx = jnp.where(
            valid, page * page_size + pos % page_size, 0).reshape(rows * t)
        read_pos = jnp.where(lpos < starts[:, None], lpos, attention.POS_SENTINEL)
        fresh_pos = jnp.where(valid, pos, attention.POS_SENTINEL)
        k_pos = jnp.concatenate([read_pos, fresh_pos], axis=1)
    read_idx = (
        page_table[:, :, None] * page_size
        + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    ).reshape(rows, n_tok)
    return attention.PagedInfo(
        write_idx=write_idx, read_idx=read_idx, k_pos=k_pos, slots=slots,
        starts=starts, lengths=lengths, active=active, pages=page_table,
        page_size=page_size,
    )


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """Distribution context handed to layers that need explicit collectives."""

    mesh: Any = None
    dp_axes: Any = None  # batch-sharding axes, e.g. ("pod", "data")
    ep_axis: str | None = None  # expert-parallel axis, e.g. "model"
    # Tensor-parallel axis; None = FSDP mode (the whole mesh is data-parallel,
    # parameters are fully sharded and gathered per use).
    tp_axis: str | None = "model"

    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]


def _dp_size(mc: MeshCtx) -> int:
    if mc.mesh is None or not mc.dp_axes:
        return 1
    n = 1
    for a in mc.dp_axes:
        n *= mc.mesh.shape[a]
    return n


class Transformer:
    def __init__(self, cfg: ModelConfig, mesh_ctx: MeshCtx | None = None,
                 engine: Engine | None = None):
        self.cfg = cfg
        # The model's engine: numerics (policy) + execution (backend, tiles)
        # in one immutable handle. Step factories may pass an override engine
        # per traced step (repro.training); entry points accept engine=.
        self.engine: Engine = (
            as_engine(engine) if engine is not None
            else Engine(policy=cfg.policy, backend=getattr(cfg, "backend", "xla"))
        )
        self.policy = self.engine.policy
        self.backend = self.engine.backend
        self.mesh_ctx = mesh_ctx or MeshCtx()
        # fp8 parameter storage (paper: fp8 across "memory", 16-bit compute).
        self.dtype = jnp.float8_e4m3fn if cfg.fp8_params else self.policy.compute
        self.kv_dtype = as_dtype(cfg.kv_cache_dtype)
        self.pattern = tuple(cfg.block_pattern)
        self.n_units, self.n_rem = divmod(cfg.n_layers, len(self.pattern))
        self.embed_scale = (
            math.sqrt(cfg.d_model) if "gemma" in cfg.name else 1.0
        )
        self.xl_cfg = xlstm.XLSTMConfig(cfg.d_model, cfg.n_heads)
        self.rg_cfg = rglru.RGLRUConfig(cfg.d_model, cfg.d_rnn)
        self.moe_cfg = moe.MoEConfig(
            cfg.n_experts, cfg.top_k, cfg.d_model, cfg.d_ff,
            cfg.capacity_factor, cfg.moe_impl, cfg.act,
        ) if cfg.is_moe else None

    # -- distribution ------------------------------------------------------
    def _constrain(self, x):
        """Sequence-parallel boundary sharding (beyond-paper optimization):
        between blocks, activations shard over ('pod','data') on batch and
        over 'model' on the sequence dim — GSPMD inserts the Megatron-SP
        all-gather/reduce-scatter pairs around attention/FFN. Cuts boundary
        activation memory by the TP factor (required to fit 33B/76B train
        cells) and replaces TP all-reduces with reduce-scatters."""
        mc = self.mesh_ctx
        if mc.mesh is None or x.ndim != 3:
            return x
        import numpy as _np

        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = mc.tp_size
        n_dp = int(_np.prod([mc.mesh.shape[a] for a in mc.dp_axes])) if mc.dp_axes else 1
        b_ax = mc.dp_axes if x.shape[0] % n_dp == 0 and x.shape[0] >= n_dp else None
        s_ax = (
            mc.tp_axis
            if mc.tp_axis is not None and x.shape[1] % tp == 0 and x.shape[1] >= tp
            else None
        )
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mc.mesh, P(b_ax, s_ax, None))
        )

    # -- attention configs -------------------------------------------------
    def attn_cfg(self, kind: str, kv_chunk: int = 512) -> AttnConfig:
        cfg = self.cfg
        return AttnConfig(
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
            rope_fraction=cfg.rope_fraction,
            softcap=cfg.attn_softcap,
            window=cfg.sliding_window if kind == "attn_local" else None,
            kv_chunk=kv_chunk,
        )

    # -- init ---------------------------------------------------------------
    def _init_block(self, key, kind: str, cross: bool = False):
        cfg = self.cfg
        keys = jax.random.split(key, 6)
        p: Params = {"norm1": common.norm_init(cfg.d_model, cfg.norm)}
        if kind in ("attn", "attn_local"):
            p["attn"] = attention.init(keys[0], cfg.d_model, self.attn_cfg(kind), self.dtype)
        elif kind == "mlstm":
            p["cell"] = xlstm.mlstm_init(keys[0], self.xl_cfg, self.dtype)
        elif kind == "slstm":
            p["cell"] = xlstm.slstm_init(keys[0], self.xl_cfg, self.dtype)
        elif kind == "rglru":
            p["cell"] = rglru.init(keys[0], self.rg_cfg, self.dtype)
        else:
            raise ValueError(kind)
        if cross:
            p["norm_x"] = common.norm_init(cfg.d_model, cfg.norm)
            p["cross"] = attention.init(keys[1], cfg.d_model, self.attn_cfg("attn"), self.dtype)
        if cfg.d_ff > 0:
            p["norm2"] = common.norm_init(cfg.d_model, cfg.norm)
            if self.moe_cfg is not None:
                p["moe"] = moe.init(keys[2], self.moe_cfg, self.dtype)
            else:
                p["ffn"] = ffn.init(keys[2], cfg.d_model, cfg.d_ff, cfg.act, self.dtype)
        return p

    def _init_stack(self, key, n_layers: int, cross: bool):
        """(stacked units, remainder blocks) for one decoder/encoder stack."""
        n_units, n_rem = divmod(n_layers, len(self.pattern))
        ku, kr = jax.random.split(key)

        def init_unit(k):
            ks = jax.random.split(k, len(self.pattern))
            return {
                f"b{j}": self._init_block(ks[j], kind, cross)
                for j, kind in enumerate(self.pattern)
            }

        units = jax.vmap(init_unit)(jax.random.split(ku, n_units))
        rem = {
            f"r{i}": self._init_block(k, self.pattern[i], cross)
            for i, k in enumerate(jax.random.split(kr, max(n_rem, 1))[:n_rem])
        }
        return {"units": units, "rem": rem}

    def init(self, key) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        p: Params = {
            "embed": common.embed_init(keys[0], cfg.vocab_size, cfg.d_model, self.dtype),
            "decoder": self._init_stack(keys[1], cfg.n_layers, cfg.is_encoder_decoder),
            "final_norm": common.norm_init(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            p["head"] = common.dense_init(keys[2], cfg.d_model, cfg.vocab_size, self.dtype)
        if cfg.family == "vlm":
            p["vis_proj"] = common.dense_init(keys[3], cfg.d_model, cfg.d_model, self.dtype)
        if cfg.is_encoder_decoder:
            # Encoder: same dims, bidirectional attention blocks, no cross.
            enc = Transformer(
                dataclasses.replace(
                    cfg, n_layers=cfg.n_encoder_layers, n_encoder_layers=0,
                    block_pattern=("attn",),
                ),
                self.mesh_ctx,
            )
            p["encoder"] = enc._init_stack(keys[4], cfg.n_encoder_layers, False)
            p["enc_final_norm"] = common.norm_init(cfg.d_model, cfg.norm)
            p["enc_proj"] = common.dense_init(keys[5], cfg.d_model, cfg.d_model, self.dtype)
        return p

    def serving_params(self, params, engine: Engine | None = None) -> Params:
        """Params for serving: every GEMM weight in the form the engine's
        kernel reads, prepared once here instead of in every step.

        Where the policy's forward storage format is narrower than a GEMM
        weight's dtype, the weight is cast to it, and a tied embedding
        gains ``unembed``: the table transposed to (d_model, vocab), cast,
        and zero-padded on the vocabulary to the 128 lane
        (``common.unembed_apply`` cuts the logits back). ``table`` stays
        for the lookup. One jitted program builds the new leaves; the
        others are the caller's. Where nothing is narrower (``tpu_bf16``,
        ``fp32``, weights already in E4M3) the input is returned as it is.
        The cast is the one each call would make, so a step reads the same
        operands. Training never calls this: its weights change every step.
        """
        eng = as_engine(engine) if engine is not None else self.engine
        store = jnp.dtype(eng.policy.storage_fwd)

        def narrower(a) -> bool:
            return (jnp.issubdtype(a.dtype, jnp.floating)
                    and jnp.dtype(a.dtype).itemsize > store.itemsize)

        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        if not flat:
            return params
        cast = [i for i, (path, a) in enumerate(flat)
                if _gemm_weight(path) and narrower(a)]
        embed = params.get("embed", {})
        tie = (self.cfg.tie_embeddings and "unembed" not in embed
               and narrower(embed["table"]))
        if not cast and not tie:
            return params
        vocab = self.cfg.vocab_size

        def prepare(weights, table):
            weights = [w.astype(store) for w in weights]
            if table is None:
                return weights, None
            pad = -vocab % LANE
            return weights, jnp.pad(table.T.astype(store), ((0, 0), (0, pad)))

        weights, unembed = jax.jit(prepare)(
            [flat[i][1] for i in cast], embed["table"] if tie else None)
        leaves = [a for _, a in flat]
        for i, w in zip(cast, weights):
            leaves[i] = w
        out = jax.tree_util.tree_unflatten(treedef, leaves)
        if tie:
            out["embed"] = {**out["embed"], "unembed": unembed}
        return out

    def _in_place(self, units, engine: Engine):
        """(units to slice per layer, rebuild) for a serving step's scan.

        A stacked dense weight already in the engine's forward storage
        format stays whole: ``rebuild(unit, i)`` hands it to the layer as
        ``LayerWeight(stack, i)``, which the kernel reads in place. Sliced
        by the scan, it would be copied out of the stack before every
        kernel call."""
        store = jnp.dtype(engine.policy.storage_fwd)
        flat, treedef = jax.tree_util.tree_flatten_with_path(units)
        keep = [_dense_weight(path) and jnp.dtype(a.dtype) == store
                for path, a in flat]
        sliced = [None if k else a for (_, a), k in zip(flat, keep)]

        def rebuild(unit, i):
            return jax.tree_util.tree_unflatten(treedef, [
                LayerWeight(a, i) if k else u
                for (_, a), k, u in zip(flat, keep, unit)
            ])

        return sliced, rebuild

    # -- block application ---------------------------------------------------
    def _apply_block(
        self, kind, p, x, positions, engine, *, cache=None, enc_out=None,
        enc_pos=None, causal=True, decode=False, paged=None,
    ):
        cfg = self.cfg
        new_cache = {} if cache is not None else None
        # Named scopes mark each layer's ops (casts and pads included) in
        # the compiled program's metadata, so a profiler trace splits a
        # step's device time by layer: embed, attn, recurrent, ffn, moe,
        # lm_head, sample.
        attn = kind in ("attn", "attn_local")
        with jax.named_scope("attn" if attn else "recurrent"):
            h = common.norm_apply(p["norm1"], x, cfg.norm)
            if attn:
                acfg = self.attn_cfg(kind)
                h, ac = attention.apply(
                    p["attn"], h, positions, acfg, engine,
                    cache=None if cache is None else cache["attn"],
                    causal=causal, mesh_ctx=self.mesh_ctx, paged=paged,
                )
                if new_cache is not None:
                    new_cache["attn"] = ac
            elif kind in ("mlstm", "slstm", "rglru"):
                h, st = self._recurrent_block(kind, p, h, cache, engine,
                                              decode=decode, paged=paged)
                if new_cache is not None:
                    new_cache["state"] = st
        x = x + h
        if "cross" in p:
            with jax.named_scope("attn"):
                hx = common.norm_apply(p["norm_x"], x, cfg.norm)
                if enc_out is None:
                    # decode: use the cross-KV cached at prefill time
                    ck = cache["cross_k"].astype(engine.policy.compute)
                    cv = cache["cross_v"].astype(engine.policy.compute)
                    cp = enc_pos
                    new_cache["cross_k"] = cache["cross_k"]
                    new_cache["cross_v"] = cache["cross_v"]
                else:
                    acfg = self.attn_cfg("attn")
                    ck = common.dense_apply(p["cross"]["k"], enc_out, engine)
                    cv = common.dense_apply(p["cross"]["v"], enc_out, engine)
                    b, se, _ = enc_out.shape
                    ck = ck.reshape(b, se, acfg.n_kv_heads, acfg.head_dim)
                    cv = cv.reshape(b, se, acfg.n_kv_heads, acfg.head_dim)
                    cp = enc_pos
                    if new_cache is not None:
                        new_cache["cross_k"] = ck.astype(self.kv_dtype)
                        new_cache["cross_v"] = cv.astype(self.kv_dtype)
                    ck = ck.astype(engine.policy.compute)
                    cv = cv.astype(engine.policy.compute)
                hx, _ = attention.apply(
                    p["cross"], hx, positions, self.attn_cfg("attn"), engine,
                    cross_kv=(ck, cv, cp), mesh_ctx=self.mesh_ctx,
                )
            x = x + hx
        aux = jnp.zeros((), jnp.float32)
        if "ffn" in p or "moe" in p:
            with jax.named_scope("moe" if "moe" in p else "ffn"):
                h2 = common.norm_apply(p["norm2"], x, cfg.norm)
                if "moe" in p:
                    mc = self.mesh_ctx
                    h2, aux = moe.apply(
                        p["moe"], h2, self.moe_cfg, engine,
                        mesh=mc.mesh, dp_axes=mc.dp_axes, ep_axis=mc.ep_axis,
                    )
                else:
                    h2 = ffn.apply(p["ffn"], h2, cfg.act, engine)
            x = x + h2
        return x, new_cache, aux

    def _recurrent_cell_fns(self, kind):
        if kind == "mlstm":
            return xlstm.mlstm_apply, xlstm.mlstm_decode, xlstm.mlstm_init_state, self.xl_cfg
        if kind == "slstm":
            return xlstm.slstm_apply, xlstm.slstm_decode, xlstm.slstm_init_state, self.xl_cfg
        return rglru.apply_scan, rglru.apply_decode, rglru.init_state, self.rg_cfg

    def _recurrent_block(self, kind, p, h, cache, engine, *, decode, paged):
        """One recurrent cell under every execution mode.

        Static (paged None): training forward / whole-prompt prefill /
        batch-shared decode, state carried per batch row. Slot-aware
        (paged set): the cache entry is the (n_slots, ...) state pool —
        prefill gathers each row's state (fresh init when the chunk starts
        at position 0, i.e. a recycled slot resets by construction), runs a
        masked scan over the right-padded chunk, and commits rows back;
        decode covers all slots in order, committing only active rows.
        """
        apply_fn, decode_fn, init_fn, ccfg = self._recurrent_cell_fns(kind)
        if decode:
            st_in = cache["state"]
            h, st = decode_fn(p["cell"], h, st_in, ccfg, engine)
            if paged is not None and paged.active is not None:
                st = jax.tree.map(
                    lambda new, old: jnp.where(_row_mask(paged.active, new), new, old),
                    st, st_in,
                )
            return h, st
        if paged is not None and cache is not None:
            rows = jax.tree.map(lambda v: v[paged.slots], cache["state"])
            init = init_fn(h.shape[0], ccfg)
            fresh = paged.starts == 0
            st_in = jax.tree.map(
                lambda i, r: jnp.where(_row_mask(fresh, r), i.astype(r.dtype), r),
                init, rows,
            )
            h, st = apply_fn(p["cell"], h, ccfg, engine,
                             state=st_in, lengths=paged.lengths)
            st = jax.tree.map(
                lambda pool, new: pool.at[paged.slots].set(
                    jnp.where(_row_mask(paged.active, new),
                              new.astype(pool.dtype), pool[paged.slots])
                ),
                cache["state"], st,
            )
            return h, st
        h, st = apply_fn(p["cell"], h, ccfg, engine)
        return h, st

    def _run_stack(
        self, stack, x, positions, engine, *, cache=None, enc_out=None,
        enc_pos=None, causal=True, decode=False, paged=None,
    ):
        """Scan the stacked units, then the remainder blocks."""
        n_units = self.n_units if stack is not None else 0
        aux_total = jnp.zeros((), jnp.float32)
        # The unit is traced once and runs n_units times: its weight
        # preparation counts that many times (the last trace's, should the
        # scan trace it again).
        unit_prep = WeightPrep()

        def unit_apply(x, unit_p, unit_c):
            nonlocal unit_prep
            new_c = {} if unit_c is not None else None
            aux_sum = jnp.zeros((), jnp.float32)
            with weight_prep_tally() as unit_prep:
                for j, kind in enumerate(self.pattern):
                    x, c, aux = self._apply_block(
                        kind, unit_p[f"b{j}"], x, positions, engine,
                        cache=None if unit_c is None else unit_c[f"b{j}"],
                        enc_out=enc_out, enc_pos=enc_pos, causal=causal,
                        decode=decode, paged=paged,
                    )
                    if new_c is not None:
                        new_c[f"b{j}"] = c
                    aux_sum += aux
            return self._constrain(x), new_c, aux_sum

        if n_units:
            units_cache = cache["units"] if cache is not None else None

            if units_cache is None:
                def body(carry, p):
                    x, aux_acc = carry
                    x, _, aux = unit_apply(x, p, None)
                    return (x, aux_acc + aux), None
                xs = stack["units"]
            else:
                # A step with a cache serves (no gradients): weights stored
                # as the kernel reads them are read in place.
                units, rebuild = self._in_place(stack["units"], engine)

                def body(carry, xs_):
                    x, aux_acc = carry
                    p, i, c = xs_
                    x, new_c, aux = unit_apply(x, rebuild(p, i), c)
                    return (x, aux_acc + aux), new_c
                xs = (units, jnp.arange(n_units, dtype=jnp.int32), units_cache)

            if self.cfg.remat == "block":
                body = jax.checkpoint(body)
            (x, aux_total), new_units_cache = jax.lax.scan(body, (x, aux_total), xs)
            count_weight_prep(unit_prep.bytes * n_units,
                              calls=unit_prep.calls * n_units)
        else:
            new_units_cache = cache["units"] if cache is not None else None

        new_rem = {}
        for i in range(len(stack["rem"])):
            kind = self.pattern[i % len(self.pattern)]
            x, c, aux = self._apply_block(
                kind, stack["rem"][f"r{i}"], x, positions, engine,
                cache=None if cache is None else cache["rem"][f"r{i}"],
                enc_out=enc_out, enc_pos=enc_pos, causal=causal, decode=decode,
                paged=paged,
            )
            aux_total += aux
            new_rem[f"r{i}"] = c
        new_cache = None
        if cache is not None:
            new_cache = {"units": new_units_cache, "rem": new_rem}
        return x, new_cache, aux_total

    # -- embedding / heads ----------------------------------------------------
    def embed(self, params, tokens, engine: Engine | None = None):
        eng = as_engine(engine) if engine is not None else self.engine
        with jax.named_scope("embed"):
            x = common.embed_apply(params["embed"], tokens).astype(eng.policy.compute)
            return x * self.embed_scale

    def logits(self, params, h, engine: Engine | None = None):
        eng = as_engine(engine) if engine is not None else self.engine
        if self.cfg.tie_embeddings:
            out = common.unembed_apply(params["embed"], h, eng)
        else:
            out = common.dense_apply(params["head"], h, eng)
        out = out.astype(jnp.float32)
        out = common.softcap(out, self.cfg.final_softcap)
        # Vocab-parallel logits: keep the vocab dim sharded over the TP axis
        # so the loss reduces per-shard and only (B, c) scalars cross the
        # wire (Megatron vocab-parallel CE) instead of full logit tensors.
        mc = self.mesh_ctx
        if (
            mc.mesh is not None
            and mc.tp_axis is not None
            and self.cfg.vocab_size % mc.tp_size == 0
        ):
            from jax.sharding import NamedSharding, PartitionSpec as P

            b_ax = mc.dp_axes if h.shape[0] % _dp_size(mc) == 0 else None
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mc.mesh, P(b_ax, None, mc.tp_axis))
            )
        return out

    def _encode(self, params, frames, engine: Engine):
        """Audio encoder on stub frame embeddings (B, S_enc, d)."""
        x = common.dense_apply(
            params["enc_proj"], frames.astype(engine.policy.compute), engine
        )
        pos = jnp.arange(frames.shape[1], dtype=jnp.int32)
        # Encoder stack: pattern is ("attn",) for encoders in this zoo.
        enc = Transformer(
            dataclasses.replace(
                self.cfg, n_layers=self.cfg.n_encoder_layers,
                n_encoder_layers=0, block_pattern=("attn",),
            ),
            self.mesh_ctx,
            engine=engine,
        )
        x, _, _ = enc._run_stack(params["encoder"], x, pos, engine, causal=False)
        return common.norm_apply(params["enc_final_norm"], x, self.cfg.norm), pos

    # -- public entry points ---------------------------------------------------
    def forward(self, params, batch, *, engine: Engine | None = None):
        """Teacher-forced forward. Returns (hidden (B,S,d), aux_loss).

        batch: {"tokens": (B, S)} (+ "vis_embeds" (B,P,d) for vlm,
        + "frames" (B,S_enc,d) for audio enc-dec). ``engine`` overrides the
        model's configured engine for this call (step-factory plumbing).
        """
        cfg = self.cfg
        eng = as_engine(engine) if engine is not None else self.engine
        tokens = batch["tokens"]
        x = self.embed(params, tokens, engine=eng)
        enc_out = enc_pos = None
        if cfg.family == "vlm":
            vis = common.dense_apply(
                params["vis_proj"], batch["vis_embeds"].astype(eng.policy.compute), eng
            )
            x = jnp.concatenate([vis, x], axis=1)
        if cfg.is_encoder_decoder:
            enc_out, enc_pos = self._encode(params, batch["frames"], eng)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x = self._constrain(x)
        x, _, aux = self._run_stack(
            params["decoder"], x, positions, eng, enc_out=enc_out, enc_pos=enc_pos
        )
        x = common.norm_apply(params["final_norm"], x, cfg.norm)
        if cfg.family == "vlm":
            x = x[:, batch["vis_embeds"].shape[1]:]
        return x, aux

    # -- caches -----------------------------------------------------------------
    def _block_cache(self, kind, batch, max_len, cross_len=0):
        c: Params = {}
        if kind in ("attn", "attn_local"):
            acfg = self.attn_cfg(kind)
            alloc = min(max_len, acfg.window) if acfg.window else max_len
            c["attn"] = attention.init_cache(batch, alloc, acfg, self.kv_dtype)
        elif kind == "mlstm":
            c["state"] = xlstm.mlstm_init_state(batch, self.xl_cfg)
        elif kind == "slstm":
            c["state"] = xlstm.slstm_init_state(batch, self.xl_cfg)
        elif kind == "rglru":
            c["state"] = rglru.init_state(batch, self.rg_cfg)
        if cross_len:
            acfg = self.attn_cfg("attn")
            c["cross_k"] = jnp.zeros(
                (batch, cross_len, acfg.n_kv_heads, acfg.head_dim), self.kv_dtype
            )
            c["cross_v"] = jnp.zeros_like(c["cross_k"])
        return c

    def init_cache(self, batch: int, max_len: int, cross_len: int = 0):
        def unit_cache(_):
            return {
                f"b{j}": self._block_cache(kind, batch, max_len, cross_len)
                for j, kind in enumerate(self.pattern)
            }

        units = jax.vmap(unit_cache)(jnp.arange(self.n_units)) if self.n_units else None
        rem = {
            f"r{i}": self._block_cache(
                self.pattern[i % len(self.pattern)], batch, max_len, cross_len
            )
            for i in range(self.n_rem)
        }
        return {"pos": jnp.zeros((), jnp.int32), "units": units, "rem": rem,
                "enc_pos": jnp.arange(max(cross_len, 1), dtype=jnp.int32)}

    # -- slot-aware serving (repro.serving continuous batching) -----------------
    def supports_cb(self) -> bool:
        """Continuous batching covers every decoder-only family: attention
        layers page K/V through the token pool, recurrent layers (rglru,
        m/sLSTM) keep per-slot state rows with masked prefill commits.
        Enc-dec and VLM need modality prefixes and stay static-batch."""
        return (
            not self.cfg.is_encoder_decoder
            and self.cfg.family not in ("vlm", "audio")
        )

    def cb_profile(self) -> CBProfile:
        """Pool-layout profile the serving layer sizes its StateStore and
        page reservations from (see ``CBProfile``)."""
        attn_kinds = [k for k in self.pattern if k in ("attn", "attn_local")]
        window = None
        if (
            attn_kinds
            and all(k == "attn_local" for k in attn_kinds)
            and self.cfg.sliding_window
        ):
            window = self.cfg.sliding_window
        return CBProfile(
            needs_kv_pages=bool(attn_kinds), kv_window=window,
            has_state_rows=any(
                k not in ("attn", "attn_local") for k in self.pattern
            ),
        )

    def init_state_store(self, num_slots: int, num_pages: int, page_size: int):
        """Per-layer serving state: attention layers get flat KV token pools
        of num_pages * page_size slots (page 0 is the serving layer's null
        page); recurrent layers get per-slot state rows, one (num_slots, ...)
        array per state leaf. Same {units, rem} layout as ``init_cache`` so
        ``_run_stack`` threads them unchanged."""
        if not self.supports_cb():
            raise NotImplementedError(
                f"{self.cfg.name}: continuous batching covers decoder-only "
                f"families (family={self.cfg.family}); use the static-batch "
                "path (make_serve_steps)"
            )
        n_tok = num_pages * page_size

        def block_pool(kind):
            if kind in ("attn", "attn_local"):
                return {"attn": attention.init_paged_pool(
                    n_tok, self.attn_cfg(kind), self.kv_dtype
                )}
            _, _, init_fn, ccfg = self._recurrent_cell_fns(kind)
            return {"state": init_fn(num_slots, ccfg)}

        def unit_pool(_):
            return {
                f"b{j}": block_pool(kind)
                for j, kind in enumerate(self.pattern)
            }

        units = jax.vmap(unit_pool)(jnp.arange(self.n_units)) if self.n_units else None
        rem = {
            f"r{i}": block_pool(self.pattern[i % len(self.pattern)])
            for i in range(self.n_rem)
        }
        return {"units": units, "rem": rem}

    def prefill_cb(self, params, tokens, pools, page_rows, slots, starts,
                   lengths, active=None, *, page_size: int,
                   engine: Engine | None = None):
        """One prefill chunk for each of P slots of the StateStore.

        tokens: (P, Tb) right-padded chunks; page_rows: (P, Pps) each
        slot's page ids; slots/starts/lengths: (P,) state row, absolute
        position of the chunk's first token, valid tokens in the chunk;
        active: (P,) bool marking the real rows (default: all). Each row
        attends over ITS committed K/V, gathered back through ITS page row,
        followed by its fresh chunk, and commits its own state row (start
        == 0 resets it — that is how a recycled slot forgets its previous
        request). Rows never mix, so P rows give the logits and pools of P
        one-row calls. Pad tokens compute garbage that never escapes:
        their keys are masked (POS_SENTINEL), their K/V writes land in the
        null page, and masked scans skip their state updates. Inactive rows
        must carry slot ids distinct from every active row in the call —
        their masked state write-back scatters the OLD row value, which
        would race a real update on a shared index. Returns (logits (P, V)
        at each row's last valid position, new pools)."""
        eng = as_engine(engine) if engine is not None else self.engine
        b, s = tokens.shape
        act = jnp.ones((b,), bool) if active is None else jnp.asarray(active)
        lengths = jnp.asarray(lengths)
        paged = _slot_mappings(
            page_rows, jnp.asarray(starts), lengths, act, jnp.asarray(slots),
            s, page_size=page_size,
        )
        x, new_pools = self._run_chunk(params, tokens, pools, paged, eng)
        with jax.named_scope("lm_head"):
            x = common.norm_apply(params["final_norm"], x, self.cfg.norm)
            last = jnp.clip(lengths - 1, 0, s - 1)[:, None, None]
            x_last = jnp.take_along_axis(x, last, axis=1)  # (P, 1, D)
            logits = self.logits(params, x_last, engine=eng)
        return logits[:, 0], new_pools

    def decode_cb(self, params, tokens, pools, page_table, seq_lens, active,
                  *, page_size: int, engine: Engine | None = None):
        """Slot-batched one-token decode over the StateStore.

        tokens: (S, 1) last sampled token per slot; page_table: (S, P) page
        ids in position order; seq_lens: (S,) tokens already cached per slot
        (= the new token's position); active: (S,) which slots are decoding.
        Inactive rows — free slots AND slots mid chunked-prefill — write
        K/V to the null page, keep their recurrent state rows untouched,
        and produce discarded logits, so the step stays one fixed shape
        regardless of which slots are live. Returns (logits (S, V), new
        pools)."""
        eng = as_engine(engine) if engine is not None else self.engine
        paged = _slot_mappings(
            page_table, seq_lens, None, active,
            jnp.arange(tokens.shape[0], dtype=jnp.int32), 1,
            page_size=page_size,
        )
        x = self.embed(params, tokens, engine=eng)
        x, new_pools, _ = self._run_stack(
            params["decoder"], x, seq_lens[:, None], eng, cache=pools,
            decode=True, paged=paged,
        )
        with jax.named_scope("lm_head"):
            x = common.norm_apply(params["final_norm"], x, self.cfg.norm)
            logits = self.logits(params, x, engine=eng)
        return logits[:, 0], new_pools

    def verify_cb(self, params, tokens, pools, page_table, seq_lens, lengths,
                  active, *, page_size: int, commit: bool,
                  engine: Engine | None = None):
        """Slot-batched multi-token verify step for speculative decoding.

        tokens: (S, T) per-slot rows [last committed token, draft_1..draft_k]
        right-padded; page_table: (S, P); seq_lens: (S,) tokens already
        committed per slot (= the first fresh position); lengths: (S,) valid
        tokens per row (0 for rows sitting this round out); active: (S,)
        rows taking part. The slot mappings are ``prefill_cb``'s, over all
        slots at once, but logits come back for EVERY position (S, T, V):
        logits[:, i] is the target distribution after token i, which is
        what judges draft i+1.

        ``commit`` (trace-time) gates recurrent state-row commits. The
        verify pass runs with commit=False: the accepted prefix is not known
        yet, so state rows must stay at the pre-step boundary; the server
        then re-runs the same step with commit=True and ``lengths`` clamped
        to accepted+1, re-scanning exactly the accepted tokens into the
        rows (K/V rewrites are bit-identical). K/V needs no such second
        thought in the commit=False pass — writes past the boundary the
        host later refuses to advance ``seq_lens`` over are never read back
        as valid, so rejected drafts roll back for free.
        """
        eng = as_engine(engine) if engine is not None else self.engine
        n_slots, t = tokens.shape
        paged = _slot_mappings(
            page_table, seq_lens, lengths, active,
            jnp.arange(n_slots, dtype=jnp.int32), t, page_size=page_size,
        )
        if not commit:
            paged = paged._replace(active=jnp.zeros_like(active))
        x, new_pools = self._run_chunk(params, tokens, pools, paged, eng)
        with jax.named_scope("lm_head"):
            x = common.norm_apply(params["final_norm"], x, self.cfg.norm)
            logits = self.logits(params, x, engine=eng)
        return logits, new_pools

    def _run_chunk(self, params, tokens, pools, paged, eng):
        """Embed (P, T) fresh tokens at their rows' positions and run the
        stack over the StateStore. Returns (hidden (P, T, D), new pools)."""
        positions = paged.starts[:, None] + jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None, :]
        x = self.embed(params, tokens, engine=eng)
        x, new_pools, _ = self._run_stack(
            params["decoder"], x, positions, eng, cache=pools, paged=paged
        )
        return x, new_pools

    def prefill(self, params, batch, cache, *, engine: Engine | None = None):
        """Run the prompt through the decoder, filling caches."""
        cfg = self.cfg
        eng = as_engine(engine) if engine is not None else self.engine
        tokens = batch["tokens"]
        x = self.embed(params, tokens, engine=eng)
        enc_out = enc_pos = None
        if cfg.family == "vlm":
            vis = common.dense_apply(
                params["vis_proj"], batch["vis_embeds"].astype(eng.policy.compute), eng
            )
            x = jnp.concatenate([vis, x], axis=1)
        if cfg.is_encoder_decoder:
            enc_out, enc_pos = self._encode(params, batch["frames"], eng)
        positions = cache["pos"] + jnp.arange(x.shape[1], dtype=jnp.int32)
        x, new_cache, _ = self._run_stack(
            params["decoder"], x, positions, eng, cache=cache,
            enc_out=enc_out, enc_pos=enc_pos,
        )
        with jax.named_scope("lm_head"):
            x = common.norm_apply(params["final_norm"], x, cfg.norm)
            logits = self.logits(params, x[:, -1:], engine=eng)
        new_cache["pos"] = cache["pos"] + x.shape[1]
        new_cache["enc_pos"] = cache["enc_pos"]
        return logits, new_cache

    def decode_step(self, params, tokens, cache, *, engine: Engine | None = None):
        """One-token decode. tokens: (B, 1)."""
        eng = as_engine(engine) if engine is not None else self.engine
        x = self.embed(params, tokens, engine=eng)
        positions = cache["pos"] + jnp.arange(1, dtype=jnp.int32)
        x, new_cache, _ = self._run_stack(
            params["decoder"], x, positions, eng, cache=cache, decode=True,
            enc_pos=cache.get("enc_pos"),
        )
        with jax.named_scope("lm_head"):
            x = common.norm_apply(params["final_norm"], x, self.cfg.norm)
            logits = self.logits(params, x, engine=eng)
        new_cache["pos"] = cache["pos"] + 1
        new_cache["enc_pos"] = cache["enc_pos"]
        return logits, new_cache

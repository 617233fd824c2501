"""LM assembly: block registry + scan-over-super-blocks transformer.

The depth dimension is folded into a ``jax.lax.scan`` over *super-blocks*
(one repetition of ``cfg.block_pattern``), so HLO size is independent of
depth — mandatory for compiling 94-layer models on one host and the right
structure at cluster scale.  Heterogeneous stacks (gemma3's 5 local : 1
global, zamba2's 5 mamba : 1 shared-attention) are expressed by the pattern;
depths not divisible by the pattern get an unscanned remainder stack.

Leading dense layers (``cfg.first_k_dense``, DeepSeek's layer 0) form an
unscanned ``lead`` stack before the scan, in params and caches alike.

Zamba2's *shared* attention block (one set of weights reused at every
occurrence) lives outside the scanned params and enters the scan body by
closure — parameter sharing that scan's per-step slicing cannot express.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import embedding_engine as ee
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .common import ModelConfig, init_mlp, init_rms, gated_mlp, rms_norm

ATTN_KINDS = ("dense", "dense_local", "moe", "shared_attn", "enc_dense",
              "xdec")
#: blocks whose serving step takes a block of C tokens per slot in one pass
#: (GQA attention + gated MLP: each token's result depends only on the
#: cache and itself, so a pass computes what C micro-steps compute)
PASS_KINDS = ("dense", "dense_local")


# ---------------------------------------------------------------------------
# Block init / apply / decode / cache — registry
# ---------------------------------------------------------------------------

def init_block(kind: str, key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    if kind in ("dense", "dense_local", "enc_dense"):
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "attn": attn.init_attn(ks[1], cfg, dtype),
                "norm2": init_rms(ks[2], cfg.d_model, dtype),
                "mlp": init_mlp(ks[3], cfg.d_model, cfg.d_ff, dtype)}
    if kind == "moe":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "attn": attn.init_attn(ks[1], cfg, dtype),
                "norm2": init_rms(ks[2], cfg.d_model, dtype),
                "moe": moe_mod.init_moe(ks[3], cfg, dtype)}
    if kind == "mla":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "attn": attn.init_mla(ks[1], cfg, dtype),
                "norm2": init_rms(ks[2], cfg.d_model, dtype),
                "moe": moe_mod.init_moe(ks[3], cfg, dtype)}
    if kind == "mla_dense":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "attn": attn.init_mla(ks[1], cfg, dtype),
                "norm2": init_rms(ks[2], cfg.d_model, dtype),
                "mlp": init_mlp(ks[3], cfg.d_model, cfg.d_ff, dtype)}
    if kind == "mamba":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "mamba": ssm_mod.init_mamba(ks[1], cfg, dtype)}
    if kind == "mlstm":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "mlstm": xlstm_mod.init_mlstm(ks[1], cfg, dtype)}
    if kind == "slstm":
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "slstm": xlstm_mod.init_slstm(ks[1], cfg, dtype)}
    if kind == "shared_attn":
        # per-occurrence params are just the norms; weights come shared
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "norm2": init_rms(ks[1], cfg.d_model, dtype)}
    if kind == "xdec":
        k5, k6 = jax.random.split(ks[3])
        return {"norm1": init_rms(ks[0], cfg.d_model, dtype),
                "attn": attn.init_attn(ks[1], cfg, dtype),
                "norm_x": init_rms(ks[2], cfg.d_model, dtype),
                "xattn": attn.init_attn(k5, cfg, dtype),
                "norm2": init_rms(k6, cfg.d_model, dtype),
                "mlp": init_mlp(jax.random.fold_in(key, 7), cfg.d_model,
                                cfg.d_ff, dtype)}
    raise ValueError(kind)


def block_apply(kind: str, p, x, cfg: ModelConfig, ctx: dict):
    """Full-sequence forward. Returns (x, aux_loss)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "dense_local", "enc_dense", "moe", "mla",
                "mla_dense"):
        window = cfg.sliding_window if kind == "dense_local" else None
        causal = kind != "enc_dense"
        h = rms_norm(x, p["norm1"], eps)
        dense = ctx.get("cost_mode", False)
        if kind in ("mla", "mla_dense"):
            h = attn.mla_forward(p["attn"], h, cfg,
                                 positions=ctx["positions"], dense=dense)
        else:
            h = attn.attn_forward(p["attn"], h, cfg,
                                  positions=ctx["positions"],
                                  causal=causal, window=window, dense=dense)
        x = x + h
        h = rms_norm(x, p["norm2"], eps)
        if kind in ("moe", "mla"):
            h, aux, _ = moe_mod.moe_ffn(
                p["moe"], h, cfg, mesh=ctx.get("mesh"),
                ep_axis=ctx.get("ep_axis"),
                data_axes=ctx.get("data_axes", ()))
        else:
            h = gated_mlp(h, p["mlp"], cfg.act)
        return x + h, aux
    if kind == "mamba":
        return x + ssm_mod.mamba_forward(
            p["mamba"], rms_norm(x, p["norm1"], eps), cfg,
            unroll=ctx.get("cost_mode", False)), aux
    if kind == "mlstm":
        return x + xlstm_mod.mlstm_forward(
            p["mlstm"], rms_norm(x, p["norm1"], eps), cfg,
            unroll=ctx.get("cost_mode", False)), aux
    if kind == "slstm":
        return x + xlstm_mod.slstm_forward(
            p["slstm"], rms_norm(x, p["norm1"], eps), cfg,
            cost_mode=ctx.get("cost_mode", False)), aux
    if kind == "shared_attn":
        sp = ctx["shared_params"]
        h = rms_norm(x, p["norm1"], eps)
        h = attn.attn_forward(sp["attn"], h, cfg, positions=ctx["positions"],
                              causal=True,
                              window=ctx.get("shared_window"),
                              dense=ctx.get("cost_mode", False))
        x = x + h
        h = rms_norm(x, p["norm2"], eps)
        return x + gated_mlp(h, sp["mlp"], cfg.act), aux
    if kind == "xdec":
        dense = ctx.get("cost_mode", False)
        h = rms_norm(x, p["norm1"], eps)
        x = x + attn.attn_forward(p["attn"], h, cfg,
                                  positions=ctx["positions"], causal=True,
                                  dense=dense)
        h = rms_norm(x, p["norm_x"], eps)
        x = x + attn.attn_forward(p["xattn"], h, cfg,
                                  positions=ctx["positions"],
                                  causal=False, kv=ctx["enc_out"],
                                  dense=dense)
        h = rms_norm(x, p["norm2"], eps)
        return x + gated_mlp(h, p["mlp"], cfg.act), aux
    raise ValueError(kind)


def init_block_cache(kind: str, cfg: ModelConfig, batch, max_len, dtype):
    if kind in ("dense", "dense_local", "moe", "shared_attn"):
        win = cfg.sliding_window if kind == "dense_local" else None
        alloc = min(max_len, win) if win else max_len
        return attn.init_kv_cache(cfg, batch, alloc if False else max_len,
                                  dtype)
    if kind in ("mla", "mla_dense"):
        return attn.init_mla_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm_mod.init_slstm_cache(cfg, batch, dtype)
    if kind == "xdec":
        return {"self": attn.init_kv_cache(cfg, batch, max_len, dtype),
                "enc_out": None}  # filled at prefill
    if kind == "enc_dense":
        return {}
    raise ValueError(kind)


def block_decode(kind: str, p, x, cfg: ModelConfig, cache: attn.LayerCache,
                 ctx: dict):
    """One decode micro-step of one block.  ``cache`` is the block's
    :class:`~repro.models.attention.LayerCache`; returns (x, cache, counts)
    with the cache written through it, and an MoE block's expert counters
    (:func:`~repro.models.moe.moe_share`) over the slots the cache view
    marks active (None for other blocks)."""
    eps = cfg.norm_eps
    if kind in ("dense", "dense_local", "moe", "mla", "mla_dense",
                "shared_attn"):
        window = cfg.sliding_window if kind == "dense_local" else None
        h = rms_norm(x, p["norm1"], eps)
        if kind in ("mla", "mla_dense"):
            h, cache = attn.mla_decode(p["attn"], h, cfg, cache)
        elif kind == "shared_attn":
            h, cache = attn.attn_decode(ctx["shared_params"]["attn"], h, cfg,
                                        cache,
                                        window=ctx.get("shared_window"))
        else:
            h, cache = attn.attn_decode(p["attn"], h, cfg, cache,
                                        window=window)
        x = x + h
        h = rms_norm(x, p["norm2"], eps)
        counts = None
        if kind in ("moe", "mla"):
            h, _, counts = moe_mod.moe_ffn(
                p["moe"], h, cfg, mesh=ctx.get("mesh"),
                ep_axis=ctx.get("ep_axis"),
                data_axes=ctx.get("data_axes", ()), active=cache.active)
        elif kind == "shared_attn":
            h = gated_mlp(h, ctx["shared_params"]["mlp"], cfg.act)
        else:
            h = gated_mlp(h, p["mlp"], cfg.act)
        return x + h, cache, counts
    recurrent = {"mamba": ssm_mod.mamba_decode,
                 "mlstm": xlstm_mod.mlstm_decode,
                 "slstm": xlstm_mod.slstm_decode}
    if kind in recurrent:
        h, state = recurrent[kind](p[kind], rms_norm(x, p["norm1"], eps), cfg,
                                   cache.read())
        return x + h, cache.write(state), None
    if kind == "xdec":
        h = rms_norm(x, p["norm1"], eps)
        h, self_c = attn.attn_decode(p["attn"], h, cfg, cache.child("self"))
        x = x + h
        h = rms_norm(x, p["norm_x"], eps)
        x = x + attn.attn_forward(p["xattn"], h, cfg,
                                  positions=jnp.zeros((1, 1)),
                                  causal=False, kv=ctx["enc_out"])
        h = rms_norm(x, p["norm2"], eps)
        return x + gated_mlp(h, p["mlp"], cfg.act), \
            cache.with_child("self", self_c), None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardCtx:
    mesh: object = None
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    use_shard_map_embed: bool = True
    remat: str = "none"              # none | dots | full
    # cost mode: scan-free/unrolled FLOP-faithful lowering for the roofline
    # pass (never executed; see repro.roofline docs)
    cost_mode: bool = False


class LM:
    def __init__(self, cfg: ModelConfig, shard: Optional[ShardCtx] = None):
        self.cfg = cfg
        self.shard = shard or ShardCtx()

    @property
    def prefills_in_one_pass(self) -> bool:
        """Whether :meth:`wave_step` feeds a wave's tokens in one pass: every
        block of ``lead``, ``scan`` and ``rest`` is a :data:`PASS_KINDS`
        block.  Other stacks replay the wave micro-step by micro-step."""
        cfg = self.cfg
        return all(k in PASS_KINDS for k in cfg.lead_pattern +
                   tuple(cfg.block_pattern) + tuple(cfg.remainder_pattern))

    @property
    def has_experts(self) -> bool:
        """Whether a block routes over experts (an MoE model)."""
        cfg = self.cfg
        pattern = tuple(cfg.block_pattern) + tuple(cfg.remainder_pattern)
        return bool(cfg.num_experts) and any(k in ("moe", "mla")
                                             for k in pattern)

    def head(self, params):
        """The output head's (vocab, D) matrix: the embedding when tied."""
        return params["embed"] if self.cfg.tie_embeddings \
            else params["lm_head"]

    # ---- Ember program compilation ----
    def embedding_program(self, batch: int, seq: int):
        """All irregular lookups of one (batch, seq) step as one
        :class:`~repro.core.ops.EmbeddingProgram` — what the runtimes
        compile (cached) and reuse across steps."""
        cfg = self.cfg
        tokens = batch * seq
        extra = []
        if self.has_experts:
            extra.append(("moe_dispatch",
                          moe_mod.dispatch_op(cfg, tokens)))
        return ee.model_embedding_program(
            vocab_size=cfg.padded_vocab, d_model=cfg.d_model, tokens=tokens,
            extra_ops=tuple(extra), name=f"{cfg.name}-step")

    def compile_embeddings(self, batch: int, seq: int,
                           opt_level: str = "O3"):
        """Compile this model's embedding program (compile-cache backed)."""
        from ..core.pipeline import compile_program
        return compile_program(self.embedding_program(batch, seq), opt_level)

    def embedding_executor(self, batch: int, seq: int,
                           opt_level: str = "O3", mesh="auto",
                           hot_rows=None, **kw):
        """The steady-state executor of this model's embedding program:
        compile (cached) + device-resident marshaling cache + double-buffered
        step loop (:mod:`repro.core.executor`).  Memoized per signature, so
        every decode wave / train restart gets the same warm executor.

        ``mesh="auto"`` inherits the model's ``ShardCtx`` mesh: with a
        >1-wide model axis the fused stacked tables come back vocab-sharded
        over it (per-device footprint ÷ shards); pass ``mesh=None`` to force
        the replicated single-device executor.  ``hot_rows`` (e.g. from
        :func:`repro.core.access_plan.hot_rows_from_traces` over decode
        token traces) replicates the classified Zipf head of each vocab on
        every shard so those lookups skip the offset-stream exchange.
        ``exchange=``/``replicate_outputs=`` (forwarded via ``**kw``)
        select the sharded exchange mode — the device-collective
        ``all_to_all`` + reduce-scatter default, or the ``"host"`` scatter
        with fully-replicated outputs."""
        from ..core.executor import executor_for
        if mesh == "auto":
            mesh = self.shard.mesh
        return executor_for(self.embedding_program(batch, seq), opt_level,
                            mesh=mesh, shard_axis=self.shard.model_axis,
                            hot_rows=hot_rows, **kw)

    def embedding_table_inputs(self, params) -> dict:
        """The *param-backed* tables of :meth:`embedding_program`, keyed the
        way :meth:`ProgramExecutor.update_tables` wants them.  Deliberately
        partial: per-step operand tables (the MoE capacity buffer) are step
        data, not params — the executor skips their units."""
        return {"tok_embed": {"table": params["embed"]},
                "label_gather": {"table": params["embed"]}}

    # ---- init ----
    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = cfg.jdtype
        keys = jax.random.split(key, 8)
        params = {
            "embed": (jax.random.normal(keys[0],
                                        (cfg.padded_vocab, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        pattern = cfg.block_pattern

        def init_super(k):
            kk = jax.random.split(k, len(pattern))
            return tuple(init_block(kind, kk[i], cfg, dtype)
                         for i, kind in enumerate(pattern))

        supers = [init_super(jax.random.fold_in(keys[1], i))
                  for i in range(cfg.n_super)]
        params["scan"] = jax.tree.map(lambda *xs: jnp.stack(xs), *supers)
        params["rest"] = tuple(
            init_block(kind, jax.random.fold_in(keys[2], i), cfg, dtype)
            for i, kind in enumerate(cfg.remainder_pattern))
        if cfg.first_k_dense:
            params["lead"] = tuple(
                init_block(kind, jax.random.fold_in(keys[6], i), cfg, dtype)
                for i, kind in enumerate(cfg.lead_pattern))
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                keys[7], (cfg.padded_vocab, cfg.d_model), jnp.float32)
                * cfg.d_model ** -0.5).astype(dtype)
        if "shared_attn" in pattern or "shared_attn" in cfg.remainder_pattern:
            params["shared"] = {
                "attn": attn.init_attn(keys[3], cfg, dtype),
                "mlp": init_mlp(keys[4], cfg.d_model, cfg.d_ff, dtype),
            }
        if cfg.enc_layers:
            enc = [init_block("enc_dense", jax.random.fold_in(keys[5], i),
                              cfg, dtype) for i in range(cfg.enc_layers)]
            params["enc_scan"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc)
            params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.modality != "text":
            params["frontend_proj"] = jnp.eye(cfg.d_model, dtype=dtype)
        return params

    # ---- shared machinery ----
    def _batch_axes(self, batch_size: int) -> tuple:
        """Data axes the batch dim can actually shard over (empty when the
        global batch is too small — e.g. long_500k's batch of 1)."""
        sh = self.shard
        if sh.mesh is None:
            return ()
        import numpy as _np
        dsize = int(_np.prod([sh.mesh.shape[a] for a in sh.data_axes]))
        return tuple(sh.data_axes) \
            if batch_size % dsize == 0 and batch_size >= dsize else ()

    def _ctx(self, params, positions, batch_size=None) -> dict:
        sh = self.shard
        return {
            "positions": positions,
            "mesh": sh.mesh,
            "ep_axis": sh.model_axis if sh.mesh is not None else None,
            "data_axes": (self._batch_axes(batch_size)
                          if batch_size is not None else
                          (sh.data_axes if sh.mesh is not None else ())),
            "cost_mode": sh.cost_mode,
            "shared_params": params.get("shared"),
            "shared_window": (self.cfg.sliding_window
                              if self.cfg.family == "hybrid" and
                              not self.cfg.long_context_ok else None),
        }

    def _maybe_remat(self, f):
        r = self.shard.remat
        if r == "none":
            return f
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if r == "dots" else None)
        return jax.checkpoint(f, policy=policy)

    def _stack(self, params, x, ctx):
        cfg = self.cfg
        pattern = cfg.block_pattern
        lead_aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.lead_pattern):
            x, a = block_apply(kind, params["lead"][i], x, cfg, ctx)
            lead_aux = lead_aux + a

        def super_step(carry, layer_params):
            h, aux = carry
            for i, kind in enumerate(pattern):
                h, a = block_apply(kind, layer_params[i], h, cfg, ctx)
                aux = aux + a
            return (h, aux), None

        step = self._maybe_remat(
            lambda c, lp: super_step(c, lp))
        (x, aux), _ = jax.lax.scan(
            step, (x, lead_aux), params["scan"],
            unroll=cfg.n_super if self.shard.cost_mode else 1)
        for i, kind in enumerate(cfg.remainder_pattern):
            x, a = block_apply(kind, params["rest"][i], x, cfg, ctx)
            aux = aux + a
        return x, aux

    def _encode(self, params, enc_embeds, ctx):
        x = enc_embeds @ params["frontend_proj"]

        def step(h, lp):
            h, _ = block_apply("enc_dense", lp, h, self.cfg, ctx)
            return h, None

        x, _ = jax.lax.scan(self._maybe_remat(step), x, params["enc_scan"],
                            unroll=(self.cfg.enc_layers
                                    if self.shard.cost_mode else 1))
        return rms_norm(x, params["enc_norm"], self.cfg.norm_eps)

    # ---- forward / loss ----
    def forward(self, params, batch: dict):
        """batch: {tokens (B,S)} [+ frontend_embeds (B,Sf,D)] [+ enc_embeds].
        Returns hidden states (B,S,D) after final norm."""
        cfg = self.cfg
        sh = self.shard
        tokens = batch["tokens"]
        b, s = tokens.shape
        ba = self._batch_axes(b)
        if sh.mesh is not None and sh.use_shard_map_embed:
            x = ee.lookup(params["embed"], tokens, mesh=sh.mesh,
                          vocab_axis=sh.model_axis,
                          strategy=cfg.embed_strategy,
                          data_axes=ba)
        else:
            x = ee.lookup(params["embed"], tokens, strategy="take")
        if cfg.modality == "vision-stub" and "frontend_embeds" in batch:
            fe = batch["frontend_embeds"] @ params["frontend_proj"]
            x = jnp.concatenate([fe, x[:, fe.shape[1]:]], axis=1)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)[None],
                                     (b, s))
        ctx = self._ctx(params, positions, batch_size=b)
        if cfg.enc_layers:
            ctx["enc_out"] = self._encode(params, batch["enc_embeds"], ctx)
        x, aux = self._stack(params, x, ctx)
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    def loss(self, params, batch: dict):
        cfg = self.cfg
        sh = self.shard
        x, aux = self.forward(params, batch)
        labels = batch["labels"]
        if sh.mesh is not None:
            ce = ee.xent_vocab_parallel(x, self.head(params), labels,
                                        mesh=sh.mesh,
                                        vocab_axis=sh.model_axis,
                                        data_axes=self._batch_axes(
                                            labels.shape[0]))
        else:
            lg = ee.logits(x, self.head(params))
            ce = jnp.mean(jax.nn.logsumexp(lg, -1) -
                          jnp.take_along_axis(lg, labels[..., None],
                                              -1)[..., 0])
        return ce + 0.01 * aux

    # ---- serving ----
    def init_caches(self, batch, max_len, dtype=None):
        cfg = self.cfg
        dtype = dtype or cfg.jdtype
        pattern = cfg.block_pattern

        def one_super():
            return tuple(init_block_cache(kind, cfg, batch, max_len, dtype)
                         for kind in pattern)

        caches = {
            "scan": jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[one_super() for _ in range(cfg.n_super)])
            if cfg.n_super else (),
            "rest": tuple(init_block_cache(k, cfg, batch, max_len, dtype)
                          for k in cfg.remainder_pattern),
        }
        if cfg.first_k_dense:
            caches["lead"] = tuple(
                init_block_cache(k, cfg, batch, max_len, dtype)
                for k in cfg.lead_pattern)
        return caches

    def prefill(self, params, batch: dict, caches):
        """The full-sequence forward's last-position hidden state (B,1,D).
        ``caches`` are neither read nor written: serving fills them through
        :meth:`wave_step`."""
        x, _ = self.forward(params, batch)
        return x[:, -1:]

    def decode_step(self, params, tokens_new, caches, batch_ctx=None,
                    active=None):
        """tokens_new (B,1) -> (logits (B,1,V-sharded…), caches).

        ``active`` (B,) bool masks the continuous-batching batch: inactive
        slots feed a zero token and keep their caches (incl. the per-slot
        ``len`` counter) bit-identical — the property that makes
        prompt-chunked prefill equal whole-prompt prefill regardless of how
        a wave's slots are staggered.

        The cache is written in place: the layer-stacked ``caches["scan"]``
        rides in the layer scan's carry (the scan's inputs are the layer
        params and index), and each layer writes through a
        :class:`~repro.models.attention.LayerCache` view.  A positional
        cache (K/V, int8 K/V, MLA latent) takes one row per slot at
        ``(layer, slot, len)`` — an inactive slot writes back the row it
        holds — before attention reads the layer; a recurrent state (mamba,
        mLSTM, sLSTM) is small and written whole under the mask.  A
        row-written leaf keeps its device's layout.  No op copies or
        selects over a whole layer's positional cache.  The leading dense
        layers (``caches["lead"]``) and the remainder run unscanned."""
        return self._decode(params, tokens_new, caches, batch_ctx,
                            active)[:2]

    def _decode(self, params, tokens_new, caches, batch_ctx, active):
        """:meth:`decode_step`, plus the micro-step's expert counters summed
        over the MoE layers (:func:`~repro.models.moe.moe_share`'s, over
        the active slots), or None for a model without experts."""
        cfg = self.cfg
        if active is not None:
            # zero the fed token so inactive slots compute on a
            # deterministic input (their results are discarded)
            tokens_new = jnp.where(active[:, None], tokens_new, 0)
        x = self._embed_fed(params, tokens_new)
        ctx = self._ctx(params, None, batch_size=tokens_new.shape[0])
        if cfg.enc_layers:
            ctx["enc_out"] = batch_ctx["enc_out"]
        x, new, counts = self._decode_stack(params, x, caches, ctx, active)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = ee.logits(x, self.head(params))[..., :cfg.vocab_size]
        return logits, new, counts

    def _embed_fed(self, params, tokens):
        """The embeddings (B,C,D) of the tokens a wave feeds."""
        cfg = self.cfg
        sh = self.shard
        if sh.mesh is not None and sh.use_shard_map_embed:
            return ee.lookup(params["embed"], tokens, mesh=sh.mesh,
                             vocab_axis=sh.model_axis,
                             strategy=cfg.embed_strategy,
                             data_axes=self._batch_axes(tokens.shape[0]))
        return ee.lookup(params["embed"], tokens, strategy="take")

    def _decode_stack(self, params, x, caches, ctx, active):
        """``x`` through every block, each writing its layer of ``caches``
        through a :class:`~repro.models.attention.LayerCache` view with
        ``active`` — (B,) for a micro-step, (B,C) for a block of C tokens
        per slot.  Returns (x, caches, expert counters or None)."""
        cfg = self.cfg
        pattern = cfg.block_pattern
        counts = jnp.zeros((2,), jnp.int32) if self.has_experts else None

        def unscanned(part, kinds, x, counts):
            new = []
            for i, kind in enumerate(kinds):
                x, view, c = block_decode(
                    kind, params[part][i], x, cfg,
                    attn.LayerCache(caches[part][i], None, active), ctx)
                new.append(view.tree)
                if c is not None:
                    counts = counts + c
            return x, tuple(new), counts

        def super_step(carry, xs):
            h, stack, counts = carry
            layer_params, layer = xs
            stack = list(stack)
            for i, kind in enumerate(pattern):
                h, view, c = block_decode(
                    kind, layer_params[i], h, cfg,
                    attn.LayerCache(stack[i], layer, active), ctx)
                stack[i] = view.tree
                if c is not None:
                    counts = counts + c
            return (h, tuple(stack), counts), None

        x, lead, counts = unscanned("lead", cfg.lead_pattern, x, counts)
        new = {"scan": ()}
        if cfg.n_super:
            (x, new["scan"], counts), _ = jax.lax.scan(
                super_step, (x, caches["scan"], counts),
                (params["scan"], jnp.arange(cfg.n_super)),
                unroll=cfg.n_super if self.shard.cost_mode else 1)
        x, new["rest"], counts = unscanned("rest", cfg.remainder_pattern, x,
                                           counts)
        if cfg.first_k_dense:
            new["lead"] = lead
        return x, new, counts

    def wave_step(self, params, tokens, lens, caches, batch_ctx=None):
        """One serving *wave*.  ``tokens`` (B,C) ragged-right with per-slot
        valid counts ``lens`` (B,); slot b consumes ``tokens[b, :lens[b]]``
        at positions ``len[b] + t``, and a slot with ``lens[b] == 0`` keeps
        its caches bit-identical.

        A stack of :data:`PASS_KINDS` blocks (:attr:`prefills_in_one_pass`)
        feeds the whole (B,C) block in one pass (:meth:`_pass`): each layer
        projects the block, writes each slot's valid rows into its cache
        and attends, so a wave streams the weights and the cache once.
        Every other stack runs a fused ``lax.scan`` of C masked decode
        micro-steps, each exactly :meth:`decode_step` with the ``active =
        t < lens`` mask.  Either way token t sees the same cache and takes
        the same reductions as micro-step t, so splitting a prompt across
        waves of any chunk size gives bit-identical logits and caches —
        prompt-chunked prefill equals whole-prompt prefill.

        ``caches`` stay in their device's layout and are written in place,
        a row (a micro-step) or a block of rows (a pass) per slot per
        layer: with ``caches`` donated a wave copies no whole cache, not
        even at its entry or exit, and the only whole-layer access is
        attention's read.

        Returns ``(logits (B,1,V) at each slot's last valid token, zero
        for a slot fed nothing, caches)``, and for a model with experts a
        third value, the wave's expert counters (2,) int32: the
        token-expert assignments that fell on held experts, and the
        (layer, micro-step, held expert) triples that received at least
        one token, over the slots each micro-step feeds.
        """
        b, c = tokens.shape
        lens = lens.astype(jnp.int32)
        if self.prefills_in_one_pass:
            return self._pass(params, tokens, lens, caches)

        def micro(carry, xs):
            caches, logits_last, counts = carry
            tok, t = xs
            active = t < lens
            logits, caches, n = self._decode(params, tok[:, None], caches,
                                             batch_ctx, active)
            logits_last = jnp.where(active[:, None, None], logits,
                                    logits_last)
            if n is not None:
                counts = counts + n
            return (caches, logits_last, counts), None

        init = (caches,
                jnp.zeros((b, 1, self.cfg.vocab_size), jnp.float32),
                jnp.zeros((2,), jnp.int32) if self.has_experts else None)
        (caches, logits_last, counts), _ = jax.lax.scan(
            micro, init, (tokens.T, jnp.arange(c, dtype=jnp.int32)))
        if counts is None:
            return logits_last, caches
        return logits_last, caches, counts

    def _pass(self, params, tokens, lens, caches):
        """:meth:`wave_step` in one pass over the (B,C) block: the head
        runs only at each slot's last valid row."""
        cfg = self.cfg
        b, c = tokens.shape
        active = jnp.arange(c, dtype=jnp.int32)[None, :] < lens[:, None]
        x = self._embed_fed(params, jnp.where(active, tokens, 0))
        ctx = self._ctx(params, None, batch_size=b)
        x, caches, _ = self._decode_stack(params, x, caches, ctx, active)
        last = jnp.maximum(lens - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = ee.logits(x, self.head(params))[..., :cfg.vocab_size]
        return jnp.where(lens[:, None, None] > 0, logits, 0.0), caches

    def reset_slots(self, caches, keep):
        """Zero the cache state of retired slots (``keep`` (B,) bool) so a
        recycled slot starts from position 0 with no stale KV.  Scan-stacked
        leaves carry batch at axis 1 (leading axis is n_super), ``lead``
        and ``rest`` leaves at axis 0."""
        def mask_at(axis):
            def f(leaf):
                shape = [1] * leaf.ndim
                shape[axis] = keep.shape[0]
                return jnp.where(keep.reshape(shape), leaf,
                                 jnp.zeros_like(leaf))
            return f
        return {part: jax.tree.map(mask_at(1 if part == "scan" else 0), c)
                for part, c in caches.items()}

"""Top-level model: param tree assembly + train/prefill/decode entry points.

``Model`` is the single public handle the launcher, trainer, server and
tests all use.  It is architecture-generic: the config decides dense /
MoE / SSM / hybrid / enc-dec / frontend-stub wiring.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.config import ModelConfig, ParallelConfig
from repro.core.module import P, abstract, materialize, spec_tree
from repro.core.precision import policy_for
from repro.kernels import ops
from repro.models import layers as L
from repro.models import transformer as T
from repro.obs.profile import scoped
from repro.parallel.sharding import ShardingCtx, fit_spec, null_ctx
from repro.parallel.sharding import spec as axis_spec


class Model:
    def __init__(self, cfg: ModelConfig, ctx: Optional[ShardingCtx] = None):
        self.cfg = cfg
        self.ctx = ctx if ctx is not None else null_ctx()
        self.policy = policy_for(cfg)

    # ------------------------------------------------------------ params
    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        defs: Dict[str, Any] = {
            "embed": L.embedding_defs(cfg),
            "layers": T.stack_defs(cfg, cross=cfg.is_encoder_decoder),
            "final_norm": L.norm_defs(cfg, cfg.d_model),
            "head": L.lm_head_defs(cfg),
        }
        if cfg.is_encoder_decoder:
            import dataclasses

            enc_cfg = dataclasses.replace(
                cfg,
                family="dense",
                num_layers=cfg.encoder_layers,
                num_experts=0,
                causal=False,
                is_encoder_decoder=False,
            )
            self._enc_cfg = enc_cfg
            defs["encoder"] = {
                "layers": T.stack_defs(enc_cfg),
                "final_norm": L.norm_defs(enc_cfg, cfg.d_model),
            }
            if cfg.frontend == "audio_stub" and cfg.max_pos:
                defs["encoder"]["pos"] = P(
                    (cfg.max_pos, cfg.d_model), (None, "fsdp"), init="normal", scale=0.02
                )
        if cfg.frontend == "vision_stub":
            # projector from (stub) vision embeddings into the LM stream
            defs["projector"] = {
                "w": P((cfg.d_model, cfg.d_model), ("fsdp", "tp"), fan_in=cfg.d_model),
                "b": P((cfg.d_model,), (None,), init="zeros"),
            }
        return defs

    def init(self, key: jax.Array):
        return materialize(self.param_defs(), key, self.policy.pdt)

    def abstract_params(self):
        return abstract(self.param_defs(), self.policy.pdt)

    def param_specs(self):
        return spec_tree(self.param_defs(), self.ctx.rules)

    # ------------------------------------------------------------ encoder
    def _encode(self, params, batch) -> jax.Array:
        """Run the encoder (enc-dec archs).  Input: precomputed frame
        embeddings (audio stub) or source tokens (seq2seq)."""
        cfg = self.cfg
        cdt = self.policy.cdt
        if "enc_embeds" in batch:  # audio stub: (B, T_enc, d_model)
            x = batch["enc_embeds"].astype(cdt)
            pos = params["encoder"].get("pos")
            if pos is not None:
                x = x + pos[: x.shape[1]].astype(cdt)[None]
        else:
            x = L.embed_apply(cfg, self.ctx, params["embed"], batch["src_tokens"],
                              compute_dtype=cdt)
        enc_cfg = getattr(self, "_enc_cfg", None)
        if enc_cfg is None:
            self.param_defs()  # populates _enc_cfg
            enc_cfg = self._enc_cfg
        x, _, _ = T.decoder_stack(
            enc_cfg, self.ctx, params["encoder"]["layers"], x,
            mode="train", causal=False,
        )
        return L.norm_apply(cfg, self.ctx, params["encoder"]["final_norm"], x)

    # ------------------------------------------------------------ backbone
    def _decoder_input(self, params, batch, mode: str) -> Tuple[jax.Array, Any]:
        cfg = self.cfg
        cdt = self.policy.cdt
        tokens = batch["tokens"]
        x = L.embed_apply(cfg, self.ctx, params["embed"], tokens, compute_dtype=cdt)
        if cfg.frontend == "vision_stub" and "img_embeds" in batch:
            img = batch["img_embeds"].astype(cdt)
            img = img @ params["projector"]["w"].astype(cdt) + params["projector"][
                "b"
            ].astype(cdt)
            x = jnp.concatenate([img, x], axis=1)
        if self.ctx.context_parallel and mode != "decode":
            x = self.ctx.cons(x, "batch", "seq_cp", None)
        else:
            x = self.ctx.cons(x, "batch", None, None)
        return x, None

    def _backbone(
        self, params, x, *, mode, positions=None, caches=None, cache_pos=None,
        cross_kv=None, block_table=None, chunk_valid=None,
    ):
        cfg = self.cfg
        x, new_caches, aux = T.decoder_stack(
            cfg, self.ctx, params["layers"], x,
            mode=mode, positions=positions, caches=caches,
            cache_pos=cache_pos, cross_kv=cross_kv, block_table=block_table,
            chunk_valid=chunk_valid,
        )
        x = L.norm_apply(cfg, self.ctx, params["final_norm"], x)
        return x, new_caches, aux

    def head_weight(self, params):
        return L.lm_head_weight(self.cfg, params["head"], params["embed"])

    @scoped("head")
    def logits(self, params, hidden: jax.Array) -> jax.Array:
        cfg = self.cfg
        w = self.head_weight(params).astype(hidden.dtype)
        lg = hidden @ w
        if cfg.logit_softcap > 0:
            lg = cfg.logit_softcap * jnp.tanh(lg / cfg.logit_softcap)
        if cfg.padded_vocab != cfg.vocab_size:
            # never sample/argmax into the Megatron vocab padding
            pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            lg = jnp.where(pad_mask, lg, -1e30)
        return lg

    # ------------------------------------------------------------ training
    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        cross_kv = self._encode(params, batch) if cfg.is_encoder_decoder else None
        x, _, aux = self._backbone(
            params,
            self._decoder_input(params, batch, "train")[0],
            mode="train",
            cross_kv=cross_kv,
        )
        B, S, D = x.shape
        hidden = x.reshape(B * S, D)
        hidden = self.ctx.cons(hidden, "tokens", None)

        if cfg.objective == "mlm":
            targets = batch["targets"].reshape(-1)
            mask = batch["loss_mask"].reshape(-1).astype(jnp.float32)
        else:  # clm / seq2seq / vlm: next-token over text region
            tokens = batch["tokens"]
            n_front = cfg.num_frontend_tokens if cfg.frontend == "vision_stub" else 0
            # hidden covers [front; text]; predict text token t+1 from position t
            hidden = x[:, n_front:, :][:, :-1, :].reshape(-1, D)
            hidden = self.ctx.cons(hidden, "tokens", None)
            targets = tokens[:, 1:].reshape(-1)
            mask = batch.get("loss_mask")
            mask = (
                mask[:, 1:].reshape(-1).astype(jnp.float32)
                if mask is not None
                else jnp.ones_like(targets, jnp.float32)
            )

        with jax.named_scope("head"):
            w_head = self.head_weight(params).astype(self.policy.cdt)
            # cfg.kernel_impl="auto": fused Pallas CE (fwd + custom-VJP bwd)
            # on TPU so the (tokens × vocab) logits/grad never materialize;
            # blockwise xla elsewhere
            # a Pallas kernel on a mesh runs per token shard, the head
            # replicated
            ts = self.ctx.fit(targets.shape, "tokens")
            ce = self.ctx.per_shard(
                functools.partial(
                    ops.cross_entropy, vocab=cfg.vocab_size,
                    impl=cfg.kernel_impl,
                ),
                (PartitionSpec(*ts, None), PartitionSpec(), ts), (ts, ts),
                when=ops.is_pallas(cfg.kernel_impl),
            )
            losses, _ = ce(hidden, w_head, targets)
            denom = jnp.maximum(mask.sum(), 1.0)
            loss = (losses * mask).sum() / denom
        metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": denom}
        if cfg.num_experts:
            # aux is the layer-summed router stats vector (moe.aux_shape):
            # [lb_loss, entropy_deficit, dropped, slots, per-expert load…]
            lb, ent_def = aux[0], aux[1]
            loss = (
                loss
                + cfg.router_aux_coef * lb
                + cfg.router_entropy_coef * ent_def
            )
            n_moe = max(T.num_moe_layers(cfg), 1)
            metrics["aux_loss"] = lb
            metrics["router_entropy"] = (
                jnp.log(float(cfg.num_experts)) - ent_def / n_moe
            )
            metrics["router_drop_frac"] = aux[2] / jnp.maximum(aux[3], 1.0)
            load = aux[4:]
            metrics["router_load"] = load / jnp.maximum(load.sum(), 1e-9)
        return loss, metrics

    # ------------------------------------------------------------ serving
    def prefill(self, params, batch, max_len: int, *, length=None):
        """Full-sequence forward; returns (last_logits, cache).

        ``length`` (traced scalar ok): the number of VALID tokens when the
        prompt is right-padded to a bucket (engine prompt bucketing) — the
        returned logits come from row ``length - 1`` and the cache position
        is ``length``.  Right padding is only sound for causal attention
        (pad rows are in the future of every real row); the engine gates
        bucketing accordingly.
        """
        cfg = self.cfg
        cross_kv = self._encode(params, batch) if cfg.is_encoder_decoder else None
        x, _ = self._decoder_input(params, batch, "prefill")
        S = x.shape[1]
        x, caches, _ = self._backbone(
            params, x, mode="prefill", cross_kv=cross_kv
        )
        caches = self._pad_caches(caches, S, max_len)
        if length is None:
            last = x[:, -1:, :]
            pos = jnp.int32(S)
        else:
            pos = jnp.asarray(length, jnp.int32)
            last = jax.lax.dynamic_slice_in_dim(x, pos - 1, 1, axis=1)
        lg = self.logits(params, last)
        cache = {"layers": caches, "pos": pos}
        return lg, cache

    def embed_pool(self, params, batch, lengths: jax.Array) -> jax.Array:
        """Masked mean-pooled sequence embeddings: (B, S) tokens +
        (B,) valid lengths -> (B, d_model) float32.

        Runs the full-sequence forward in ``mode="train"`` — no decode
        cache is built (embedding extraction never decodes), and for
        bidirectional (MLM) models the pad tokens are visible to
        attention exactly as they are during training, so pooled vectors
        match what the model was optimized to produce.  Only positions
        ``< lengths[b]`` enter the mean.
        """
        x, _ = self._decoder_input(params, batch, "train")
        x, _, _ = self._backbone(params, x, mode="train")
        S = x.shape[1]
        mask = (
            jnp.arange(S, dtype=jnp.int32)[None, :]
            < jnp.asarray(lengths, jnp.int32)[:, None]
        )
        x = x.astype(jnp.float32) * mask[..., None]
        denom = jnp.maximum(mask.sum(axis=1), 1).astype(jnp.float32)
        return x.sum(axis=1) / denom[:, None]

    def prefill_chunk(self, params, layers, tokens: jax.Array,
                      block_row: jax.Array, start, n_valid):
        """One bounded chunk of an incremental prefill over the paged
        engine cache (prefix caching + chunked prefill, serving engine).

        ``layers`` is the engine cache's ``"layers"`` pytree (shared page
        pools); ``tokens`` is a (1, C) chunk right-padded to a bucket;
        ``block_row`` is (1, pages_per_seq) — the slot's row of the block
        table; ``start`` (traced scalar) is the logical position of the
        chunk's first token (> 0 when a cached prefix was skipped or an
        earlier chunk already ran); ``n_valid`` (traced scalar, <= C) is
        the number of real rows.  The chunk's K/V rows are scattered into
        the slot's pages and attention runs causally over positions
        [0, start + n_valid) through the block table — including pages
        shared from the prefix cache.

        Returns ``(logits, new_layers)`` where ``logits`` (1, 1, V) come
        from the last valid row (only meaningful on the final chunk).

        Only valid for causal attention-only stacks (the same condition
        as prompt bucketing: SSM state and cross-attention cannot skip or
        pad rows); the serving engine gates accordingly.
        """
        cfg = self.cfg
        C = tokens.shape[1]
        positions = jnp.asarray(start, jnp.int32) + jnp.arange(C, dtype=jnp.int32)
        emb_pos = positions[None] if (not cfg.use_rope and cfg.max_pos) else None
        x = L.embed_apply(
            cfg, self.ctx, params["embed"], tokens,
            positions=emb_pos, compute_dtype=self.policy.cdt,
        )
        x = self.ctx.cons(x, "batch", None, None)
        x, new_layers, _ = self._backbone(
            params, x, mode="chunk", positions=positions,
            caches=layers, cache_pos=jnp.asarray(start, jnp.int32),
            block_table=block_row, chunk_valid=jnp.asarray(n_valid, jnp.int32),
        )
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(n_valid, jnp.int32) - 1, 1, axis=1
        )
        return self.logits(params, last), new_layers

    def decode_step(self, params, cache, tokens: jax.Array):
        """One-token step.  tokens: (B, 1).  ``cache["pos"]`` may be a
        scalar (lockstep decoding) or a (B,) vector (continuous batching)."""
        cfg = self.cfg
        pos = cache["pos"]
        vec = jnp.ndim(pos) > 0
        emb_pos = None
        if not cfg.use_rope and cfg.max_pos:
            emb_pos = pos[:, None] if vec else pos[None]
        x = L.embed_apply(
            cfg, self.ctx, params["embed"], tokens,
            positions=emb_pos, compute_dtype=self.policy.cdt,
        )
        x = self.ctx.cons(x, "batch", None, None)
        rope_pos = None if vec else jnp.full((1,), pos, jnp.int32)
        block_table = cache.get("block_table")
        x, new_caches, _ = self._backbone(
            params, x, mode="decode",
            positions=rope_pos,
            caches=cache["layers"], cache_pos=pos, block_table=block_table,
        )
        lg = self.logits(params, x)
        new_cache = {"layers": new_caches, "pos": pos + 1}
        if block_table is not None:
            new_cache["block_table"] = block_table
        return lg, new_cache

    def init_cache(
        self, batch: int, max_len: int, cross_len: int = 0, *,
        layout: str = "dense", page_size: int = 0, num_pages: int = 0,
    ):
        """Preallocated decode cache.

        ``layout="paged"`` builds shared K/V page pools plus a top-level
        ``block_table`` (all-null-page) the serving engine's allocator
        maintains; ``pos`` is per-slot ``(batch,)`` in that layout.

        The cache is built with implicit (single-device) placement even
        when the model carries a mesh ``ShardingCtx``.  Mesh consumers place it
        explicitly via ``cache_shardings``: the serving engine device_puts
        the tree once at construction and pins every per-step jit to the
        same specs.
        """
        if layout == "paged":
            if page_size <= 0 or num_pages <= 1:
                raise ValueError("paged layout needs page_size>0, num_pages>1")
            pages_per_seq = -(-max_len // page_size)
            return {
                "layers": T.init_stack_cache(
                    self.cfg, batch, max_len, self.policy.cdt,
                    cross_len=cross_len, layout="paged",
                    page_size=page_size, num_pages=num_pages,
                ),
                "block_table": jnp.zeros((batch, pages_per_seq), jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
        return {
            "layers": T.init_stack_cache(
                self.cfg, batch, max_len, self.policy.cdt, cross_len=cross_len
            ),
            "pos": jnp.int32(0),
        }

    def cache_specs(self, cache):
        """``PartitionSpec`` tree for a decode cache (same structure as
        ``cache`` — works on concrete arrays or ``jax.eval_shape`` output).

        Mirrors the constraints the layers apply internally
        (models/attention.py, models/ssm.py) so the serving engine can pin
        jit ``in_shardings``/``out_shardings`` without inserting reshard
        collectives into the per-token step: paged ``k_pool``/``v_pool``
        shard over the KV-head (``model``) axis — one logical cache,
        sharded storage — dense K/V over (``cache_batch``,
        ``cache_seq``), SSM state/conv over ``tp``.  The block table and
        positions are host-maintained control state and stay replicated,
        as does the (write-once, batch-1-inserted) cross-attention KV.
        Mesh axes that do not evenly divide a dim are dropped per-dim:
        placement shardings must divide exactly, unlike
        ``with_sharding_constraint``.
        """
        from jax.sharding import PartitionSpec

        ctx = self.ctx
        logical = {
            # (units, P, page, Hkv, D): shared page pools, head-sharded
            "k_pool": (None, None, None, "kv_tp", None),
            "v_pool": (None, None, None, "kv_tp", None),
            # (units, B, T, Hkv, D): dense per-slot KV
            "k": (None, "cache_batch", "cache_seq"),
            "v": (None, "cache_batch", "cache_seq"),
            # (units, B, H, P, N) / (units, B, kw-1, conv_dim)
            "state": (None, "cache_batch", "tp"),
            "conv": (None, "cache_batch", None, "tp"),
        }

        def walk(tree, keys=()):
            if isinstance(tree, dict):
                return {k: walk(v, keys + (k,)) for k, v in tree.items()}
            if ctx.mesh is None:
                return PartitionSpec()
            name = keys[-1] if keys else ""
            axes = () if "xattn" in keys else logical.get(name, ())
            ps = axis_spec(ctx.rules, *axes)
            return fit_spec(tree.shape, ctx.mesh, ps)

        return walk(cache)

    def cache_shardings(self, cache):
        """``NamedSharding`` tree for a decode cache, or ``None`` when the
        model is off-mesh (single-device: placement stays implicit)."""
        from jax.sharding import NamedSharding

        mesh = self.ctx.mesh
        if mesh is None:
            return None
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.cache_specs(cache)
        )

    # -------------------------------------------------------------- utils
    def _pad_caches(self, caches, S: int, max_len: int):
        """Place prefill KV (length S) into preallocated (rolling) buffers."""
        cfg = self.cfg
        W = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len

        def pad_leaf(path_keys, leaf):
            # only attn k/v leaves have a seq dim at axis 2 equal to S
            if leaf.ndim >= 3 and leaf.shape[2] == S and any(
                k in ("k", "v") for k in path_keys
            ) and "xattn" not in path_keys:
                if S <= W:
                    buf = jnp.zeros((leaf.shape[0], leaf.shape[1], W, *leaf.shape[3:]),
                                    leaf.dtype)
                    return jax.lax.dynamic_update_slice(
                        buf, leaf, (0,) * 2 + (0,) * (leaf.ndim - 2)
                    )
                # rolling placement: slot j holds token  S-W + ((j - S) % W)
                slots = jnp.arange(W)
                tok = S - W + ((slots - S) % W)
                return jnp.take(leaf, tok, axis=2)
            return leaf

        def walk(tree, keys=()):
            if isinstance(tree, dict):
                return {k: walk(v, keys + (k,)) for k, v in tree.items()}
            return pad_leaf(keys, tree)

        return walk(caches)


def build_model(
    cfg: ModelConfig, pc: Optional[ParallelConfig] = None, mesh=None
) -> Model:
    pc = pc or ParallelConfig()
    if mesh is not None:
        tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        pc = pc.validate(cfg, tp)
        ctx = ShardingCtx(mesh, pc)
    else:
        ctx = ShardingCtx(None, pc)
    return Model(cfg, ctx)

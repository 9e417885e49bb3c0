"""Core configuration dataclasses for the repro framework.

BioNeMo-style modularity: every model in the zoo is a ``ModelConfig`` plus the
shared substrate.  Configs are plain frozen dataclasses so they hash, print,
and serialize cleanly; ``replace()`` (dataclasses.replace) is the sanctioned
way to derive variants (reduced smoke configs, sliding-window variants, ...).
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


VOCAB_DIVISOR = 256  # Megatron make_vocab_size_divisible_by — faithful.


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  One instance per model-zoo entry."""

    name: str
    family: str  # dense | ssm | moe | hybrid | audio | vlm | bio_bert | bio_encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention options ---
    qkv_bias: bool = False
    attn_out_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    max_pos: int = 0                   # learned absolute positions (use_rope=False)
    sliding_window: int = 0            # 0 = full attention
    causal: bool = True
    attn_logit_softcap: float = 0.0

    # --- block options ---
    norm_type: str = "rmsnorm"         # rmsnorm | layernorm | layernorm_nobias
    act: str = "swiglu"                # swiglu | gelu | geglu | relu
    mlp_bias: bool = False
    parallel_residual: bool = False    # command-r style parallel attn+ffn
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 1
    moe_layer_period: int = 1          # apply MoE every k-th layer
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # entropy-deficit coefficient (log E − mean router entropy): pushes the
    # router toward exploration; 0 keeps the legacy loss exactly
    router_entropy_coef: float = 0.0

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    ssm_ngroups: int = 1
    ssm_conv: int = 4
    attn_layer_period: int = 0         # hybrid: 1 attention layer per k layers

    # --- encoder/decoder & modality frontends ---
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    frontend: str = ""                 # "" | audio_stub | vision_stub
    num_frontend_tokens: int = 0       # patch/frame tokens provided by the stub
    cross_attn_heads: int = 0          # 0 -> num_heads

    # --- objective (bio recipes) ---
    objective: str = "clm"             # clm | mlm | seq2seq
    mlm_mask_prob: float = 0.15

    # --- numerics / kernels ---
    dtype: str = "bfloat16"            # activation/compute dtype
    param_dtype: str = "float32"       # stored parameter dtype
    # hot-path kernel implementation for attention + fused cross-entropy:
    # auto (pallas on TPU, xla elsewhere) | pallas | xla | naive |
    # pallas_interpret (Pallas fwd+bwd kernels interpreted on any backend —
    # the CPU-verifiable training path).  See kernels/README.md.
    kernel_impl: str = "auto"
    citation: str = ""

    # ------------------------------------------------------------------ #
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, VOCAB_DIVISOR)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def is_moe_layer(self, layer_idx: int) -> bool:
        if self.num_experts == 0:
            return False
        return (layer_idx % self.moe_layer_period) == (self.moe_layer_period - 1)

    def is_attn_layer(self, layer_idx: int) -> bool:
        """Hybrid (jamba) interleave: one attention layer per attn_layer_period."""
        if self.family == "ssm":
            return False
        if self.family != "hybrid":
            return True
        p = self.attn_layer_period
        return (layer_idx % p) == (p // 2)  # jamba places attn mid-group

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once; MoE counts all experts)."""
        d, hd = self.d_model, self.resolved_head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        att = d * hd * (nq + 2 * nkv) + nq * hd * d
        if self.act in ("swiglu", "geglu"):
            mlp_dense = 3 * d * self.d_ff
        else:
            mlp_dense = 2 * d * self.d_ff
        total = 0
        for i in range(self.num_layers):
            is_attn = self.is_attn_layer(i)
            if is_attn:
                total += att
            else:  # mamba block
                di, ns = self.d_inner, self.ssm_state
                total += d * (2 * di + 2 * self.ssm_ngroups * ns + self.ssm_nheads)
                total += di * d  # out proj
                total += 3 * self.ssm_nheads  # A, D, dt_bias
            if self.is_moe_layer(i):
                total += (self.num_experts + self.n_shared_experts) * mlp_dense
                total += d * self.num_experts  # router
            elif self.d_ff > 0:
                total += mlp_dense
            total += 2 * d  # norms
        total += self.padded_vocab * d  # embedding
        if not self.tie_embeddings:
            total += self.padded_vocab * d
        if self.is_encoder_decoder:
            enc = self.encoder_layers * (att + mlp_dense + 2 * d)
            xattn = self.num_layers * (att + d)
            total += enc + xattn
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.num_experts == 0:
            return self.param_count()
        d = self.d_model
        if self.act in ("swiglu", "geglu"):
            mlp_dense = 3 * d * self.d_ff
        else:
            mlp_dense = 2 * d * self.d_ff
        inactive = 0
        for i in range(self.num_layers):
            if self.is_moe_layer(i):
                unused = self.num_experts - self.num_experts_per_tok
                inactive += unused * mlp_dense
        return self.param_count() - inactive

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)


@dataclass(frozen=True)
class ParallelConfig:
    """How a model maps onto the mesh.

    attention_parallelism:
      * "head_tp"  — Megatron convention: q-heads sharded over `model`
                     (requires num_heads % tp == 0); KV replicated over
                     `model` when num_kv_heads % tp != 0.
      * "context"  — sequence dim sharded over `model`, GQA KV all-gathered
                     (Llama-3-style CP).  No head-divisibility constraint.
    """

    attention_parallelism: str = "head_tp"   # head_tp | context
    fsdp_axes: Tuple[str, ...] = ("data",)   # axes weights are FSDP-sharded over
    expert_axis: str = "model"

    def validate(self, mc: ModelConfig, tp: int) -> "ParallelConfig":
        """Auto-downgrade head_tp -> context when heads don't divide tp."""
        if self.attention_parallelism == "head_tp" and mc.num_heads % tp != 0:
            return dataclasses.replace(self, attention_parallelism="context")
        return self


@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 256
    seq_len: int = 4096
    # microbatch gradient accumulation: each optimizer step scans
    # accum_steps microbatches of global_batch/accum_steps rows with fp32
    # grad accumulators; accum_steps=N is numerically equivalent to one
    # N×-larger batch (token-weighted — see training/train_step.py)
    accum_steps: int = 1
    learning_rate: float = 1e-3
    min_lr: float = 1e-5
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    decay_steps: int = 1000
    total_steps: int = 1000
    schedule: str = "wsd"      # wsd | cosine | noam | const
    seed: int = 0
    log_every: int = 10
    ckpt_every: int = 0        # 0 = disabled
    ckpt_dir: str = ""
    # non-finite guard (training/loop.py): a step whose loss or global
    # grad-norm is non-finite applies NO update (params/opt state keep
    # their old values, opt.step does not advance) and is counted in the
    # metrics as a skip; this many CONSECUTIVE skips aborts the run with
    # the offending step number instead of silently training on garbage
    max_nonfinite_skips: int = 10


@dataclass(frozen=True)
class ServeConfig:
    max_seq_len: int = 32768
    batch_size: int = 128
    # default sampling knobs, mapped into a default SamplingParams by the
    # LLM facade (serving/api.py); individual requests override them with
    # their own per-request SamplingParams
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # 0 = disabled
    top_p: float = 1.0         # 1.0 = disabled
    seed: int = 0              # keys the counter-based sampling PRNG
    # KV-cache layout for the continuous-batching engine: "dense" per-slot
    # buffers, or "paged" block-table pages over a shared pool
    # (serving/paged_cache.py + kernels/paged_attention.py)
    cache_layout: str = "dense"
    page_size: int = 16        # tokens per page in the paged layout
    # paged-layout serving features (serving/README.md):
    #   prefix_cache — content-addressed sharing of full prompt blocks
    #   (refcounted pages, copy-on-write, LRU eviction of unreferenced
    #   cached pages); prefill skips hash-hit blocks entirely.
    #   prefill_chunk — bound each prefill step to N tokens, interleaved
    #   with decode iterations (0 = prefill the suffix in one chunk).
    prefix_cache: bool = False
    prefill_chunk: int = 0
    # fault tolerance (serving/README.md "Failure semantics"):
    #   max_queue — bounded admission queue; 0 = unbounded.  A full queue
    #   rejects at submit with the typed retriable EngineOverloaded
    #   instead of growing TTFT for everyone.
    #   preempt — under page pressure, evict the newest in-flight decode
    #   and replay it later (token-identical resume) instead of
    #   head-of-line blocking the queue.  Paged layout only.
    #   deadline_ms — default per-request wall-clock SLO from submit
    #   (None = no deadline); individual SamplingParams override it.
    max_queue: int = 0
    preempt: bool = False
    deadline_ms: Optional[float] = None


def reduced(mc: ModelConfig, **over: Any) -> ModelConfig:
    """Smoke-test variant of a config: <=2 layers, d_model<=256, <=4 experts.

    Keeps the *family wiring* (GQA ratios, MoE periods, hybrid interleave)
    so smoke tests exercise the same code paths as the full config.
    """
    d_model = min(mc.d_model, 256)
    nh = max(2, min(mc.num_heads, 4))
    nkv = max(1, min(mc.num_kv_heads, nh))
    while nh % nkv:
        nkv -= 1
    layers = min(mc.num_layers, 2)
    if mc.family == "hybrid":
        layers = mc.attn_layer_period  # one full interleave group
    kw = dict(
        num_layers=layers,
        d_model=d_model,
        num_heads=nh,
        num_kv_heads=nkv,
        head_dim=d_model // nh,
        d_ff=min(mc.d_ff, 512) if mc.d_ff else 0,
        vocab_size=min(mc.vocab_size, 512),
        num_experts=min(mc.num_experts, 4) if mc.num_experts else 0,
        encoder_layers=min(mc.encoder_layers, 2) if mc.encoder_layers else 0,
        num_frontend_tokens=min(mc.num_frontend_tokens, 16) if mc.num_frontend_tokens else 0,
        ssm_headdim=32 if mc.ssm_state else mc.ssm_headdim,
        ssm_state=min(mc.ssm_state, 16) if mc.ssm_state else 0,
        ssm_chunk=8 if mc.ssm_state else mc.ssm_chunk,
        sliding_window=min(mc.sliding_window, 64) if mc.sliding_window else 0,
        dtype="float32",
        param_dtype="float32",
    )
    kw.update(over)
    return dataclasses.replace(mc, **kw)

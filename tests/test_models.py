"""Model-level invariants: decode==prefill consistency across families,
sliding-window cache rotation, MLM masking semantics, param-spec sanity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import ModelConfig, ParallelConfig
from repro.core.module import P, spec_tree
from repro.models.model import build_model
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import axis_rules


def cfg_for(family, **kw):
    base = dict(
        name=f"t-{family}", family=family, num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32",
    )
    if family == "ssm":
        base.update(d_ff=0, num_kv_heads=4, ssm_state=16, ssm_headdim=32, ssm_chunk=8)
    if family == "hybrid":
        # capacity_factor high so prefill-vs-decode routing is drop-free
        # (capacity-based MoE is batch-dependent by design — GShard semantics)
        base.update(num_layers=4, attn_layer_period=4, ssm_state=16,
                    ssm_headdim=32, ssm_chunk=8, capacity_factor=8.0,
                    num_experts=4, num_experts_per_tok=2, moe_layer_period=2)
    if family == "moe":
        base.update(num_experts=4, num_experts_per_tok=1, n_shared_experts=1,
                    capacity_factor=8.0)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_decode_matches_prefill(family):
    cfg = cfg_for(family)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    lg_full, _ = model.prefill(params, {"tokens": toks}, 24)
    _, cache = model.prefill(params, {"tokens": toks[:, :-1]}, 24)
    lg_dec, _ = model.decode_step(params, cache, toks[:, -1:])
    np.testing.assert_allclose(
        np.asarray(lg_full), np.asarray(lg_dec), atol=3e-4, rtol=1e-3
    )


def test_sliding_window_rolling_cache_long_decode():
    """Decode far past the window: rolling cache must equal windowed ref."""
    cfg = cfg_for("dense", sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 30), 0, cfg.vocab_size)
    # ground truth: teacher-forced full forward (window applies inside attn)
    lg_full, _ = model.prefill(params, {"tokens": toks}, 40)
    _, cache = model.prefill(params, {"tokens": toks[:, :20]}, 40)
    lg = None
    for t in range(20, 30):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
    lg_want, _ = model.prefill(
        params, {"tokens": jnp.concatenate([toks, jnp.zeros((1, 0), jnp.int32)], 1)}, 40
    )
    np.testing.assert_allclose(
        np.asarray(lg)[:, -1], np.asarray(lg_want)[:, -1], atol=3e-4, rtol=1e-3
    )
    # cache buffer is window-sized
    k = jax.tree.leaves(cache["layers"])[0]
    assert cfg.sliding_window in k.shape


def test_mlm_loss_only_on_masked_positions():
    cfg = cfg_for("dense", objective="mlm", causal=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 5, cfg.vocab_size)
    tgt = toks
    mask = jnp.zeros((B, S)).at[:, :4].set(1.0)
    batch = {"tokens": toks, "targets": tgt, "loss_mask": mask}
    loss1, _ = model.loss_fn(params, batch)
    # changing UNMASKED targets must not change the loss
    tgt2 = tgt.at[:, 8:].set((tgt[:, 8:] + 7) % cfg.vocab_size)
    loss2, _ = model.loss_fn(params, {**batch, "targets": tgt2})
    assert float(loss1) == pytest.approx(float(loss2), rel=1e-6)


def test_vlm_image_tokens_excluded_from_loss():
    cfg = cfg_for("vlm", frontend="vision_stub", num_frontend_tokens=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 4, cfg.d_model))
    loss, m = model.loss_fn(params, {"tokens": toks, "img_embeds": img})
    # token count in metrics == text next-token positions only
    assert float(m["tokens"]) == 2 * 11


def test_encdec_uses_encoder_output():
    cfg = cfg_for(
        "audio", is_encoder_decoder=True, encoder_layers=2,
        frontend="audio_stub", num_frontend_tokens=8,
        use_rope=False, max_pos=64, norm_type="layernorm", act="gelu",
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, cfg.vocab_size)
    emb1 = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    # NB: a constant shift would be annihilated by LayerNorm (shift
    # invariance) — perturb with noise instead
    emb2 = emb1 + jax.random.normal(jax.random.PRNGKey(3), emb1.shape)
    l1, _ = model.loss_fn(params, {"tokens": toks, "enc_embeds": emb1})
    l2, _ = model.loss_fn(params, {"tokens": toks, "enc_embeds": emb2})
    assert float(l1) != pytest.approx(float(l2))


def test_parallel_residual_structure():
    """command-r style block has a single pre-norm (no norm2 params)."""
    from repro.models.transformer import stack_defs
    cfg = cfg_for("dense", parallel_residual=True)
    defs = stack_defs(cfg)
    assert "norm2" not in defs["sub0"]
    assert "ffn" in defs["sub0"]


def test_param_specs_cover_all_leaves_and_axes_exist():
    import jax.sharding as shd

    for family in ("dense", "moe", "ssm", "hybrid"):
        cfg = cfg_for(family)
        pc = ParallelConfig()
        model = build_model(cfg)
        defs = model.param_defs()
        rules = axis_rules(pc, make_mesh((1, 1), ("data", "model")))
        specs = spec_tree(defs, rules)
        names = {a for s in jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, shd.PartitionSpec))
            for a in s if a is not None
            for a in (a if isinstance(a, tuple) else (a,))}
        assert names <= {"data", "model"}, names


def test_hybrid_interleave_structure():
    cfg = cfg_for("hybrid")
    # unit of 4: attn at index 2 (period//2), ssm elsewhere; moe on odd layers
    from repro.models.transformer import unit_defs
    defs = unit_defs(cfg)
    assert "attn" in defs["sub2"]
    assert "ssm" in defs["sub0"] and "ssm" in defs["sub1"] and "ssm" in defs["sub3"]
    assert "router" in defs["sub1"]["ffn"]      # MoE layer
    assert "router" not in defs["sub0"]["ffn"]  # dense layer


def test_logit_softcap_bounds_logits():
    cfg = cfg_for("dense", logit_softcap=5.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    lg, _ = model.prefill(params, {"tokens": toks}, 16)
    # padded-vocab ids are masked to -inf at serve time; check real vocab
    assert float(jnp.abs(lg[..., : cfg.vocab_size]).max()) <= 5.0 + 1e-3

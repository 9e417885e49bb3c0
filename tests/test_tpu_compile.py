"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode (``pallas_interpret``) runs the kernel math on the CPU but
never asks the TPU compiler (Mosaic) for its verdict on tiling, layouts
or VMEM.  These tests do: each kernel is lowered at real model widths
against ``topologies.get_topology_desc("v5e:2x2")`` and compiled by the
installed TPU compiler, with no chip attached.  A refusal here is exactly
what the chip would raise on the first step.  Nothing runs, so numerics
stay with the interpret-mode parity suites (test_kernels, test_grads,
test_paged_cache, test_sampling).

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, so describing it at
collection would break multi-worker test runs.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode
from repro.kernels.grouped_matmul import gmm, gmm_dw
from repro.kernels.paged_attention import (
    paged_flash_decode,
    paged_flash_prefill,
    paged_kv_write,
)
from repro.kernels.rmsnorm import layernorm, rmsnorm
from repro.kernels.sampling import fused_sample
from repro.kernels.ssd_scan import ssd_scan

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled HLO"
    return text


# (B, S, T, H, Hkv, D, causal): the ESM-2 650M train step, at 8 rows and
# at the benchmark cell's 16 (the tiles it runs), the whisper-medium
# encoder over 1,500 frames (512-row tiles over keys padded to 1,536, so
# masked) and its decoder's cross-attention of a 37-token prompt over
# them, and qwen2-7b prefills of 512 tokens and of the serving engine's
# short buckets: 8 tokens, and 37 (a tile of the whole unaligned length)
ATTN = {
    "esm2-650m": (8, 1024, 1024, 20, 20, 64, False),
    "esm2-650m.mlm": (16, 1024, 1024, 20, 20, 64, False),
    "whisper-medium.encoder": (1, 1500, 1500, 16, 16, 64, False),
    "whisper-medium.cross37": (1, 37, 1500, 16, 16, 64, False),
    "qwen2-7b": (1, 512, 512, 28, 4, 128, True),
    "qwen2-7b.prefill8": (1, 8, 8, 28, 4, 128, True),
    "qwen2-7b.prefill37": (1, 37, 37, 28, 4, 128, True),
}


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_flash_attention_fwd_bwd(chip, arch):
    B, S, T, H, Hkv, D, causal = ATTN[arch]

    def loss(q, k, v):
        o = ops.attention(q, k, v, causal=causal, impl="pallas")
        return jnp.sum(o.astype(F32))

    _compile(
        chip, jax.grad(loss, argnums=(0, 1, 2)),
        ((B, S, H, D), BF16), ((B, T, Hkv, D), BF16), ((B, T, Hkv, D), BF16),
    )


@pytest.mark.parametrize("arch", ["esm2-650m", "whisper-medium.cross37", "qwen2-7b",
                                  "qwen2-7b.prefill8", "qwen2-7b.prefill37"])
def test_flash_attention_forward_only(chip, arch):
    """A call with no backward (a prefill) compiles with one forward
    kernel: the head-major one for a causal call, the lane-dense one
    otherwise (``flash_attention.flash_attention``)."""
    import re

    B, S, T, H, Hkv, D, causal = ATTN[arch]
    text = _compile(
        chip, lambda q, k, v: ops.attention(q, k, v, causal=causal, impl="pallas"),
        ((B, S, H, D), BF16), ((B, T, Hkv, D), BF16), ((B, T, Hkv, D), BF16),
    )
    names = re.findall(r"%(?:\w+?_)?(flash_attention_\w+?)_*\.\d+ = .*tpu_custom_call", text)
    want = "flash_attention_fwd_head_major" if causal else "flash_attention_fwd"
    assert names == [want], names


@pytest.mark.parametrize("arch", ["esm2-650m.mlm", "qwen2-7b"])
def test_flash_attention_signatures(chip, arch):
    """The boundary of the lane-dense kernels, as the model's step has it:
    q, k, v come as the projections write them, (B, S, H·D) rows, and go
    through RoPE first.  The compiled gradient holds one forward, one dQ
    and one dK/dV kernel, under those names, whose bf16 operands are all
    (B, S, H·D) or (B, S, Hkv·D), and nothing around them relayouts q, k,
    v, o or their cotangents: no copy or transpose of a head-major
    (B, H, S, D) or (B·H, S, D) array, of (B, S, H, D), or of any array
    of q's shape, and no fp32 (…, 128) broadcast of per-row statistics.
    At the ESM-2 cell's shape a block holds a head pair; qwen2-7b's GQA
    call takes a head a block."""
    import re

    B, S, _, H, Hkv, D, causal = ATTN[arch]

    def loss(q, k, v):
        q, k = (ops.rope(x.reshape(B, S, h, D), jnp.arange(S), 1e4, impl="pallas")
                for x, h in ((q, H), (k, Hkv)))
        o = ops.attention(q, k, v.reshape(B, S, Hkv, D), causal=causal, impl="pallas")
        return jnp.sum(o.astype(F32))

    text = _compile(
        chip, jax.grad(loss, argnums=(0, 1, 2)),
        ((B, S, H * D), BF16), ((B, S, Hkv * D), BF16), ((B, S, Hkv * D), BF16),
    )
    kernels = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and "flash" in line:
            name = re.search(r"%(?:\w+?_)?(flash_attention_\w+?)_*\.\d+ = ", line)
            kernels.setdefault(name.group(1), []).append(line)
    assert sorted(kernels) == ["flash_attention_dkv", "flash_attention_dq",
                               "flash_attention_fwd"], sorted(kernels)
    rows = {f"bf16[{B},{S},{H * D}]", f"bf16[{B},{S},{Hkv * D}]"}
    for name, lines in kernels.items():
        assert len(lines) == 1, (name, lines)
        blocks = re.findall(r"bf16\[[\d,]+\]", lines[0].split(", custom_call_target")[0])
        assert blocks and set(blocks) <= rows, (name, blocks)
    shapes = "|".join(re.escape(x) for x in (
        f"[{B},{H},{S},{D}]", f"[{B * H},{S},{D}]", f"[{B},{S},{H},{D}]",
        f"[{B},{S},{H * D}]", f"[{B},{Hkv},{S},{D}]", f"[{B * Hkv},{S},{D}]",
        f"[{B},{S},{Hkv},{D}]", f"[{B},{S},{Hkv * D}]"))
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) (\w[\w-]*)\(", line)
        if not op:
            continue
        shape, kind = op.groups()
        assert not (kind in ("copy", "transpose") and re.search(shapes, shape)), line
        assert not (kind == "broadcast" and re.fullmatch(r"f32\[[\d,]*,128\]\S*", shape)), line


# (B, S, H, D, per-row positions): the ESM-2 cell's q, and qwen2-7b's q
# in a prefill of 37 tokens and of a prompt whose rows span several
# blocks, and with a position per row
ROPE = {
    "esm2-650m.mlm": (16, 1024, 20, 64, False),
    "qwen2-7b.prefill37": (1, 37, 28, 128, False),
    "qwen2-7b.prefill4096": (1, 4096, 28, 128, False),
    "qwen2-7b.rows": (8, 256, 28, 128, True),
}


@pytest.mark.parametrize("arch", sorted(ROPE))
def test_rope_fwd_bwd(chip, arch):
    B, S, H, D, rows = ROPE[arch]

    def loss(x, pos):
        return jnp.sum(ops.rope(x, pos, 1e6, impl="pallas").astype(F32))

    _compile(chip, jax.grad(loss), ((B, S, H, D), BF16),
             ((B, S) if rows else (S,), I32))


# (tokens, d_model, padded vocab, vocab)
CE = {
    "esm2-650m": (8192, 1280, 256, 33),
    "qwen2-7b": (512, 3584, 152064, 152064),
}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("arch", sorted(CE))
def test_cross_entropy_fwd_bwd(chip, arch, precision):
    T, D, Vp, V = CE[arch]

    def loss(h, w, t):
        losses, _ = ops.cross_entropy(h, w, t, vocab=V, impl="pallas")
        return jnp.sum(losses)

    # `highest` is how a float32 reference is computed around the kernel
    with jax.default_matmul_precision(precision):
        _compile(
            chip, jax.grad(loss, argnums=(0, 1)),
            ((T, D), BF16), ((D, Vp), BF16), ((T,), I32),
        )


def test_training_kernels_carry_their_names(chip):
    """The training path's kernels pass ``name=`` to ``pallas_call``: the
    compiled HLO names each custom call after its kernel, and the trace
    of a step shows those names (``repro.obs.scope_map`` reads them)."""
    import re

    def loss(q, k, v, h, w, t):
        q = ops.rope(q, jnp.arange(256), 1e4, impl="pallas")
        o = ops.attention(q, k, v, causal=False, impl="pallas")
        ce, _ = ops.cross_entropy(h, w, t, vocab=33, impl="pallas")
        return jnp.sum(o.astype(F32)) + jnp.sum(ce)

    text = _compile(
        chip, jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        ((2, 256, 4, 64), BF16), ((2, 256, 4, 64), BF16),
        ((2, 256, 4, 64), BF16), ((512, 1280), BF16), ((1280, 256), BF16),
        ((512,), I32),
    )
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = ["rope", "rope", "flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv", "cross_entropy_fwd", "cross_entropy_dh",
               "cross_entropy_dw"]
    assert len(calls) == len(kernels), calls
    for k in kernels:
        assert any(re.fullmatch(rf"(ROOT )?%(\w+_)?{k}_*\.\d+", c)
                   for c in calls), (k, calls)
    norm = _compile(
        chip, lambda x, w, b: layernorm(x, w, b),
        ((8192, 1280), BF16), ((1280,), F32), ((1280,), F32),
    )
    assert re.search(r"%layernorm(\.\d+)? = ", norm)


def test_flash_decode(chip):
    # qwen2-7b decode: 8 slots over a 4096-token dense cache
    _compile(
        chip, flash_decode,
        ((8, 1, 28, 128), BF16), ((8, 4096, 4, 128), BF16),
        ((8, 4096, 4, 128), BF16), ((8,), I32),
    )


# qwen2-7b paged pool: 16-token pages, 4 KV heads of 128
PAGES, PAGE, HKV, HD = 512, 16, 4, 128


def test_paged_decode(chip):
    _compile(
        chip, paged_flash_decode,
        ((8, 1, 28, HD), BF16), ((PAGES, PAGE, HKV, HD), BF16),
        ((PAGES, PAGE, HKV, HD), BF16), ((8, 36), I32), ((8,), I32),
    )


@pytest.mark.parametrize("chunk", [37, 128, 512])
def test_paged_prefill(chip, chunk):
    # one prefill chunk attending through a 36-page block table
    _compile(
        chip, paged_flash_prefill,
        ((1, chunk, 28, HD), BF16), ((PAGES, PAGE, HKV, HD), BF16),
        ((PAGES, PAGE, HKV, HD), BF16), ((1, 36), I32), ((1,), I32),
        ((1,), I32),
    )


def test_paged_kv_write(chip):
    _compile(
        chip, paged_kv_write,
        ((PAGES, PAGE, HKV, HD), BF16), ((PAGES, PAGE, HKV, HD), BF16),
        ((8, 1, HKV, HD), BF16), ((8, 1, HKV, HD), BF16), ((8,), I32),
        ((8,), I32),
    )


def test_fused_sample(chip):
    _compile(
        chip, fused_sample,
        ((8, 152064), F32), ((8,), F32), ((8,), I32), ((8,), F32),
        ((8,), jnp.uint32), ((8,), jnp.uint32),
    )


@pytest.mark.parametrize("d", [1280, 3584])
def test_norms(chip, d):
    _compile(chip, lambda x, w: rmsnorm(x, w), ((8192, d), BF16), ((d,), F32))
    _compile(
        chip, lambda x, w, b: layernorm(x, w, b),
        ((8192, d), BF16), ((d,), F32), ((d,), F32),
    )


def test_gmm_fwd_bwd(chip):
    # 8 experts, a 4096-row dispatch at d_model 1024 / d_ff 2048
    M, K, N, E = 4096, 1024, 2048, 8
    _compile(chip, gmm, ((M, K), BF16), ((E, K, N), BF16), ((E,), I32))
    _compile(chip, gmm_dw, ((M, K), BF16), ((M, N), BF16), ((E,), I32))


@pytest.mark.xfail(
    strict=True,
    reason="TPU compiler refuses ssd_scan: 'the last two dimensions of your "
    "block shape are divisible by 8 and 128 respectively, or be equal to the "
    "respective dimensions of the overall array' — block (1, chunk, 1, P) on "
    "(B, S, H, P); needs a head-major layout and a 2-D in-kernel cumsum",
)
def test_ssd_scan(chip):
    # mamba2-2.7b: 80 SSD heads of 64, state 128, one group, chunk 128
    B, S, H, P, G, N = 1, 1024, 80, 64, 1, 128
    _compile(
        chip, lambda *a: ssd_scan(*a, chunk=128),
        ((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
        ((B, S, G, N), BF16), ((B, S, G, N), BF16), ((H,), F32),
    )

"""Plain ESM-2 masked-LM training reference, in jax.numpy and float32.

The layer equations follow the configuration file as it is run
(``bench/configs/<name>.json``): pre-LN encoder blocks with rotary
positions (rotate-half), softmax attention scaled by head_dim^-1/2, a
GELU FFN, a final layer norm, and logits from the embedding (tied head).
``as_run.attention_pad_keys`` says whether pad keys are "visible" to
attention or "masked"; ``hidden_act`` is "gelu" (erf) or "gelu_tanh".
No kernel, cache or sharding of the program is used or imported.

Matrix products run at ``highest`` precision. ``quant="fp8"`` runs every
product of the forward and the backward on operands rounded to fp8 with
one scale per tensor (e4m3 forward, e5m2 for gradients): the control
that the comparison in ``bench/entries/train.py`` has to refuse.

Parameters live in this module's own layout: ``embed`` (vocab, d), the
per-layer tensors of ``LAYER_LEAVES`` stacked over layers, and the final
norm ``lnf_g``/``lnf_b``. ``init_params`` makes them from a seed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv",
                "wo", "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")
HIGHEST = jax.lax.Precision.HIGHEST


def shapes(c: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Leaf name -> (shape, init), init one of normal / ones / zeros."""
    d, ff, n = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    qkv = c["num_attention_heads"] * c["head_dim"]
    per_layer = {
        "ln1_g": ((d,), "ones"), "ln1_b": ((d,), "zeros"),
        "wq": ((d, qkv), "normal"), "bq": ((qkv,), "zeros"),
        "wk": ((d, qkv), "normal"), "bk": ((qkv,), "zeros"),
        "wv": ((d, qkv), "normal"), "bv": ((qkv,), "zeros"),
        "wo": ((qkv, d), "normal"), "bo": ((d,), "zeros"),
        "ln2_g": ((d,), "ones"), "ln2_b": ((d,), "zeros"),
        "w1": ((d, ff), "normal"), "b1": ((ff,), "zeros"),
        "w2": ((ff, d), "normal"), "b2": ((d,), "zeros"),
    }
    out = {"embed": ((c["vocab_size"], d), "normal"),
           "lnf_g": ((d,), "ones"), "lnf_b": ((d,), "zeros")}
    out.update({k: ((n,) + s, i) for k, (s, i) in per_layer.items()})
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 32 bits at a time."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(c: Dict, key: jax.Array) -> Dict[str, jax.Array]:
    """Float32 parameters: matrices and the embedding N(0, initializer_range),
    biases 0, norm gains 1. Call under ``jax.jit``; leaf ``i`` of the sorted
    names draws from ``fold_in(key, i)``, so the values do not depend on how
    the output is sharded."""
    std = c["initializer_range"]
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(shapes(c).items())):
        if init == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        else:
            out[name] = (jnp.ones if init == "ones" else jnp.zeros)(
                shape, jnp.float32)
    return out


# --------------------------------------------------------------------- #
# fp8 control: scaled rounding of every matmul operand and cotangent
# --------------------------------------------------------------------- #
def _round(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    scale = jax.lax.stop_gradient(scale)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_operand(x):
    return _round(x, jnp.float8_e4m3fn)


_q_operand.defvjp(lambda x: (_round(x, jnp.float8_e4m3fn), None),
                  lambda _, g: (g,))


@jax.custom_vjp
def _q_cotangent(x):
    return x


_q_cotangent.defvjp(lambda x: (x, None),
                    lambda _, g: (_round(g, jnp.float8_e5m2),))


def _einsum(eq: str, a, b, quant: str):
    if quant == "fp8":
        out = jnp.einsum(eq, _q_operand(a), _q_operand(b), precision=HIGHEST)
        return _q_cotangent(out)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


# --------------------------------------------------------------------- #
# forward and loss
# --------------------------------------------------------------------- #
def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rope(x, theta):
    """Rotate-half rotary embedding of (B, S, H, D) at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(c: Dict, quant: str, x, key_pad, p):
    B, S, d = x.shape
    H, D = c["num_attention_heads"], c["head_dim"]
    eps = c["layer_norm_eps"]
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    q, k, v = (
        (_einsum("bsd,de->bse", h, p[w], quant) + p[b]).reshape(B, S, H, D)
        for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv"))
    )
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    s = _einsum("bshd,bthd->bhst", q, k, quant) / math.sqrt(D)
    if key_pad is not None:
        s = jnp.where(key_pad[:, None, None, :], -jnp.inf, s)
    o = _einsum("bhst,bthd->bshd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _einsum("bse,ed->bsd", o.reshape(B, S, H * D), p["wo"], quant) + p["bo"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    f = _einsum("bsd,df->bsf", h, p["w1"], quant) + p["b1"]
    f = jax.nn.gelu(f, approximate=c["hidden_act"] == "gelu_tanh")
    return x + _einsum("bsf,fd->bsd", f, p["w2"], quant) + p["b2"]


def loss_sum(c: Dict, quant: str, params, batch) -> Tuple[jax.Array, jax.Array]:
    """(sum of the masked tokens' cross-entropy, number of masked tokens)
    for one block of rows: ``tokens`` (input, corrupted), ``targets``,
    ``loss_mask``."""
    tokens = batch["tokens"]
    pads = c["as_run"]["attention_pad_keys"]
    if pads not in ("visible", "masked"):
        raise ValueError(f"attention_pad_keys {pads!r}")
    key_pad = tokens == c["pad_token_id"] if pads == "masked" else None
    x = params["embed"][tokens]
    layer = jax.checkpoint(functools.partial(_block, c, quant))

    def body(x, p):
        return layer(x, key_pad, p), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_LEAVES})
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], c["layer_norm_eps"])
    logits = _einsum("bsd,vd->bsv", x, params["embed"], quant)
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, batch["targets"][..., None], -1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    return (ce * mask).sum(), mask.sum()


# --------------------------------------------------------------------- #
# training steps
# --------------------------------------------------------------------- #
def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def make_grads(c: Dict, *, quant: str = "", row_block: int = 0):
    """``grads(params, batch) -> (loss, grads)``: the masked mean loss of
    ``batch`` and its gradient, rows taken ``row_block`` at a time."""
    def grads(params, batch):
        rows = batch["tokens"].shape[0]
        rb = row_block or rows
        blocks = jax.tree.map(
            lambda x: x.reshape(rows // rb, rb, *x.shape[1:]), batch)
        zero = jax.tree.map(jnp.zeros_like, params)

        def body(acc, blk):
            (ls, n), g = jax.value_and_grad(
                lambda p: loss_sum(c, quant, p, blk), has_aux=True)(params)
            return (acc[0] + ls, acc[1] + n,
                    jax.tree.map(jnp.add, acc[2], g)), None

        (ls, n, g), _ = jax.lax.scan(body, (0.0, 0.0, zero), blocks)
        n = jnp.maximum(n, 1.0)
        return ls / n, jax.tree.map(lambda x: x / n, g)

    return grads


def make_update(opt: Dict):
    """``update(params, mu, nu, grads, t) -> (params, mu, nu, grad_norms)``:
    AdamW step ``t`` (1-based) after clipping ``grads`` by global norm;
    ``grad_norms`` are the per-leaf norms of the gradient as the optimizer
    gets it."""
    def update(params, mu, nu, g, t):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        g = {k: x * clip for k, x in g.items()}
        b1, b2 = opt["beta1"], opt["beta2"]
        t = jnp.asarray(t, jnp.float32)
        lr = opt["learning_rate"] * jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
        new_p, new_m, new_v = {}, {}, {}
        for k, p in params.items():
            m = b1 * mu[k] + (1 - b1) * g[k]
            v = b2 * nu[k] + (1 - b2) * g[k] * g[k]
            delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
            if p.ndim >= 2:  # decay every tensor of two or more dims
                delta = delta + opt["weight_decay"] * p
            new_p[k], new_m[k], new_v[k] = p - lr * delta, m, v
        return new_p, new_m, new_v, leaf_norms(g)

    return update


def train_readings(c: Dict, opt: Dict, seed: int, batches: Sequence[Dict],
                   *, quant: str = "", row_block: int = 0,
                   shard=None) -> Dict:
    """Run ``len(batches)`` reference steps from the weights of ``seed``.

    Returns ``losses`` (one per step), ``grad_norms`` (per leaf, step 1)
    and ``change_norms`` (per leaf, the norm of the parameters' change
    after the last step). ``shard(name -> shape) -> name -> sharding``
    places the state on several devices; ``None`` keeps it on the default
    one. Adam's moments wait on the host while the gradient is computed,
    so that the device holds the parameters, one gradient and the
    activations of ``row_block`` rows at a time."""
    key = seed_key(seed)
    shardings = shard({k: s for k, (s, _) in shapes(c).items()}) if shard else None
    init = jax.jit(functools.partial(init_params, c), out_shardings=shardings)
    params = init(key)
    grads = jax.jit(make_grads(c, quant=quant, row_block=row_block))
    update = jax.jit(make_update(opt), donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    losses: List[float] = []
    grad_norms, moments = None, None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            loss, g = grads(params, batch)
            if moments is None:
                mu, nu = zeros(g), zeros(g)
            else:
                mu, nu = jax.device_put(moments, (shardings, shardings))
            params, mu, nu, gnorm = update(params, mu, nu, g, t)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(v) for k, v in gnorm.items()}
            moments = jax.device_get((mu, nu)) if t < len(batches) else None
            del mu, nu, g
        change = jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(jnp.subtract, p, init_params(c, k))))(params, key)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(v) for k, v in change.items()}}

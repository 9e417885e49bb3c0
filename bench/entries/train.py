"""Entry: masked-LM pretraining through the program's ``Trainer``.

Set-up builds the corpus of the traffic mix in a temporary memmap
(``MemmapTokenDataset``) read by ``MLMBatches``, the model through
``build_model`` (on four chips over ``launch.train.build_mesh("auto")``,
FSDP over ``data``), the weights on the device in one jitted call from
the seed (``bench/reference/esm2.py``'s generator, in the program's
layout), and one ``Trainer``. That trainer takes its first steps, which
compile; the comparison reads it after step 1 and step 3. One more step
warms up, then the window steps the same trainer until ``seconds`` have
passed and waits for the last step's state.

The window steps at the program's own log interval (``TrainConfig``'s
default): the compared losses are read from the metrics that the
compared steps themselves returned.

After the window the program's state is freed and the reference follows
the first steps from the same seed, on batches whose targets it builds
from the traffic's corpus, made anew from the seed
(``bench/reference/mlm_batches.py``). ``correct`` holds when every
compared number is within its limit (``bench/limits/<cell>.json``):

- ``loss_gap``: the widest relative gap of a step's loss;
- ``grad_norm_gap``: per leaf, the gap between the norms of the first
  gradient as the optimizer gets it (the program's from Adam's first
  moment after step 1), over the larger of the reference's norm of that
  leaf and of the median leaf; the worst leaf;
- ``change_norm_gap``: the same for the norm of the parameters' change
  over the compared steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (a key's bias under softmax);
- ``window_compiles``: programs traced or compiled inside the window;
- over every batch the program drew, set-up's and the window's:
  ``target_slots_wrong``, ``loss_on_non_residue``,
  ``unselected_changed``, ``mask_rate_z`` and ``mask_token_z`` (see
  ``bench/reference/mlm_batches.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import flops
from reference import esm2, mlm_batches

# steps the comparison follows, and warm-up steps after them
CHECK_STEPS = 3
WARM_STEPS = 1

LAYER = ("layers", "sub0")
# reference leaf -> the program's parameter path (models/model.py layout)
PROGRAM_PATHS = {
    "embed": ("embed", "tok"),
    "lnf_g": ("final_norm", "scale"), "lnf_b": ("final_norm", "bias"),
    "ln1_g": LAYER + ("norm1", "scale"), "ln1_b": LAYER + ("norm1", "bias"),
    "wq": LAYER + ("attn", "wq"), "bq": LAYER + ("attn", "bq"),
    "wk": LAYER + ("attn", "wk"), "bk": LAYER + ("attn", "bk"),
    "wv": LAYER + ("attn", "wv"), "bv": LAYER + ("attn", "bv"),
    "wo": LAYER + ("attn", "wo"), "bo": LAYER + ("attn", "bo"),
    "ln2_g": LAYER + ("norm2", "scale"), "ln2_b": LAYER + ("norm2", "bias"),
    "w1": LAYER + ("ffn", "w_in"), "b1": LAYER + ("ffn", "b_in"),
    "w2": LAYER + ("ffn", "w_out"), "b2": LAYER + ("ffn", "b_out"),
}


# --------------------------------------------------------------------- #
# the program's side
# --------------------------------------------------------------------- #
def program_config(c: Dict):
    """The program's ``ModelConfig`` of registry id ``c["registry"]``, with
    the file's ``program_overrides`` if any, checked against the
    configuration file; a difference raises."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(c["registry"]),
                              **c.get("program_overrides", {}))
    run = c["as_run"]
    want = {
        "family": "bio_bert", "objective": "mlm", "causal": False,
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_attention_heads"],
        "resolved_head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
        "vocab_size": c["vocab_size"], "use_rope": True,
        "rope_theta": c["rope_theta"], "norm_type": "layernorm",
        # the program's "gelu" is jax.nn.gelu, the tanh approximation
        "act": {"gelu_tanh": "gelu"}.get(c["hidden_act"]),
        "qkv_bias": True, "attn_out_bias": True, "mlp_bias": True,
        "tie_embeddings": True, "dtype": run["compute_dtype"],
        "param_dtype": run["param_dtype"],
    }
    diff = {k: (getattr(cfg, k), v) for k, v in want.items()
            if getattr(cfg, k) != v}
    if diff:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file (program, file): {diff}")
    return cfg


def to_program(ref: Dict, padded_vocab: int) -> Dict:
    """Reference-layout parameters in the program's tree; the embedding's
    padding rows (vocab to ``padded_vocab``) are zero."""
    tree: Dict = {"head": {}}
    for name, path in PROGRAM_PATHS.items():
        x = ref[name]
        if name == "embed":
            x = jnp.pad(x, ((0, padded_vocab - x.shape[0]), (0, 0)))
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return tree


def program_norms(tree: Dict) -> Dict[str, jax.Array]:
    """Per reference leaf, the norm of the program's tensor at its path."""
    out = {}
    for name, path in PROGRAM_PATHS.items():
        x = tree
        for k in path:
            x = x[k]
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out


class InOrder:
    """Sampler for ``MLMBatches``: the corpus's rows in order, wrapping."""

    def __init__(self, n: int):
        self.n, self.k = n, 0

    def sample(self, m: int) -> np.ndarray:
        idx = (self.k + np.arange(m)) % self.n
        self.k += m
        return idx


class Feed:
    """The iterator handed to ``Trainer``: ``MLMBatches`` with the host time
    of each batch, its real and padded slots, and every batch it handed
    on (the pipeline makes new arrays for each batch, which nothing
    writes to afterwards)."""

    def __init__(self, pipeline, pad_id: int):
        self.it = iter(pipeline)
        self.pad_id = pad_id
        self.host_s: List[float] = []
        self.real: List[int] = []
        self.slots: List[int] = []
        self.kept: List[Dict[str, np.ndarray]] = []

    def __iter__(self):
        return self

    def __next__(self):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.data"):
            b = next(self.it)
        self.host_s.append(time.perf_counter() - t)
        self.real.append(int((b["targets"] != self.pad_id).sum()))
        self.slots.append(int(b["targets"].size))
        self.kept.append(b)
        return b


class CompileCount:
    """Counts programs traced or compiled by JAX while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_, **__):
        if self.on and name in self.EVENTS:
            self.n += 1


def optimizer_config(c: Dict, mix: Dict, seed: int):
    from repro.core.config import TrainConfig

    o = c["optimizer"]
    return TrainConfig(
        global_batch=mix["rows"], seq_len=mix["seq_len"],
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        eps=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"],
        schedule="wsd", total_steps=2**31 - 1, decay_steps=1, seed=seed,
    )


def make_feed(ctx) -> Feed:
    """The cell's batches: its traffic's corpus from the seed in a
    temporary memmap, read by the program's ``MLMBatches``."""
    from repro.data.dataset import MemmapTokenDataset
    from repro.data.pipeline import MLMBatches
    from repro.data.tokenizer import ProteinTokenizer

    mix = ctx.mix
    tok = ProteinTokenizer()
    ids = {"pad_id": tok.pad_id, "bos_id": tok.cls_id, "eos_id": tok.eos_id,
           "mask_id": tok.mask_id}
    if any(mix[k] != v for k, v in ids.items()):
        raise ValueError(f"mix token ids differ from the tokenizer's {ids}")
    corpus = ctx.generator.make_corpus(mix, ctx.seed)
    ds = MemmapTokenDataset.write(os.path.join(ctx.tmp, "corpus"), corpus)
    pipeline = MLMBatches(ds, tok, InOrder(len(ds)), mix["rows"],
                          mix["seq_len"], mix["mask_prob"], seed=ctx.seed)
    return Feed(pipeline, tok.pad_id)


def build(ctx, cfg, feed: Feed):
    """The cell's model and one trainer over ``feed``, its state made from
    the seed on the device."""
    from repro.core.config import ParallelConfig
    from repro.launch.train import build_mesh
    from repro.models.model import build_model
    from repro.optim import adamw
    from repro.training import train_step as TS
    from repro.training.loop import Trainer

    c = ctx.config
    mesh = build_mesh("auto") if ctx.cell["chips"] > 1 else None
    model = build_model(cfg, ParallelConfig(), mesh)

    def make_state(key):
        params = to_program(esm2.init_params(c, key), cfg.padded_vocab)
        return TS.TrainState(params, adamw.init_state(params, jnp.float32))

    abstract = model.abstract_params()
    made = jax.eval_shape(make_state, esm2.seed_key(0)).params
    if (jax.tree.structure(made) != jax.tree.structure(abstract)
            or jax.tree.leaves(jax.tree.map(lambda a, b: a.shape != b.shape,
                                            made, abstract)).count(True)):
        raise ValueError("reference weights do not fit the program's tree")
    shardings = TS.state_shardings(model) if mesh is not None else None
    state = jax.jit(make_state, out_shardings=shardings)(esm2.seed_key(ctx.seed))
    trainer = Trainer(model, optimizer_config(c, ctx.mix, ctx.seed), verbose=False)
    trainer.prepare(feed, state=state)
    return trainer


def step_loss(trainer) -> float:
    """Step ``trainer`` once and return the loss that the step returned:
    flushed into the trainer's history where this step logged, else
    still among the metrics the trainer holds for its next flush."""
    trainer.step()
    last = trainer.history[-1] if trainer.history else {}
    if last.get("step") == trainer.step_idx - 1:
        return last["loss"]
    return float(trainer._pending[-1]["loss"])


def steps_skipped(trainer) -> int:
    """Steps whose update the program withheld so far, those not yet
    flushed to the trainer's count included."""
    pending = jax.device_get(trainer._pending)
    return trainer.skipped_total + sum(float(m["skipped"]) > 0 for m in pending)


def program_readings(ctx, trainer, cfg) -> Dict:
    """Step the trainer through the compared steps and read it: each
    step's loss, the per-leaf norms of the first gradient (Adam's first
    moment after step 1, over 1 - beta1) and of the parameters' change."""
    b1 = ctx.config["optimizer"]["beta1"]
    norms = jax.jit(program_norms)
    losses = [step_loss(trainer)]
    grads = {k: float(v) / (1 - b1)
             for k, v in norms(trainer.state.opt.mu).items()}
    while trainer.step_idx < CHECK_STEPS:
        losses.append(step_loss(trainer))

    def change(params, key):
        return program_norms(jax.tree.map(
            jnp.subtract, params,
            to_program(esm2.init_params(ctx.config, key), cfg.padded_vocab)))

    moved = jax.jit(change)(trainer.state.params, esm2.seed_key(ctx.seed))
    return {"losses": losses, "grad_norms": grads,
            "change_norms": {k: float(v) for k, v in moved.items()}}


def free_device() -> int:
    """Collect what the dropped trainer held on the device and the
    compiled programs; returns the bytes of arrays still alive."""
    gc.collect()
    jax.clear_caches()
    return sum(a.nbytes for a in jax.live_arrays())


# --------------------------------------------------------------------- #
# the reference's side and the comparison
# --------------------------------------------------------------------- #
def reference_shardings(names_shapes: Dict):
    """On several devices: the reference's matrices split over them by
    output columns (Q, K, V, FFN in) or input rows (O, FFN out), the rest
    replicated. ``None`` on one device."""
    n = jax.device_count()
    if n == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    split_last = {"wq", "wk", "wv", "w1", "bq", "bk", "bv", "b1"}
    split_rows = {"wo", "w2"}
    out = {}
    for name, shape in names_shapes.items():
        spec = [None] * len(shape)
        if name in split_last and shape[-1] % n == 0:
            spec[-1] = "x"
        elif name in split_rows and shape[-2] % n == 0:
            spec[-2] = "x"
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def reference_corpus(ctx):
    """The traffic's corpus, made anew from the seed for the reference."""
    return ctx.generator.make_corpus(ctx.mix, ctx.seed)


def reference_steps(ctx, batches, corpus=None) -> List[Dict[str, np.ndarray]]:
    """The first ``CHECK_STEPS`` of ``batches`` (the program's, in the
    order it drew them) as the reference takes them: targets from the
    corpus."""
    corpus = reference_corpus(ctx) if corpus is None else corpus
    return mlm_batches.reference_batches(ctx.mix, corpus, batches[:CHECK_STEPS])


def reference_readings(ctx, steps, *, quant: str = "") -> Dict:
    """The reference's steps on ``steps`` from the seed's weights."""
    rows = steps[0]["tokens"].shape[0]
    return esm2.train_readings(
        ctx.config, ctx.config["optimizer"], ctx.seed,
        [{k: jnp.asarray(v) for k, v in b.items()} for b in steps],
        quant=quant, row_block=max(rows // 4, 1),
        shard=reference_shardings)


def norm_gap(got: Dict, want: Dict, leaves) -> float:
    """The worst leaf's gap between two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(want[k] for k in leaves)
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in leaves)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers (see the module docstring)."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    g = ref["grad_norms"]
    med_g = statistics.median(g.values())
    moved = [k for k in g if g[k] >= 1e-3 * med_g]
    return {"loss_gap": loss,
            "grad_norm_gap": norm_gap(prog["grad_norms"], g, list(g)),
            "change_norm_gap": norm_gap(prog["change_norms"],
                                        ref["change_norms"], moved)}


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #
def run(ctx) -> Dict:
    c, mix = ctx.config, ctx.mix
    cfg = program_config(c)
    compiles = CompileCount()
    feed = make_feed(ctx)
    trainer = build(ctx, cfg, feed)
    prog = program_readings(ctx, trainer, cfg)
    while trainer.step_idx < CHECK_STEPS + WARM_STEPS:
        trainer.step()
    jax.block_until_ready(trainer.state)
    # what set-up left on the heap is not traversed again: a full
    # collection over it inside the window stalled a step by 0.1-0.9 s
    skipped = steps_skipped(trainer)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx.t_start
    with ctx.window():
        compiles.on = True
        t0 = time.perf_counter()
        i0, p0 = trainer.step_idx, len(feed.host_s)
        while time.perf_counter() - t0 < ctx.seconds:
            trainer.step()
        jax.block_until_ready(trainer.state)
        wall = time.perf_counter() - t0
        compiles.on = False
    gc.unfreeze()
    steps = trainer.step_idx - i0
    failed = steps_skipped(trainer) - skipped
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    print(f"bench: memory_stats {stats}", file=sys.stderr)
    memory = max(s.get("peak_bytes_in_use", 0) for s in stats)
    del trainer
    left = free_device()
    if left:
        print(f"bench: {left} bytes of arrays alive before the reference",
              file=sys.stderr)
    t_ref = time.perf_counter()
    corpus = reference_corpus(ctx)
    ref = reference_readings(ctx, reference_steps(ctx, feed.kept, corpus))
    numbers = dict(compare(prog, ref),
                   **mlm_batches.data_checks(mix, corpus, feed.kept))
    print(f"bench: reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    checks = {k: {"value": v, "limit": ctx.limits[k]}
              for k, v in numbers.items()}
    checks["window_compiles"] = {"value": compiles.n, "limit": 0}
    tokens = sum(feed.real[i0:i0 + steps])
    slots = sum(feed.slots[i0:i0 + steps])
    return {
        "correct": all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()),
        "attempted": steps, "failed": failed,
        "memory_peak_bytes": memory,
        "end_to_end": {"train_tokens_per_s": tokens / wall, "setup_s": setup_s},
        "facts": {
            "steps": steps, "window_s": wall, "real_tokens": tokens,
            "slots": slots, "rows": mix["rows"], "seq_len": mix["seq_len"],
            "data_host_s": sum(feed.host_s[p0:p0 + steps]),
            "flops_per_token": flops.train_flops_per_token(c, mix["seq_len"]),
        },
        "checks": checks,
    }

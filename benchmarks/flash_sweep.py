"""Device time of the flash-attention kernels per (block_q, block_k), for
one or more versions of ``kernels/flash_attention.py``, on a TPU.

Each version is a module file, loaded by path, so the tree's kernels can
be timed beside an older commit's in the same process:

    git show <rev>:src/repro/kernels/flash_attention.py > old_fa.py
    python3 benchmarks/flash_sweep.py \\
        --kernels old=old_fa.py,tree=src/repro/kernels/flash_attention.py

For every version and tile the forward and the backward (dQ and dK/dV)
of a call at ``--shape`` (B,S,H,D; bf16, the ESM-2 650M cell's by
default; bidirectional unless ``--causal``) are compiled, run ``--iters`` times under the
profiler, and each kernel's device time is read from the trace by its
``pallas_call`` name.  Times are ms per call, one layer's worth.  Each
row also records a digest of each kernel's code, so two rows that time
the same kernel can be told apart from two that do not.  Then
every version's forward and cotangents are compared with the float32
reference on two batch rows, and with the first version's bit for bit.
The rows and the comparison are printed as JSON lines and written to
``--out``.  Last, at each of ``--lengths``, the tree's own
``ops.attention`` (its tile rule) is compared with the reference.
``--interpret`` rehearses the script on the CPU, timing
nothing.
"""
from __future__ import annotations

import argparse
import hashlib
import importlib.util
import itertools
import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.kernels import ops, ref  # noqa: E402

KERNELS = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def _load(label, path):
    spec = importlib.util.spec_from_file_location(f"flash_sweep_{label}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kernel_digests(fn, *args):
    """{kernel name: sha1 of its kernel jaxpr} for the ``pallas_call``s
    that ``fn`` makes: equal code gives equal digests, whatever file or
    line it was loaded from."""
    out = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                body = str(eqn.params["jaxpr"]).encode()
                out[str(eqn.params["name"])] = hashlib.sha1(body).hexdigest()[:12]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _kernel_ms(fn, args, iters, trace_mod):
    """Device ms per call of each kernel, and of all busy time."""
    path = tempfile.mkdtemp()
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(path)
    with jax.profiler.TraceAnnotation("sweep.window"):
        for _ in range(iters):
            result = fn(*args)
        jax.block_until_ready(result)
    jax.profiler.stop_trace()
    red = trace_mod.reduce_dir(path, "sweep.window")
    got = dict.fromkeys(KERNELS, 0.0)
    for op, sec in red["op_s"].items():
        for name in KERNELS:
            if re.search(rf"\b{name}\b", op):
                got[name] += sec
    return {n: 1e3 * s / iters for n, s in got.items()}, 1e3 * red["busy_s"] / iters


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--kernels", default="tree=src/repro/kernels/flash_attention.py",
                    help="label=path[,label=path...] of flash_attention modules")
    ap.add_argument("--shape", default="16,1024,20,64",
                    help="B,S,H,D[,KV heads] (KV heads default to H)")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--tiles", default="256,512,1024",
                    help="block sizes swept on both axes; (128, 128) is always run")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--out", default="tmp/flash_sweep.json")
    ap.add_argument("--lengths", default="1500,200",
                    help="bidirectional lengths at which the tree's "
                         "ops.attention, with its own tiles, is compared "
                         "with the reference (empty: none)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernels in interpret mode and time nothing "
                         "(a rehearsal on the CPU)")
    a = ap.parse_args(argv)

    trace_mod = _load("trace", os.path.join(ROOT, "bench", "trace.py"))
    versions = [kv.split("=", 1) for kv in a.kernels.split(",")]
    mods = {label: _load(label, path) for label, path in versions}
    B, S, H, D, *kv = map(int, a.shape.split(","))
    Hkv = kv[0] if kv else H
    tiles = [int(t) for t in a.tiles.split(",")]
    pairs = [(128, 128)] + [p for p in itertools.product(tiles, tiles) if p != (128, 128)]

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, do = (jax.random.normal(kk, (B, S, h, D), jnp.bfloat16)
                   for kk, h in zip(keys, (H, Hkv, Hkv, H)))
    out = {"device": jax.devices()[0].device_kind, "shape": [B, S, H, D, Hkv],
           "causal": a.causal,
           "kernels": dict(versions), "rows": [], "numerics": []}
    print(out["device"], flush=True)

    for label, mod in mods.items():
        for bq, bk in pairs:
            kw = dict(causal=a.causal, block_q=bq, block_k=bk, interpret=a.interpret)
            fwd = jax.jit(lambda q, k, v: mod.flash_attention_fwd(q, k, v, **kw))
            bwd = jax.jit(lambda q, k, v, o, l, do:
                          mod.flash_attention_bwd(q, k, v, o, l, do, **kw))
            o, lse = fwd(q, k, v)
            bargs = (q, k, v, o, lse, do)
            digests = {**_kernel_digests(fwd, q, k, v), **_kernel_digests(bwd, *bargs)}
            if a.interpret:
                f_ms, f_busy = dict.fromkeys(KERNELS), None
                b_ms, b_busy = dict.fromkeys(KERNELS), None
            else:
                f_ms, f_busy = _kernel_ms(fwd, (q, k, v), a.iters, trace_mod)
                b_ms, b_busy = _kernel_ms(bwd, bargs, a.iters, trace_mod)
            row = {"kernels": label, "bq": bq, "bk": bk,
                   "fwd_ms": f_ms["flash_attention_fwd"],
                   "dq_ms": b_ms["flash_attention_dq"],
                   "dkv_ms": b_ms["flash_attention_dkv"],
                   "fwd_busy_ms": f_busy, "bwd_busy_ms": b_busy, "digests": digests}
            out["rows"].append(row)
            print(json.dumps(row), flush=True)

    rows = slice(0, min(B, 2))
    qs, ks, vs, dos = (x[rows] for x in (q, k, v, do))
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (qs, ks, vs)]
        want_o, vjp = jax.vjp(lambda q, k, v: ref.attention_ref(q, k, v, causal=a.causal),
                              *f32)
        want = (want_o,) + vjp(dos.astype(jnp.float32))
    checked = [(128, 128)] + [(t, t) for t in [min(max(tiles), S)] if t != 128]
    first = {}
    for label, mod in mods.items():
        for bq, bk in checked:
            kw = dict(causal=a.causal, block_q=bq, block_k=bk, interpret=a.interpret)
            o, lse = mod.flash_attention_fwd(qs, ks, vs, **kw)
            got = (o,) + tuple(mod.flash_attention_bwd(qs, ks, vs, o, lse, dos, **kw))
            base = first.setdefault((bq, bk), got)
            names = ("out", "dq", "dk", "dv")
            entry = {"kernels": label, "bq": bq, "bk": bk,
                     "rel_to_ref": {n: _rel(g, w) for n, g, w in zip(names, got, want)},
                     "equal_to_first": {n: bool(jnp.array_equal(g, b))
                                        for n, g, b in zip(names, got, base)}}
            out["numerics"].append(entry)
            print(json.dumps(entry), flush=True)

    impl = "pallas_interpret" if a.interpret else "pallas"
    for n in [int(x) for x in a.lengths.split(",") if x]:
        keys = jax.random.split(jax.random.PRNGKey(n), 4)
        q, k, v, do = (jax.random.normal(kk, (1, n, 2, D), jnp.bfloat16) for kk in keys)
        got_o, vjp = jax.vjp(lambda q, k, v: ops.attention(q, k, v, causal=False, impl=impl),
                             q, k, v)
        got = (got_o,) + vjp(do)
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            want_o, vjp = jax.vjp(lambda q, k, v: ref.attention_ref(q, k, v, causal=False), *f32)
            want = (want_o,) + vjp(do.astype(jnp.float32))
        entry = {"length": n, "tiles": ops.attention_blocks(n, n, causal=False, window=0),
                 "rel_to_ref": {m: _rel(g, w) for m, g, w in
                                zip(("out", "dq", "dk", "dv"), got, want)}}
        out.setdefault("lengths", []).append(entry)
        print(json.dumps(entry), flush=True)

    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

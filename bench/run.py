#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
root of the checkout:

- the cell's ``config`` is ``bench/configs/<config>.json``;
- its ``traffic`` is ``bench/traffic/<traffic>.json``, which names the
  generator that reads it (``bench/traffic/<generator>.py``) and the entry
  that drives the program (``bench/entries/<entry>.py``);
- the limits of the comparison that decides ``correct`` are in
  ``bench/limits/<cell>.json``;
- each per-layer metric is read by ``bench/metrics/<metric>.py``.

A new cell, mix, metric or entry is a new file and a new entry in
``BENCHMARK.json``; no file here changes.

The run needs ``chips`` TPU chips of the cell and fails with no result
line without them. It keeps JAX's compilation cache in ``.jax_cache/``
of the checkout (``$JAX_COMPILATION_CACHE_DIR`` where that is set). With
``--trace 1`` the measured window is traced and the per-layer metrics are
printed in place of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WINDOW_SPAN = "bench.window"
if sys.path and sys.path[0] == BENCH:  # run as a script: bench/ goes last
    sys.path.append(sys.path.pop(0))


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, cell_name: str, bench: str = BENCH) -> SimpleNamespace:
    """The cell ``cell_name`` of ``spec`` with every file it names loaded:
    configuration, traffic mix, generator, entry, limits and metric
    readers. Raises when one is missing."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r}; known: {sorted(cells)}")
    cell = cells[cell_name]
    config = load_json(bench, "configs", cell["config"] + ".json")
    mix = load_json(bench, "traffic", cell["traffic"] + ".json")
    e2e = [m for m in spec["end_to_end"] if applies(m, cell_name)]
    per_layer = [m for m in spec["per_layer"] if applies(m, cell_name)]
    reported = {m["name"] for m in e2e}
    readers = {
        m["name"]: load_module(
            os.path.join(bench, "metrics", m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        for m in per_layer if m["moves"] in reported
    }
    return SimpleNamespace(
        cell=cell, config=config, mix=mix, e2e=e2e,
        per_layer=[m for m in per_layer if m["name"] in readers],
        readers=readers,
        limits=load_json(bench, "limits", cell_name + ".json"),
        generator=load_module(
            os.path.join(bench, "traffic", mix["generator"] + ".py"),
            "bench_traffic_" + mix["generator"]),
        entry=load_module(os.path.join(bench, "entries", mix["entry"] + ".py"),
                          "bench_entry_" + mix["entry"]),
    )


def add_paths(root: str) -> None:
    """The program's ``src`` first on the path; ``bench`` (whose modules
    the entries import) last, so that ``bench/trace.py`` hides no module
    of the standard library."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if os.path.join(root, "bench") not in sys.path:
        sys.path.append(os.path.join(root, "bench"))


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(jax, chips: int) -> dict:
    """The device facts, or exit non-zero unless ``chips`` TPU chips are
    visible."""
    info = device_info(jax)
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chip(s); JAX found "
                 f"{info['count']} {info['platform']} device(s)")
    return info


@contextlib.contextmanager
def window(jax, trace_dir: str):
    """The measured window: traced into ``trace_dir`` when it is set, and
    marked by a host span ``WINDOW_SPAN`` that the trace reduction finds."""
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        if trace_dir:
            jax.profiler.stop_trace()


def run(args, *, root: str = ROOT, chips_check=require_chips) -> dict:
    """One run of a cell of ``root``'s ``BENCHMARK.json``; returns the
    result line as a dict. ``chips_check(jax, chips)`` returns the device
    facts or exits."""
    bench = os.path.join(root, "bench")
    spec = load_json(root, "BENCHMARK.json")
    r = resolve(spec, args.workload, bench)
    add_paths(root)
    import jax

    dev = chips_check(jax, r.cell["chips"])
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        trace_dir = os.path.join(tmp, "trace") if args.trace else ""
        ctx = SimpleNamespace(
            cell=r.cell, config=r.config, mix=r.mix, limits=r.limits,
            generator=r.generator, seed=args.seed, seconds=args.seconds,
            t_start=T_START, tmp=tmp,
            window=lambda: window(jax, trace_dir),
        )
        out = r.entry.run(ctx)
        reduced = None
        if args.trace:
            reduced = load_module(os.path.join(bench, "trace.py"),
                                  "bench_trace").reduce_dir(trace_dir, WINDOW_SPAN)
    device = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        peaks = load_module(os.path.join(bench, "peaks.py"), "bench_peaks").peaks
        facts = dict(out["facts"], trace=reduced, config=r.config, mix=r.mix,
                     chips=r.cell["chips"], peak=peaks(dev["kind"]))
        metrics = {}
        for m in r.per_layer:
            value = r.readers[m["name"]].read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown=reduced["breakdown"])
    else:
        metrics = {}
        for m in r.e2e:
            if m["name"] not in out["end_to_end"]:
                raise KeyError(f"entry gave no {m['name']}")
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = out["checks"]
    return result


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> None:
    result = run(parse(argv))
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    if not all(math.isfinite(m["value"]) for m in result["metrics"].values()):
        sys.exit(f"bench: a metric is not finite: {result['metrics']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

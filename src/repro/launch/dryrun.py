"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against placeholder devices, and extract the roofline terms.

MUST be run as its own process (run as a script, the lines below set
XLA_FLAGS before jax is imported, so jax sees 512 host devices; importing
the module leaves the environment alone):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
        --shape train_4k --mesh single --out experiments/dryrun

Outputs one JSON per combination with:
  * memory_analysis (bytes/device: args, outputs, temps)
  * cost_analysis   (per-device HLO FLOPs + bytes accessed)
  * per-collective byte totals parsed from the compiled HLO
  * derived roofline terms vs TPU v5e constants (see benchmarks/roofline.py)
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict

import jax

from repro.configs import get_config, list_archs
from repro.core.config import ModelConfig, ParallelConfig, TrainConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, applicable, dryrun_bundle

# ----------------------------------------------------------------- v5e constants
PEAK_FLOPS = 197e12          # bf16 TFLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (per-direction, approx)

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}
# effective traffic multiplier per algorithm (ring), in units of buffer bytes
_COLL_FACTOR = {
    "all-gather": 1.0,        # each device receives (g-1)/g of the full buffer
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result-buffer bytes per collective kind from per-device HLO."""
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        result_type, kind = m.group(1), m.group(2)
        if kind.endswith("-done"):
            continue
        nbytes = 0
        for dm in _SHAPE_RE.finditer(result_type):
            dt, dims = dm.group(1), dm.group(2)
            size = 1
            if dims:
                for d in dims.split(","):
                    size *= int(d)
            nbytes += size * (1 if dt.startswith("f8") else _DTYPE_BYTES.get(dt, 2))
        rec = out.setdefault(kind, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["traffic"] += nbytes * _COLL_FACTOR[kind]
    return out


def roofline_terms(
    cfg: ModelConfig, flops: float, hbm_bytes: float, coll: Dict[str, Dict[str, float]],
    n_chips: int, shape_name: str,
) -> Dict[str, Any]:
    coll_traffic = sum(v["traffic"] for v in coll.values())
    t_compute = flops / PEAK_FLOPS            # per-device flops already
    t_memory = hbm_bytes / HBM_BW
    t_coll = coll_traffic / ICI_BW
    terms = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": max(
            [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
            key=lambda kv: kv[1],
        )[0],
    }
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        D = shape.seq_len * shape.global_batch
        model_flops = 6 * cfg.active_param_count() * D / n_chips
    elif shape.kind == "prefill":
        D = shape.seq_len * shape.global_batch
        model_flops = 2 * cfg.active_param_count() * D / n_chips
    else:
        model_flops = 2 * cfg.active_param_count() * shape.global_batch / n_chips
    terms["model_flops_per_chip"] = model_flops
    terms["useful_flop_ratio"] = model_flops / flops if flops else 0.0
    return terms


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    pc: ParallelConfig,
    out_dir: str,
    variant: str = "",
    tag: str = "",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    if variant == "sliding_window" and not cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=8192)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant, "tag": tag,
        "parallel": dataclasses.asdict(pc),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _dump(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        fn, args, in_sh, meta = dryrun_bundle(cfg, shape, mesh, pc)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        # scan-aware extraction (XLA cost_analysis counts while bodies once)
        from repro.launch.hlo_cost import analyze as hlo_analyze

        h = hlo_analyze(hlo, breakdown=True)
        coll = h["collectives"]
        flops = float(h["flops"])
        hbm_bytes = float(h["hbm_bytes"])
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_chips=n_chips,
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "total_per_device": ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            cost={
                "flops_per_device": flops,
                "hbm_bytes_per_device": hbm_bytes,
                "hbm_bytes_f32_large": float(h.get("hbm_bytes_f32_large", 0.0)),
                "xla_flops_scan_body_once": float(ca.get("flops", 0.0)),
                "xla_bytes_scan_body_once": float(ca.get("bytes accessed", 0.0)),
            },
            collectives=coll,
            traffic_top=h.get("traffic_top", {}),
            roofline=roofline_terms(cfg, flops, hbm_bytes, coll, n_chips, shape_name),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["elapsed_s"] = round(time.time() - t0, 1)
    _dump(rec, out_dir)
    return rec


def _dump(rec: Dict[str, Any], out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"_{rec['tag']}" if rec.get("tag") else ""
    var = f"_{rec['variant']}" if rec.get("variant") else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{var}{tag}.json"
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    r = rec.get("roofline", {})
    print(
        f"[{rec['status']:7s}] {rec['arch']:28s} {rec['shape']:12s} "
        f"{rec['mesh']:6s} "
        + (
            f"compute={r['t_compute_s']:.3e}s memory={r['t_memory_s']:.3e}s "
            f"coll={r['t_collective_s']:.3e}s dom={r['dominant']}"
            if r
            else rec.get("reason", rec.get("error", ""))[:100]
        ),
        flush=True,
    )


def parallel_from_args(a) -> ParallelConfig:
    kw: Dict[str, Any] = {}
    if a.attn != "auto":
        kw["attention_parallelism"] = a.attn
    if a.fsdp == "pod_data":
        kw["fsdp_axes"] = ("pod", "data")
    elif a.fsdp == "data":
        kw["fsdp_axes"] = ("data",)
    elif a.fsdp == "none":
        kw["fsdp_axes"] = ()
    if a.remat:
        kw["remat_policy"] = a.remat
    if a.opt_dtype:
        kw["optimizer_state_dtype"] = a.opt_dtype
    return ParallelConfig(**kw)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all", choices=["all", *SHAPES])
    p.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--variant", default="", choices=["", "sliding_window"])
    p.add_argument("--tag", default="")
    p.add_argument("--attn", default="auto", choices=["auto", "head_tp", "context"])
    p.add_argument("--fsdp", default="data", choices=["data", "pod_data", "none"])
    p.add_argument("--remat", default="", choices=["", "none", "block", "dots", "full"])
    p.add_argument("--opt-dtype", dest="opt_dtype", default="",
                   choices=["", "float32", "bfloat16"])
    a = p.parse_args()

    assigned = [
        "command-r-35b", "mamba2-2.7b", "qwen1.5-32b", "llama4-scout-17b-a16e",
        "whisper-medium", "internvl2-26b", "qwen2-7b", "llama3-405b",
        "llama4-maverick-400b-a17b", "jamba-1.5-large-398b",
    ]
    archs = assigned if a.arch == "all" else [a.arch]
    # "all" = the four assigned shapes; bio recipe shapes run explicitly
    assigned_shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    shapes = assigned_shapes if a.shape == "all" else [a.shape]
    meshes = ["single", "multi"] if a.mesh == "both" else [a.mesh]
    pc = parallel_from_args(a)

    failures = 0
    for arch in archs:
        for sh in shapes:
            for m in meshes:
                rec = run_one(arch, sh, m == "multi", pc, a.out, a.variant, a.tag)
                failures += rec["status"] == "error"
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

"""Fixtures of the benchmark's tests: the ``bench`` directory on the path,
and a copy of the checkout with a tiny cell added as data files only."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))
if BENCH not in sys.path:
    sys.path.append(BENCH)  # last: bench/trace.py hides no standard module

TINY = "tiny.mlm"
TINY4 = "tiny.mlm.4chip"
# readings of the tiny cell on the CPU (sound program, seeds 1-4): loss
# 7.4e-5..1.1e-4, grad norms 0.005..0.012, change norms 0.015..0.047;
# half batch: 0.013, 0.11, 0.094 and up; fp8 control loss 4.7e-4 and up
TINY_LIMITS = {"loss_gap": 3e-4, "grad_norm_gap": 0.1, "change_norm_gap": 0.3,
               "target_slots_wrong": 0, "loss_on_non_residue": 0,
               "unselected_changed": 0, "mask_rate_z": 5, "mask_token_z": 5}


@pytest.fixture
def tiny_limits():
    return dict(TINY_LIMITS)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout in ``tmp_path``: this ``bench`` directory, the program's
    ``src`` (linked), and ``BENCHMARK.json`` with the cells ``tiny.mlm``
    and ``tiny.mlm.4chip``, which exist only as added files:
    ``configs/tiny.json``, ``traffic/tiny-mlm.json``,
    ``traffic/tiny-mlm8.json`` and their ``limits/<cell>.json``."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    shutil.copy(os.path.join(DATA, "tiny.json"), bench / "configs" / "tiny.json")
    for mix in ("tiny-mlm", "tiny-mlm8"):
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    bench / "traffic" / (mix + ".json"))
    for cell in (TINY, TINY4):
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(TINY_LIMITS))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] += [
        {"name": TINY, "config": "tiny", "traffic": "tiny-mlm", "chips": 1,
         "why": "test fixture"},
        {"name": TINY4, "config": "tiny", "traffic": "tiny-mlm8", "chips": 4,
         "why": "test fixture"}]
    for m in spec["per_layer"]:
        m.get("workloads", []).extend([TINY, TINY4])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    # the run's compile cache stays off: JAX was imported without one
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path

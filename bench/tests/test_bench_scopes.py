"""The scope readers (``bench/metrics/{attention,ffn,optimizer,recompute,
data_wait}_ms.train.py`` over ``bench/scopes.py``) on one ESM-2 650M
training step of the cell ``esm2-650m.mlm`` (16 x 1024, Pallas path)
traced on a TPU v5e: ``data/esm2-650m.16x1024.step.xplane.pb.gz``, cut to
the device's ``XLA Ops`` line and the host's spans (the host span
``bench/step`` covers the step), beside the program's scope map and
step-log record for it (``data/esm2-650m.16x1024.step.scopes.json``)."""
import gzip
import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
READERS = ("attention_ms.train", "ffn_ms.train", "optimizer_ms.train",
           "recompute_ms.train", "data_wait_ms.train")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def step():
    from jax.profiler import ProfileData

    T = load(os.path.join(BENCH, "trace.py"), "bench_trace")
    with gzip.open(os.path.join(DATA, "esm2-650m.16x1024.step.xplane.pb.gz")) as f:
        reduced = T.reduce(ProfileData.from_serialized_xspace(f.read()),
                           "bench/step")
    with open(os.path.join(DATA, "esm2-650m.16x1024.step.scopes.json")) as f:
        recorded = json.load(f)
    return reduced, recorded


@pytest.fixture
def log(step, monkeypatch):
    """The program's step log as it stood after the recorded step: its
    record, and the program's scope map."""
    import repro.obs
    from repro.obs import StepLog

    _, recorded = step
    log = StepLog()
    log.add_program(recorded["program"], lambda: recorded["scopes"])
    for r in recorded["steps"]:
        with log.step("train", r["index"]) as rec:
            rec.program = recorded["program"]
            rec.spans.update(r["spans"])
            rec.counters.update(r["counters"])
    monkeypatch.setattr(repro.obs, "STEP_LOG", log)
    return log


def facts(step):
    reduced, recorded = step
    return {"trace": reduced, "steps": len(recorded["steps"]), "chips": 1,
            "rows": 16, "seq_len": 1024,
            "config": {"num_attention_heads": 20, "head_dim": 64}}


def test_scopes_cover_busy_time(step, log):
    reduced, _ = step
    by = log.device_seconds(reduced["op_s"], log.last("train", 1))
    scoped = sum(s for (scope, _), s in by.items() if scope is not None)
    assert scoped >= 0.95 * reduced["busy_s"]
    assert sum(by.values()) == pytest.approx(sum(reduced["op_s"].values()))


def test_signature_kernels_fall_in_attention(step, log):
    from repro.obs.profile import instruction_name

    reduced, _ = step
    roof = load(os.path.join(BENCH, "metrics",
                             "flash_attention_roofline.train.py"), "roofline")
    scopes = log.scopes(log.last("train", 1)[0].program)
    found = []
    for name, sec in reduced["op_s"].items():
        k = roof.kernels({name: sec}, {name: 1.0}, 16 * 20, 1024, 64)
        if any(v[1] for v in k.values()):
            found.append(name)
            assert scopes[instruction_name(name)][0] == "attention", name
    # per layer: forward, its recompute, dQ and dK/dV
    assert len(found) == 4


# the readings recorded in PERF.md (section 5) for this step
RECORDED = {
    "attention_ms.train": 1864.861343,
    "ffn_ms.train": 273.622567,
    "optimizer_ms.train": 27.098801,
    "recompute_ms.train": 521.396959,
    "data_wait_ms.train": 1.8278200000168,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_recorded_step(step, log, name):
    reader = load(os.path.join(BENCH, "metrics", name + ".py"),
                  "reader_" + name.replace(".", "_"))
    assert reader.read(facts(step)) == pytest.approx(RECORDED[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_step_log(step, monkeypatch, name):
    """A program whose ``repro.obs`` has no step log (the parent of the
    change that brought it) gives nothing to read, and no error."""
    import repro.obs

    monkeypatch.delattr(repro.obs, "STEP_LOG")
    reader = load(os.path.join(BENCH, "metrics", name + ".py"),
                  "reader_" + name.replace(".", "_"))
    assert reader.read(facts(step)) is None

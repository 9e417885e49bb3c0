"""The trace reduction (bench/trace.py): exact interval arithmetic on a
synthetic two-chip trace, and the reduction of a trace recorded on a TPU
v5e (``data/esm2-650m.steps.xplane.pb``)."""
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def T():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trace", os.path.join(BENCH, "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plane(pid, name, line, events):
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "\n".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}" for n, s, e in events)
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 1000 {evs} }} {meta} }}")


# times in ns after the line's timestamp; the window is [0, 1000]
CHIP0 = [("pre.1", -100, 50), ("fusion.1", 0, 300), ("all-gather.1", 250, 400),
         ("convolution.2", 500, 700), ("_fa_kernel", 700, 900),
         ("fusion.9", 1100, 1200)]
CHIP1 = [("fusion.1", 0, 500), ("reduce-scatter.2", 500, 600),
         ("_fa_kernel", 600, 1000)]
HOST = [("bench.window", 0, 1000), ("bench.data", 400, 500)]


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto("\n".join([
        plane(1, "/device:TPU:0", "XLA Ops", CHIP0),
        plane(2, "/device:TPU:1", "XLA Ops", CHIP1),
        plane(3, "/host:CPU", "python", HOST),
    ]))


def test_synthetic_two_chips(T, synthetic):
    r = T.reduce(synthetic, "bench.window")
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy [0, 400] + [500, 900]; chip 1 busy throughout
    assert r["busy_s"] == pytest.approx(900e-9)
    # collectives: chip 0 [250, 400], 100 ns of it alone; chip 1 [500, 600]
    assert r["collective_s"] == pytest.approx(125e-9)
    assert r["exposed_collective_s"] == pytest.approx(100e-9)
    assert r["op_s"]["_fa_kernel"] == pytest.approx(300e-9)
    assert r["op_n"]["_fa_kernel"] == pytest.approx(1.0)
    assert r["op_s"]["pre.1"] == pytest.approx(25e-9)  # clipped to [0, 50]
    assert "fusion.9" not in r["op_s"]
    gaps = r["breakdown"]["idle_gaps"]
    assert sorted(g[0] for g in gaps) == ["bench.data", "bench.window"]
    assert [g[1] for g in gaps] == pytest.approx([100e-9, 100e-9])
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]


def test_interval_helpers(T):
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.length([(0, 3), (5, 8)]) == 6


def test_no_window_span_raises(T, synthetic):
    with pytest.raises(ValueError, match="host spans"):
        T.reduce(synthetic, "no.such.span")


@pytest.fixture(scope="module")
def recorded():
    """One ESM-2 650M training step (8 x 1024, Pallas path) traced on a TPU
    v5e and cut to the device's ``XLA Ops`` line and the host's Python
    thread; the host span ``bench/step`` covers the step."""
    import gzip

    from jax.profiler import ProfileData

    path = os.path.join(BENCH, "tests", "data", "esm2-650m.step.xplane.pb.gz")
    with gzip.open(path) as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_step(T, recorded):
    r = T.reduce(recorded, "bench/step")
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1.0698, abs=1e-3)
    assert 0.9 * r["window_s"] < r["busy_s"] <= r["window_s"]
    assert r["collective_s"] == 0.0
    # the while loops over the layers hold the leaves and are not counted
    assert not any(k.startswith("%while") for k in r["op_s"])
    assert sum(r["op_s"].values()) <= r["busy_s"]
    top = r["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][0].endswith("custom-call bf16[160,1024,64]")


def test_flash_attention_roofline_on_recorded_step(T, recorded):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "roofline", os.path.join(BENCH, "metrics", "flash_attention_roofline.train.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    r = T.reduce(recorded, "bench/step")
    k = m.kernels(r["op_s"], r["op_n"], 8 * 20, 1024, 64)
    # 33 layers: forward twice (once more under remat), each backward once
    assert {n: calls for n, (_, calls) in k.items()} == {"fwd": 66, "dq": 33, "dkv": 33}
    facts = {"trace": r, "config": {"num_attention_heads": 20, "head_dim": 64},
             "rows": 8, "chips": 1, "seq_len": 1024,
             "peak": {"flops": 197e12, "bytes": 819e9}}
    share = m.read(facts)
    least = (66 * 4 + 33 * 8) * 160 * 1024 ** 2 * 64 / 197e12
    spent = sum(s for s, _ in k.values())
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share < 100
    facts["trace"] = dict(r, op_s={}, op_n={})
    assert m.read(facts) is None

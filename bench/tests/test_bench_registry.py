"""Every entry of BENCHMARK.json resolves, by name, to its files; and a
cell added as data files alone resolves as well."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

import run as R  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
TRAINING = ("loss_gap", "grad_norm_gap", "change_norm_gap")
COUNTS = ("target_slots_wrong", "loss_on_non_residue", "unselected_changed")
DATA = COUNTS + ("mask_rate_z", "mask_token_z")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cell_resolves(cell):
    r = R.resolve(spec(), cell)
    assert r.config["name"] == r.cell["config"]
    assert callable(r.entry.run) and callable(r.generator.make_corpus)
    assert set(r.limits) == set(TRAINING) | set(DATA)
    assert r.per_layer and all(callable(r.readers[m["name"]].read)
                               for m in r.per_layer)
    assert {m["name"] for m in r.e2e} == {"train_tokens_per_s", "setup_s"}


def test_configs_match_their_files():
    s = spec()
    for c in s["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]


def test_names_and_metric_files():
    s = spec()
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    names += [w["name"] for w in s["workloads"]] + [c["name"] for c in s["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.fullmatch(n) for n in names)
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


def test_cell_added_as_data_only(tiny_root):
    """tiny.mlm exists only as a config, a mix and a limits file added to a
    copy of the checkout, and an entry in its BENCHMARK.json."""
    with open(tiny_root / "BENCHMARK.json") as f:
        s = json.load(f)
    r = R.resolve(s, "tiny.mlm", str(tiny_root / "bench"))
    assert r.config["num_hidden_layers"] == 2 and r.mix["rows"] == 4
    assert r.entry.__file__ == str(tiny_root / "bench" / "entries" / "train.py")
    with pytest.raises(KeyError):
        R.resolve(s, "no-such-cell", str(tiny_root / "bench"))


def test_limits_are_set():
    """Every cell's limits are set (from readings, PERF.md): the training
    gaps are shares, the data counts exact, the z-scores a few binomial
    standard deviations."""
    for w in spec()["workloads"]:
        limits = R.resolve(spec(), w["name"]).limits
        assert all(0 < limits[k] < 1 for k in TRAINING), limits
        assert all(limits[k] == 0 for k in COUNTS), limits
        assert all(3 <= limits[k] <= 10 for k in ("mask_rate_z", "mask_token_z"))

"""The protein-length generator: deterministic from the seed, lognormal
lengths as the mix states them, and the same real tokens in every step."""
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gen():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "uniref_lengths", os.path.join(BENCH, "traffic", "uniref_lengths.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", params=["uniref50-mlm-16x1024"])
def mix(gen, request):
    return gen.load_mix(os.path.join(BENCH, "traffic", request.param + ".json"))


def test_lengths_as_stated(gen, mix):
    # mean 311 (UniRef50), mean / median = 320 / 267 (the shape's source):
    # median 311 * 267 / 320 = 259.49, sigma sqrt(2 ln(320 / 267)) = 0.6018
    median, sigma = gen.lognormal(mix)
    assert median == pytest.approx(259.49, abs=0.01)
    assert sigma == pytest.approx(0.6018, abs=1e-4)
    lengths = gen.quantile_lengths(mix)
    assert np.median(lengths) == pytest.approx(259.5, abs=1)
    assert lengths.mean() == pytest.approx(311, rel=0.01)
    # P(L > 1022) = 1 - Phi(ln(1022 / 259.49) / 0.6018) = 1.1%; a period of
    # 128 quantiles holds one such protein
    assert (lengths > 1022).sum() == 1
    # real tokens over 1024 slots: the generator's own figure, 30.3%
    assert gen.real_share(mix) == pytest.approx(0.3028, abs=0.001)


def test_same_seed_same_corpus(gen, mix):
    seed = 2**31 + 12345
    a, b = gen.make_corpus(mix, seed), gen.make_corpus(mix, seed)
    assert len(a) == len(b) == mix["period_proteins"] * mix["periods"]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = gen.make_corpus(mix, seed + 1)
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_rows_are_cropped_proteins(gen, mix):
    corpus = gen.make_corpus(mix, 7)
    lo, hi = mix["residue_ids"]
    for row in corpus[:512]:
        assert 3 <= len(row) <= mix["seq_len"]
        assert row[0] == mix["bos_id"] and row[-1] == mix["eos_id"]
        assert row[1:-1].min() >= lo and row[1:-1].max() <= hi
    assert max(len(r) for r in corpus) == mix["max_residues"] + 2


def test_every_step_holds_the_same_work(gen, mix):
    """Each seed feeds the same set of sizes in another order, and every
    step's real tokens are within 1% of the mean."""
    rows = mix["rows"]
    sizes = []
    for seed in (1, 2**31 + 3):
        corpus = gen.make_corpus(mix, seed)
        per_step = [sum(len(r) for r in corpus[i:i + rows])
                    for i in range(0, len(corpus), rows)]
        assert max(per_step) / min(per_step) < 1.01
        sizes.append(sorted(len(r) for r in corpus))
    assert sizes[0] == sizes[1]

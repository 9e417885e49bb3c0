"""Protein MLM traffic: a corpus of heavy-tailed protein lengths, drawn
the same way for every seed.

A mix file (``bench/traffic/<name>.json``) gives the parameters; this one
generator reads them:

- ``mean_residues``, ``shape_mean_residues``, ``shape_median_residues``:
  protein lengths are lognormal with the mean ``mean_residues`` and the
  mean-to-median ratio of ``shape_mean_residues`` to
  ``shape_median_residues``, each a published figure (``sources``):
  sigma = sqrt(2 ln(mean / median)), median = mean / that ratio. A period
  holds ``period_proteins`` lengths at the distribution's quantiles
  ``(i + 0.5) / period_proteins``, so every seed gets the same set of
  sizes.
- ``max_residues``: longer proteins are cropped to a window of this many
  residues, at a start drawn from the seed (ESM-2 crops to 1022).
- ``seq_len``: rows are BOS + residues + EOS, padded to this length.
- ``rows``: proteins per optimizer step. The lengths of a period are
  dealt to its ``period_proteins / rows`` steps so that every step holds
  nearly the same number of real tokens (``balanced_steps``); the seed
  only orders the steps and the rows within a step.
- ``periods``: the corpus holds this many periods, every protein with
  fresh residues, i.i.d. over ``residue_ids``.

So the real tokens per step, and with them the work a window completes,
do not depend on the seed, while the pad share is the lognormal's.
"""
from __future__ import annotations

import json
import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np


def load_mix(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def lognormal(mix: Dict) -> Tuple[float, float]:
    """(median, sigma) of the lengths' lognormal (see the module docstring)."""
    ratio = mix["shape_mean_residues"] / mix["shape_median_residues"]
    return mix["mean_residues"] / ratio, math.sqrt(2 * math.log(ratio))


def quantile_lengths(mix: Dict) -> np.ndarray:
    """The period's protein lengths (residues, before cropping), ascending."""
    n = int(mix["period_proteins"])
    median, sigma = lognormal(mix)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(math.log(median) + sigma * z)
    return np.maximum(np.rint(lengths), 1).astype(np.int64)


def row_tokens(mix: Dict) -> np.ndarray:
    """Real tokens of each row of a period: cropped residues + BOS + EOS."""
    return np.minimum(quantile_lengths(mix), mix["max_residues"]) + 2


def balanced_steps(tokens: np.ndarray, rows: int) -> List[np.ndarray]:
    """Deal ``tokens`` (one entry per row) to ``len(tokens) // rows`` steps
    of ``rows`` rows each so that the steps' sums are as equal as a snake
    draft and then single swaps between the fullest and the emptiest step
    make them. Returns the row indices of each step; deterministic."""
    n_steps = len(tokens) // rows
    if n_steps * rows != len(tokens):
        raise ValueError(f"{len(tokens)} proteins do not fill steps of {rows}")
    order = np.argsort(-tokens, kind="stable")
    steps: List[List[int]] = [[] for _ in range(n_steps)]
    for r in range(rows):
        lane = range(n_steps) if r % 2 == 0 else range(n_steps - 1, -1, -1)
        for s, i in zip(lane, order[r * n_steps:(r + 1) * n_steps]):
            steps[s].append(int(i))
    for _ in range(len(tokens) * rows):
        sums = [int(tokens[s].sum()) for s in steps]
        hi, lo = int(np.argmax(sums)), int(np.argmin(sums))
        gap = sums[hi] - sums[lo]
        best = None
        for a in steps[hi]:
            for b in steps[lo]:
                d = int(tokens[a] - tokens[b])
                if 0 < d < gap and (best is None or abs(gap - 2 * d) < best[0]):
                    best = (abs(gap - 2 * d), a, b)
        if best is None or best[0] >= gap:
            break
        _, a, b = best
        steps[hi][steps[hi].index(a)] = b
        steps[lo][steps[lo].index(b)] = a
    return [np.asarray(s, np.int64) for s in steps]


def make_corpus(mix: Dict, seed: int) -> List[np.ndarray]:
    """The corpus in feed order: ``periods`` periods, each its balanced
    steps in an order drawn from ``seed``. Each protein is an int32 row of
    token ids: BOS, residues (cropped), EOS, unpadded."""
    rng = np.random.default_rng(seed)
    lengths = quantile_lengths(mix)
    steps = balanced_steps(row_tokens(mix), int(mix["rows"]))
    lo, hi = mix["residue_ids"]
    crop = int(mix["max_residues"])
    corpus: List[np.ndarray] = []
    for _ in range(int(mix["periods"])):
        for s in rng.permutation(len(steps)):
            for i in rng.permutation(steps[s]):
                full = int(lengths[i])
                start = int(rng.integers(0, full - crop + 1)) if full > crop else 0
                res = rng.integers(lo, hi + 1, size=full)[start:start + crop]
                corpus.append(np.concatenate(
                    [[mix["bos_id"]], res, [mix["eos_id"]]]).astype(np.int32))
    return corpus


def real_share(mix: Dict) -> float:
    """Share of the ``seq_len`` slots of a row that hold real tokens."""
    return float(row_tokens(mix).mean()) / mix["seq_len"]

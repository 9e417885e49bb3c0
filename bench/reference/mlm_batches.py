"""The reference's side of the MLM batches, built from the traffic's own
corpus (``bench/traffic/<generator>.py``), not from the program's data
path.

Batch ``j`` of a run holds the corpus's proteins ``j * rows`` to
``(j + 1) * rows - 1`` (the entry feeds them in order, wrapping), each
padded with ``pad_id`` to ``seq_len``: those rows are the targets. The
masking is a random draw of the program's, so the reference takes the
program's corrupted ``tokens`` and ``loss_mask`` and holds them to the
mix's recipe (``data_checks``):

- ``target_slots_wrong``: slots where the program's targets differ from
  the corpus's row;
- ``loss_on_non_residue``: slots in the loss that hold no residue (pad,
  BOS, EOS);
- ``unselected_changed``: slots out of the loss whose input differs from
  the corpus's row;
- ``mask_rate_z``: residues in the loss against ``mask_prob`` of all
  residues, in binomial standard deviations;
- ``mask_token_z``: the loss's slots given ``mask_id`` against
  ``mask_token_share`` of them, in binomial standard deviations.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np


def target_rows(mix: Dict, corpus: Sequence[np.ndarray], j: int) -> np.ndarray:
    """Batch ``j``'s rows of the corpus, padded: (rows, seq_len) int32."""
    rows, seq_len = int(mix["rows"]), int(mix["seq_len"])
    out = np.full((rows, seq_len), mix["pad_id"], np.int32)
    for r in range(rows):
        p = corpus[(j * rows + r) % len(corpus)][:seq_len]
        out[r, :len(p)] = p
    return out


def reference_batches(mix: Dict, corpus: Sequence[np.ndarray],
                      batches: Sequence[Dict]) -> List[Dict[str, np.ndarray]]:
    """The first ``len(batches)`` batches as the reference steps them:
    targets from the corpus, the program's corrupted tokens and loss mask."""
    return [{"tokens": np.asarray(b["tokens"]),
             "targets": target_rows(mix, corpus, j),
             "loss_mask": np.asarray(b["loss_mask"])}
            for j, b in enumerate(batches)]


def z_score(hits: int, n: int, p: float) -> float:
    """|hits - p n| in binomial standard deviations of ``n`` draws."""
    return abs(hits - p * n) / math.sqrt(max(n * p * (1 - p), 1e-12))


def data_checks(mix: Dict, corpus: Sequence[np.ndarray],
                batches: Sequence[Dict]) -> Dict[str, float]:
    """The numbers of the module docstring over ``batches``, the program's
    batches in the order it drew them."""
    lo, hi = mix["residue_ids"]
    wrong = off = changed = residues = picked = masked = 0
    for j, b in enumerate(batches):
        want = target_rows(mix, corpus, j)
        tokens = np.asarray(b["tokens"])
        loss = np.asarray(b["loss_mask"]) != 0
        residue = (want >= lo) & (want <= hi)
        wrong += int((np.asarray(b["targets"]) != want).sum())
        off += int((loss & ~residue).sum())
        changed += int(((tokens != want) & ~loss).sum())
        residues += int(residue.sum())
        picked += int((loss & residue).sum())
        masked += int((loss & (tokens == mix["mask_id"])).sum())
    return {
        "target_slots_wrong": wrong,
        "loss_on_non_residue": off,
        "unselected_changed": changed,
        "mask_rate_z": z_score(picked, residues, mix["mask_prob"]),
        "mask_token_z": z_score(masked, picked, mix["mask_token_share"]),
    }

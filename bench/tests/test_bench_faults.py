"""The comparison that decides ``correct``, driven through a whole run of
the tiny cell on the CPU (the look for a chip skipped): it holds for the
program as it is, and fails for each fault a one-chip training cell can
have, planted underneath the timed path, for the control, and for
batches altered where the program's data path makes them.

Limits of the tiny cell are in conftest.py with the readings they come
from; the cells' own limits are in bench/limits/."""
import numpy as np
import pytest

import run as R
from repro.data import pipeline as DP
from repro.models import model as M
from repro.training import train_step as TS

SEED = 2**31 + 7


def cpu(jax, chips):
    return R.device_info(jax)


def run_tiny(root):
    args = R.parse(["--workload", "tiny.mlm", "--seed", str(SEED),
                    "--seconds", "2"])
    return R.run(args, root=str(root), chips_check=cpu)


def failed_numbers(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


def test_sound_program_is_correct(tiny_root):
    res = run_tiny(tiny_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_state_left_unchanged_fails(tiny_root, monkeypatch):
    real = TS.make_train_step

    def unchanged(model, tc):
        step = real(model, tc)

        def broken(state, batch):
            return state, step(state, batch)[1]

        return broken

    monkeypatch.setattr(TS, "make_train_step", unchanged)
    res = run_tiny(tiny_root)
    assert not res["correct"]
    assert {"grad_norm_gap", "change_norm_gap"} <= set(failed_numbers(res))
    assert res["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_fails(tiny_root, monkeypatch):
    real = M.Model.loss_fn

    def half(self, params, batch):
        n = batch["tokens"].shape[0] // 2
        return real(self, params, {k: v[:n] for k, v in batch.items()})

    monkeypatch.setattr(M.Model, "loss_fn", half)
    res = run_tiny(tiny_root)
    assert not res["correct"]
    assert "loss_gap" in failed_numbers(res)


def test_control_fails(tiny_root, tiny_limits):
    """The reference in fp8, in the program's place, against the reference
    (what bench/calibrate.py reads on the chip at the cells' sizes)."""
    import calibrate as C

    args = C.argparse.Namespace(workload="tiny.mlm", seeds=[],
                                control_seeds=[SEED])
    lines = {l["kind"]: l for l in C.readings(args, root=str(tiny_root),
                                                chips_check=cpu)}
    control = lines["control"]
    assert any(control[k] > tiny_limits[k] for k in control if k in tiny_limits), control
    half = lines["half_batch"]
    assert any(half[k] > tiny_limits[k] for k in half if k in tiny_limits), half


def loss_on_pad(out):
    out["loss_mask"][:, -1] = 1.0  # every row's last slot, a pad
    return out


def target_altered(out):
    out["targets"][0, 1] = 5 + (out["targets"][0, 1] - 4) % 20  # another residue
    return out


def input_altered(out):
    row = np.flatnonzero(out["loss_mask"][0] == 0)
    out["tokens"][0, row[1]] = 4  # a slot out of the loss given <mask>
    return out


def every_loss_slot_masked(out):
    out["tokens"][out["loss_mask"] > 0] = 4  # no 10% random, no 10% kept
    return out


DATA_FAULTS = {
    # name: (alteration of the batch, masking rate in place of 15%, number)
    "every_loss_slot_masked": (every_loss_slot_masked, None, "mask_token_z"),
    "loss_on_pad": (loss_on_pad, None, "loss_on_non_residue"),
    "target_altered": (target_altered, None, "target_slots_wrong"),
    "input_altered": (input_altered, None, "unselected_changed"),
    "mask_rate_doubled": (lambda out: out, 0.3, "mask_rate_z"),
}


@pytest.mark.parametrize("fault", sorted(DATA_FAULTS))
def test_data_fault_fails(tiny_root, monkeypatch, fault):
    """A batch altered where the program's data path makes it: the
    reference, building its targets from the corpus, catches it."""
    alter, rate, number = DATA_FAULTS[fault]
    real = DP.mlm_corrupt

    def corrupt(tokens, tokenizer, rng, mask_prob=0.15):
        return alter(real(tokens, tokenizer, rng, rate or mask_prob))

    monkeypatch.setattr(DP, "mlm_corrupt", corrupt)
    res = run_tiny(tiny_root)
    assert not res["correct"]
    assert number in failed_numbers(res)


"""The check catches a broken timed path: each fault the cells can have,
planted under a whole small run on the CPU, makes ``correct`` false."""
import json

import pytest
from conftest import ROOT
from conftest import small_run as run

import repro.train.trainer as trainer_mod
from bench import calibrate

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def small_run(name):
    return run(name, 31337)


def frozen_dispatch(self, fn, *args):
    """A step that returns its state unchanged."""
    out = fn(self.params, self.opt_state, *args)
    return out[2], out[3], None


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged(name, monkeypatch):
    monkeypatch.setattr(trainer_mod.Trainer, "_dispatch_step", frozen_dispatch)
    result = small_run(name)
    assert not result["correct"]
    assert result["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_half_the_batch_left_out(name, monkeypatch):
    monkeypatch.setattr(trainer_mod, "masked_softmax_xent",
                        calibrate.half_batch_loss(trainer_mod.masked_softmax_xent))
    assert not small_run(name)["correct"]


"""The trainer cell's check fails where it should, on the CPU at a toy
size: the control (the reference's products in float8 put in the
program's place) and faults planted in the trainer underneath a run."""
import time

import pytest

from conftest import CPU

from bench import harness
from bench.surfaces import train


def run(root, seed=4):
    cell = harness.Cell("tiny.train", root=root)
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            device=dict(CPU))


def failed(out):
    return {n for n, c in out["checks"].items() if c["value"] > c["limit"]}


def test_control_fails(train_root):
    cell = harness.Cell("tiny.train", root=train_root)
    state = train.State(cell.config, cell.traffic, 3)
    got = train.readings(state, 3, train.CONTROL)
    limits = cell.traffic["limits"]
    assert any(got[n] > lim for n, lim in limits.items()), got


def test_state_unchanged_fails(train_root, monkeypatch):
    """Every update returns the parameters and state it was given."""
    from repro.optim.optimizers import DelayAdaptiveOptimizer
    real = DelayAdaptiveOptimizer.step_fn

    def unchanged(self, params, grads, state, tau):
        _, _, gamma = real(self, params, grads, state, tau)
        return params, state, gamma
    monkeypatch.setattr(DelayAdaptiveOptimizer, "step_fn", unchanged)
    out = run(train_root)
    assert out["correct"] is False
    assert {"grad_leaf_gap", "change_leaf_gap"} <= failed(out)


@pytest.mark.parametrize("fault,catches", [
    ("half_batch", {"grad_diff_median"}),
    ("altered_step", {"gamma_err"}),
])
def test_planted_fault_fails(train_root, fault, catches):
    """The surface's faults (each gradient over half of its batch; the
    policy's step-size altered where it is produced) planted underneath a
    whole run."""
    undo = train.FAULTS[fault]()
    try:
        out = run(train_root)
    finally:
        undo()
    assert out["correct"] is False
    assert failed(out) & catches

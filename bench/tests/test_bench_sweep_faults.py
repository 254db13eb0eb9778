"""The sweep cell's check fails where it should: the control (the plain
reference in bfloat16 put in the program's place) and faults planted in
the timed path underneath a whole run on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import CPU

from bench import harness
from bench.surfaces import sweep


def run(root, seed=11):
    cell = harness.Cell("tiny.grid", root=root)
    return harness.run_cell(cell, seed, 0.2, False, time.perf_counter(),
                            device=dict(CPU))


def failed(out):
    return {n for n, c in out["checks"].items() if c["value"] > c["limit"]}


def test_control_fails(tiny_root, monkeypatch):
    def control(state, cells):
        out = sweep.reference_outputs(state, cells, dtype=sweep.CONTROL,
                                      precision=jax.lax.Precision.DEFAULT)
        return dict(out, tau_bar=int(state.results[-1].tau_bar))
    monkeypatch.setattr(sweep, "program_outputs", control)
    out = run(tiny_root)
    assert out["correct"] is False
    assert failed(out) & {"gamma_err", "objective_rel", "x_rel"}


def _wrap_scan(monkeypatch, change):
    import repro.sweep.runners as runners
    real = runners.piag_scan
    monkeypatch.setattr(runners, "piag_scan",
                        lambda *a, **k: change(real, *a, **k))


def test_state_unchanged_fails(tiny_root, monkeypatch):
    """Every step returns the iterate it was given."""
    _wrap_scan(monkeypatch, lambda real, *a, **k: real(
        *a, **dict(k, grad_fn=lambda x, *d: jnp.zeros_like(x))))
    out = run(tiny_root)
    assert out["correct"] is False
    assert {"objective_rel", "x_rel"} <= failed(out)


def test_half_batch_fails(tiny_root, monkeypatch):
    """Each worker's gradient is the mean over half of its samples."""
    from repro.core.problems import LogRegProblem
    real = LogRegProblem.worker_loss
    monkeypatch.setattr(LogRegProblem, "worker_loss",
                        lambda self, x, A, b: real(self, x, A[:A.shape[0] // 2],
                                                   b[:b.shape[0] // 2]))
    out = run(tiny_root)
    assert out["correct"] is False
    assert "x_rel" in failed(out)


@pytest.mark.parametrize("field,change,check", [
    ("objective", lambda v: v.at[7].multiply(1.01), "objective_rel"),
    ("taus", lambda v: v.at[5].add(1), "tau_mismatch"),
])
def test_altered_answer_fails(tiny_root, monkeypatch, field, change, check):
    """One answer of every cell altered where the solver produces it."""
    def altered(real, *a, **k):
        res = real(*a, **k)
        return res._replace(**{field: change(getattr(res, field))})
    _wrap_scan(monkeypatch, altered)
    out = run(tiny_root)
    assert out["correct"] is False
    assert check in failed(out)

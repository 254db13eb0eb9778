"""The worker-gradient layouts of the batched PIAG program.

``piag_scan(grad_layout="gathered")`` slices the returning worker's shard,
which under the cells vmap copies one shard per cell every event;
``"grouped"`` differentiates every worker's loss at the returning worker's
snapshot and keeps row w, two products over the stacked shards.  Rows of
the two layouts agree within the solo envelope of ``tests/test_sweep.py``
on every path of the step; the runners pick the layout from the cells one
device runs and the bucket width, and key their programs on it.
"""
import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.spec import ExecutionSpec, PolicyGridSpec
from repro.core import (Adaptive1, Adaptive2, FixedStepSize, L1, WorkerModel,
                        generate_trace, make_logreg, run_piag_logreg)
from repro.core import piag as piag_mod
from repro.core.engine import heterogeneous_workers
from repro.faults import FaultSpec
from repro.staticcheck import cachekey as ck
from repro.sweep import make_grid, make_sweep_piag, pick_grad_layout
from repro.sweep.shard import _piag_grad_layout
from repro.telemetry.accumulators import TelemetryConfig


@pytest.fixture(scope="module")
def problem():
    return make_logreg(240, 40, n_workers=4, seed=0)


def _gamma_envelope(gp: float) -> float:
    return 32 * float(np.spacing(np.float32(gp)))


def _policies(gp):
    return {"a1": Adaptive1(gamma_prime=gp),
            "a2": Adaptive2(gamma_prime=gp),
            "fx": FixedStepSize(gamma_prime=gp, tau_bound=12)}


def _grid(gp, n_events=120, n_workers=None):
    if n_workers is None:
        topologies = {"uniform": [WorkerModel() for _ in range(4)],
                      "hetero": heterogeneous_workers(4, seed=1)}
    else:  # ragged: one bucket of width max(n_workers), padded and masked
        topologies = {"uniform": lambda w: [WorkerModel() for _ in range(w)],
                      "hetero": lambda w: heterogeneous_workers(w, seed=1)}
    return make_grid(policies=_policies(gp), seeds=[0, 1],
                     topologies=topologies, n_events=n_events,
                     n_workers=n_workers)


def _program(problem, layout, **kw):
    return make_sweep_piag(
        lambda x, A, b: problem.worker_loss(x, A, b),
        jnp.zeros((problem.dim,), jnp.float32), problem.worker_slices(),
        L1(lam=problem.lam1), objective=problem.P, grad_layout=layout, **kw)


# each path of piag_scan's step that the worker gradient feeds: the
# program's keywords and whether the grid is ragged (masked bucket)
PATHS = {
    "plain": ({}, False),
    "masked-ragged": ({"masked": True}, True),
    "faults": ({"faults": FaultSpec(p_crash=0.05, p_rejoin=0.3,
                                    p_spike=0.1, p_drop=0.1, p_dup=0.05,
                                    p_corrupt=0.05, seed=0)}, False),
    "telemetry": ({"telemetry": TelemetryConfig(delay_bins=8)}, False),
    "fused": ({"engine": "fused"}, False),
}


def _args(grid, kw, ragged):
    width = 4
    args = [jnp.asarray(grid.service_times(width))]
    if ragged:
        args.append(jnp.asarray(grid.active_masks(width)))
    args.append(grid.policy_params())
    if "faults" in kw:
        args.append(jnp.asarray([c.seed for c in grid.cells], jnp.int32))
    return args


def _assert_rows_close(a, b, gp):
    np.testing.assert_array_equal(np.asarray(a.taus), np.asarray(b.taus))
    np.testing.assert_allclose(np.asarray(a.gammas), np.asarray(b.gammas),
                               rtol=1e-6, atol=_gamma_envelope(gp))
    np.testing.assert_allclose(np.asarray(a.objective),
                               np.asarray(b.objective), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_grouped_rows_match_gathered(problem, path):
    kw, ragged = PATHS[path]
    gp = 0.99 / problem.L
    grid = _grid(gp, n_workers=[3, 4] if ragged else None)
    args = _args(grid, kw, ragged)
    gathered = _program(problem, "gathered", **kw)(*args)
    grouped = _program(problem, "grouped", **kw)(*args)
    _assert_rows_close(gathered, grouped, gp)
    # the accumulators ride after the gradient: counts equal, moments close
    for extra in ("telemetry", "faults"):
        la = jax.tree_util.tree_leaves(getattr(gathered, extra))
        lb = jax.tree_util.tree_leaves(getattr(grouped, extra))
        assert len(la) == len(lb)
        for u, v in zip(la, lb):
            u, v = np.asarray(u), np.asarray(v)
            if np.issubdtype(u.dtype, np.integer):
                np.testing.assert_array_equal(u, v)
            else:
                np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6)


def test_grouped_rows_match_solo(problem):
    gp = 0.99 / problem.L
    prox = L1(lam=problem.lam1)
    grid = _grid(gp)
    res = _program(problem, "grouped")(*_args(grid, {}, False))
    Ts = grid.service_times()
    for i, cell in enumerate(grid.cells):
        solo = run_piag_logreg(problem, generate_trace(Ts[i]), cell.policy,
                               prox)
        row = jax.tree_util.tree_map(lambda leaf: leaf[i], res)
        _assert_rows_close(solo, row, gp)


def test_grouped_program_copies_no_shard_per_cell():
    """The compiled grouped program holds no (cells, rows, dim) array: no
    gather or dynamic-slice copies a worker shard per cell.  The gathered
    program, compiled alike, does (the check can see the copy).  Its two
    products run at the highest precision, as float32 as the gathered
    layout's multiply-reduces; nothing else in either program asks for
    it."""
    problem = make_logreg(120, 13, n_workers=4, seed=0)  # shards 30 x 13
    gp = 0.99 / problem.L
    grid = make_grid(policies=_policies(gp), seeds=[0, 1],
                     topologies={"uniform": [WorkerModel()
                                             for _ in range(4)]},
                     n_events=40)
    assert len(grid) == 6
    args = _args(grid, {}, False)
    lowered = {layout: _program(problem, layout, horizon=64).lower(*args)
               for layout in ("gathered", "grouped")}
    highest = [line for line in lowered["grouped"].as_text().splitlines()
               if "precision = [HIGHEST, HIGHEST]" in line]
    assert len(highest) == 2
    assert all("tensor<4x30x13xf32>" in line for line in highest)
    assert "HIGHEST" not in lowered["gathered"].as_text()
    texts = {k: v.compile().as_text() for k, v in lowered.items()}
    copy = "f32[6,30,13]"
    assert copy in texts["gathered"]
    assert copy not in texts["grouped"]
    assert "f32[4,6,13]" in texts["grouped"]  # every worker's gradient


# ------------------------------------------------------ the layout rule ----

@pytest.mark.parametrize("cells,width,grad_fn,layout", [
    (48, 10, None, "grouped"),
    (6, 10, None, "grouped"),
    (5, 10, None, "gathered"),
    (3, 10, None, "gathered"),
    (48, 10, lambda x, A, b: x, "gathered"),
], ids=["wide", "above-half", "half-width", "narrow", "grad_fn"])
def test_pick_grad_layout(cells, width, grad_fn, layout):
    assert pick_grad_layout(cells, width, grad_fn) == layout


def test_solo_run_piag_keeps_gathered(problem, monkeypatch):
    seen = []
    scan = piag_mod.piag_scan

    def spy(*args, **kw):
        seen.append(kw.get("grad_layout", inspect.signature(
            scan).parameters["grad_layout"].default))
        return scan(*args, **kw)

    monkeypatch.setattr(piag_mod, "piag_scan", spy)
    trace = generate_trace(_grid(0.5, n_events=20).service_times()[0])
    run_piag_logreg(problem, trace, Adaptive1(gamma_prime=0.5),
                    L1(lam=problem.lam1))
    assert seen == ["gathered"]


@pytest.mark.parametrize("cells,devices,layout", [
    (48, 1, "grouped"), (48, 8, "grouped"), (12, 1, "grouped"),
    (12, 4, "gathered"), (3, 1, "gathered")])
def test_sharded_layout_counts_cells_per_device(cells, devices, layout):
    mesh = types.SimpleNamespace(axis_names=("cells",),
                                 shape={"cells": devices})
    assert _piag_grad_layout(cells, 10, mesh, None) == layout
    assert _piag_grad_layout(cells, 10, mesh, lambda x: x) == "gathered"


@pytest.mark.parametrize("backend", ["batched", "sharded"])
def test_layout_rides_the_program_cache_key(backend):
    """Base spec: 1 cell of width 3 (gathered); two seeds make 2 cells on
    one device, more than half the width (grouped).  The key carries the
    layout, so a program built for one is never served to the other."""
    execution = ExecutionSpec(backend=backend,
                              devices=1 if backend == "sharded" else None)
    one = ck.capture(ck.base_spec("piag", execution=execution))
    two = ck.capture(ck.base_spec("piag", execution=execution).replace(
        policies=PolicyGridSpec(names=("adaptive1",), seeds=(0, 1))))
    assert "gathered" in one.key and "grouped" not in one.key
    assert "grouped" in two.key and "gathered" not in two.key
    (outcome,) = ck.check_completeness(only=[("PolicyGridSpec", "seeds")])
    assert not outcome.violation, outcome


def test_unknown_layout_is_refused(problem):
    with pytest.raises(ValueError, match="grad_layout"):
        _program(problem, "scattered")(*_args(_grid(0.5, n_events=8), {},
                                              False))

"""The unified spec API's redesign contract: bitwise fidelity.

For every solver in {piag, bcd, fedasync, fedbuff} and every backend in
{solo, batched, sharded}, ``repro.api.run(spec)`` rows must be
BITWISE-identical to the pre-redesign runner the spec dispatches to --
the spec layer routes, it never re-implements numerics.  The expected
values here are computed by calling those runners directly with exactly
the argument patterns the legacy conveniences used.

Also pinned: the declarative build path (spec -> problem/policies/grid)
matches the manual construction it automates, spec-build-time horizon
validation (satellite: fail early instead of the post-hoc ``clipped``
counter), the legacy shims (DeprecationWarning + bitwise-equal rows), the
``Results`` common columns, and the round-robin padding rule.
"""
import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import (Adaptive1, Adaptive2, FixedStepSize, L1,
                        make_logreg)
from repro.core.bcd import run_async_bcd, sample_blocks
from repro.core.engine import (WorkerModel, generate_trace,
                               heterogeneous_workers, sample_service_times)
from repro.core.piag import run_piag
from repro.core.stepsize import HingeWeight, PolyWeight
from repro.federated.events import (generate_federated_trace,
                                    heterogeneous_clients)
from repro.federated.server import (_problem_pieces, run_fedasync,
                                    run_fedbuff)
from repro.sweep import make_grid, round_robin_pad
from repro.sweep.runners import (sweep_bcd, sweep_fedasync, sweep_fedbuff,
                                 sweep_piag)
from repro.sweep.shard import (sharded_sweep_bcd, sharded_sweep_fedasync,
                               sharded_sweep_fedbuff, sharded_sweep_piag)

N_EVENTS = 100
N_EVENTS_FED = 80
M_BLOCKS = 8


@pytest.fixture(scope="module")
def problem():
    return make_logreg(240, 40, n_workers=4, seed=0)


@pytest.fixture(scope="module")
def prox(problem):
    return L1(lam=problem.lam1)


@pytest.fixture(scope="module")
def worker_grid(problem):
    gp = 0.99 / problem.L
    return make_grid(
        policies={"a1": Adaptive1(gamma_prime=gp),
                  "fx": FixedStepSize(gamma_prime=gp, tau_bound=40)},
        seeds=[0, 1],
        topologies={"uniform": [WorkerModel() for _ in range(4)],
                    "hetero": heterogeneous_workers(4, seed=1)},
        n_events=N_EVENTS)


@pytest.fixture(scope="module")
def fed_grid():
    return make_grid(
        policies={"hinge": HingeWeight(gamma_prime=0.6),
                  "poly": PolyWeight(gamma_prime=0.6, a=0.5)},
        seeds=[0, 1],
        topologies={"edge": heterogeneous_clients(4, seed=2)},
        n_events=N_EVENTS_FED)


def assert_raw_bitwise(actual, expected):
    """Every leaf of the solver result tuple, bit for bit."""
    assert type(actual).__name__ == type(expected).__name__
    for f in expected._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(expected, f)), np.asarray(getattr(actual, f)),
            err_msg=f)


def _stack(rows):
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *rows)


# ------------------------------------------- solver x backend parity ----

def _piag_expected(problem, grid, prox, backend):
    Aw, bw = problem.worker_slices()
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    loss = lambda x, A, b: problem.worker_loss(x, A, b)
    if backend == "batched":
        return sweep_piag(loss, x0, (Aw, bw), grid, prox,
                          objective=problem.P, horizon=4096)
    if backend == "sharded":
        return sharded_sweep_piag(loss, x0, (Aw, bw), grid, prox,
                                  objective=problem.P, horizon=4096)
    rows = []
    for c in grid.cells:
        T = sample_service_times(c.workers, grid.n_events + 1, seed=c.seed)
        tr = generate_trace(T)
        w = c.n_workers
        rows.append(run_piag(loss, x0, (Aw[:w], bw[:w]), tr, c.policy, prox,
                             objective=problem.P, horizon=4096))
    return _stack(rows)


def _bcd_expected(problem, grid, prox, backend):
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    if backend == "batched":
        return sweep_bcd(problem.grad_f, problem.P, x0, M_BLOCKS, grid, prox,
                         horizon=4096)
    if backend == "sharded":
        return sharded_sweep_bcd(problem.grad_f, problem.P, x0, M_BLOCKS,
                                 grid, prox, horizon=4096)
    rows = []
    for c in grid.cells:
        T = sample_service_times(c.workers, grid.n_events + 1, seed=c.seed)
        tr = generate_trace(T, kind="shared_memory")
        blocks = sample_blocks(M_BLOCKS, grid.n_events, seed=c.seed)
        rows.append(run_async_bcd(problem.grad_f, problem.P, x0, M_BLOCKS,
                                  tr, blocks, c.policy, prox, horizon=4096))
    return _stack(rows)


def _fed_expected(problem, grid, prox, backend, solver):
    update, x0, data = _problem_pieces(problem, prox, None)
    eta, bs = 0.5, (2 if solver == "fedbuff" else 1)
    if backend == "batched":
        if solver == "fedasync":
            return sweep_fedasync(update, x0, data, grid,
                                  objective=problem.P, horizon=4096)
        return sweep_fedbuff(update, x0, data, grid, eta=eta, buffer_size=bs,
                             objective=problem.P, horizon=4096)
    if backend == "sharded":
        if solver == "fedasync":
            return sharded_sweep_fedasync(update, x0, data, grid,
                                          objective=problem.P, horizon=4096)
        return sharded_sweep_fedbuff(update, x0, data, grid, eta=eta,
                                     buffer_size=bs, objective=problem.P,
                                     horizon=4096)
    rows = []
    for c in grid.cells:
        tr = generate_federated_trace(c.n_workers, grid.n_events,
                                      clients=list(c.workers),
                                      buffer_size=bs, seed=c.seed)
        cd = jax.tree_util.tree_map(lambda l: l[:c.n_workers], data)
        if solver == "fedasync":
            rows.append(run_fedasync(update, x0, cd, tr, c.policy,
                                     objective=problem.P, horizon=4096))
        else:
            rows.append(run_fedbuff(update, x0, cd, tr, c.policy, eta=eta,
                                    buffer_size=bs, objective=problem.P,
                                    horizon=4096))
    return _stack(rows)


@pytest.mark.parametrize("backend", api.BACKENDS)
def test_api_piag_rows_bitwise_equal_runner(problem, worker_grid, prox,
                                            backend):
    res = api.run_components("piag", backend, problem=problem,
                             grid=worker_grid, prox=prox, horizon=4096)
    assert res.solver == "piag" and res.backend == backend
    assert_raw_bitwise(res.raw,
                       _piag_expected(problem, worker_grid, prox, backend))


@pytest.mark.parametrize("backend", api.BACKENDS)
def test_api_bcd_rows_bitwise_equal_runner(problem, worker_grid, prox,
                                           backend):
    res = api.run_components("bcd", backend, problem=problem,
                             grid=worker_grid, prox=prox, m=M_BLOCKS,
                             horizon=4096)
    assert_raw_bitwise(res.raw,
                       _bcd_expected(problem, worker_grid, prox, backend))


@pytest.mark.parametrize("backend", api.BACKENDS)
def test_api_fedasync_rows_bitwise_equal_runner(problem, fed_grid, prox,
                                                backend):
    res = api.run_components("fedasync", backend, problem=problem,
                             grid=fed_grid, prox=prox, horizon=4096)
    assert_raw_bitwise(res.raw, _fed_expected(problem, fed_grid, prox,
                                              backend, "fedasync"))


@pytest.mark.parametrize("backend", api.BACKENDS)
def test_api_fedbuff_rows_bitwise_equal_runner(problem, fed_grid, prox,
                                               backend):
    res = api.run_components("fedbuff", backend, problem=problem,
                             grid=fed_grid, prox=prox, eta=0.5,
                             buffer_size=2, horizon=4096)
    assert_raw_bitwise(res.raw, _fed_expected(problem, fed_grid, prox,
                                              backend, "fedbuff"))


# ----------------------------------------------- declarative build ----

def test_declarative_spec_matches_manual_build():
    """A fully-declarative spec (problem + topology + policies built by the
    resolver) reproduces the manually-constructed grid run bitwise: the
    resolver uses the same make_* factories and the same tau-bar protocol
    the callers used inline."""
    spec = api.ExperimentSpec(
        problem=api.ProblemSpec(kind="logreg",
                                params=dict(n_samples=240, dim=40, seed=0)),
        solver=api.SolverSpec(name="piag", horizon=4096),
        topology=api.TopologySpec(kind="standard",
                                  names=("uniform", "hetero2"),
                                  n_workers=(4,)),
        policies=api.PolicyGridSpec(names=("adaptive1", "adaptive2"),
                                    seeds=(0, 1)),
        n_events=N_EVENTS)
    res = api.run(spec)

    # the manual equivalent of what the resolver builds
    from repro.sweep import standard_topology_factories
    problem = make_logreg(n_samples=240, dim=40, seed=0, n_workers=4)
    prox = L1(lam=problem.lam1)
    gp = 0.99 / problem.L
    facs = standard_topology_factories(0)
    grid = make_grid({"adaptive1": Adaptive1(gamma_prime=gp),
                      "adaptive2": Adaptive2(gamma_prime=gp)},
                     [0, 1],
                     {k: facs[k] for k in ("uniform", "hetero2")},
                     N_EVENTS, n_workers=[4])
    assert [c.policy_name for c in res.grid.cells] == \
        [c.policy_name for c in grid.cells]
    expected = _piag_expected(problem, grid, prox, "batched")
    assert_raw_bitwise(res.raw, expected)


# ---------------------------------------------- horizon validation ----

def test_spec_construction_rejects_unrepresentable_declared_delay():
    """Satellite: a spec whose horizon cannot represent the DECLARED
    expected max delay fails at construction (window_sum caps at H - 1),
    not via the post-hoc clipped counter."""
    with pytest.raises(ValueError, match="H - 1"):
        api.ExperimentSpec(
            solver=api.SolverSpec(name="piag", horizon=16),
            delay=api.DelaySpec(expected_max_delay=16))
    # H - 1 == expected delay is representable: constructs fine
    api.ExperimentSpec(solver=api.SolverSpec(name="piag", horizon=17),
                       delay=api.DelaySpec(expected_max_delay=16))


def test_resolve_rejects_horizon_below_measured_tau_bar():
    """With no declared bound, the resolver measures tau-bar from the
    grid's own traces and validates the horizon against it BEFORE running
    anything."""
    spec = api.ExperimentSpec(
        problem=api.ProblemSpec(kind="logreg",
                                params=dict(n_samples=120, dim=20, seed=0)),
        solver=api.SolverSpec(name="piag", horizon=4),
        topology=api.TopologySpec(kind="standard", names=("straggler",),
                                  n_workers=(4,)),
        policies=api.PolicyGridSpec(names=("adaptive1",), seeds=(0,)),
        n_events=60)
    with pytest.raises(ValueError, match="expected max delay"):
        spec.validate()
    # a roomy horizon passes the same validation
    spec.replace(solver=api.SolverSpec(name="piag", horizon=4096)).validate()


def test_component_spec_skips_validation_for_deliberate_tiny_horizons(
        problem, worker_grid, prox):
    """The shims must keep serving deliberate undersized-horizon runs (the
    clipped-counter diagnostics), so component specs validate nothing."""
    res = api.run_components("piag", "batched", problem=problem,
                             grid=worker_grid, prox=prox, horizon=2)
    assert np.asarray(res.clipped).sum() > 0  # post-hoc counter still works


# ------------------------------------------------------ legacy shims ----

def test_legacy_shims_warn_and_match_spec_rows(problem, worker_grid, prox):
    from repro.sweep import sweep_piag_logreg
    with pytest.warns(DeprecationWarning, match="repro.api"):
        legacy = sweep_piag_logreg(problem, worker_grid, prox)
    res = api.run_components("piag", "batched", problem=problem,
                             grid=worker_grid, prox=prox, horizon=4096)
    assert_raw_bitwise(legacy, res.raw)


def test_legacy_fed_shim_warns_and_matches(problem, fed_grid, prox):
    from repro.sweep import sweep_fedasync_problem
    with pytest.warns(DeprecationWarning, match="repro.api"):
        legacy = sweep_fedasync_problem(problem, fed_grid, prox)
    res = api.run_components("fedasync", "batched", problem=problem,
                             grid=fed_grid, prox=prox, horizon=4096)
    assert_raw_bitwise(legacy, res.raw)


# ------------------------------------------------- Results surface ----

def test_results_common_columns(problem, fed_grid, prox):
    res = api.run_components("fedbuff", "batched", problem=problem,
                             grid=fed_grid, prox=prox, eta=0.5,
                             buffer_size=2)
    # fed weights surface under the unified `gammas` column
    np.testing.assert_array_equal(np.asarray(res.gammas),
                                  np.asarray(res.raw.weights))
    assert "versions" in res.extras
    rows = res.to_rows()
    assert rows[0].keys() >= {"label", "policy", "seed", "topology",
                              "n_workers", "final_objective", "sum_gamma",
                              "max_tau", "clipped"}
    summary = res.per_policy()
    assert set(summary) == {"hinge", "poly"}
    assert res.clipped_summary()["cells"] == len(fed_grid)


def test_results_virtual_time_matches_traces(problem, worker_grid, prox):
    """The wall/virtual-time column reproduces each cell's trace clock."""
    res = api.run_components("piag", "batched", problem=problem,
                             grid=worker_grid, prox=prox)
    vt = res.virtual_time()
    assert vt.shape == (len(worker_grid), worker_grid.n_events)
    c = worker_grid.cells[0]
    T = sample_service_times(c.workers, worker_grid.n_events + 1, seed=c.seed)
    tr = generate_trace(T)
    np.testing.assert_array_equal(vt[0], tr.t_wall.astype(vt.dtype))


def test_execution_spec_bucket_widths_routes_to_runners(problem, prox):
    """ExecutionSpec.bucket_widths overrides the ragged grid's padded-width
    menu: forcing every cell into one width-8 masked bucket must reproduce
    the default (pow-2 buckets) rows -- the bucketed == exact-width
    guarantee -- through the spec API."""
    gp = 0.99 / problem.L
    from repro.sweep import standard_topology_factories
    facs = standard_topology_factories()
    grid = make_grid({"a1": Adaptive1(gamma_prime=gp)}, [0, 1],
                     {"uniform": facs["uniform"]}, 80, n_workers=[3, 4])
    default = api.run_components("piag", "batched", problem=problem,
                                 grid=grid, prox=prox)
    forced = api.run(api.component_spec(
        "piag", "batched", problem=problem, grid=grid, prox=prox).replace(
            execution=api.ExecutionSpec(backend="batched",
                                        bucket_widths=(4,))))
    np.testing.assert_array_equal(np.asarray(default.taus),
                                  np.asarray(forced.taus))
    np.testing.assert_allclose(np.asarray(default.objective),
                               np.asarray(forced.objective),
                               rtol=1e-6, atol=1e-7)


# ------------------------------------------------ shard pad fix ----

def test_round_robin_pad_one_round_per_cells_axis():
    """The pad is the next cells-axis multiple and nothing more: one cell
    per shard is a valid batch, and padding replays real cells in order."""
    np.testing.assert_array_equal(round_robin_pad(8, 8), np.arange(8))
    np.testing.assert_array_equal(round_robin_pad(4, 4), np.arange(4))
    np.testing.assert_array_equal(round_robin_pad(5, 4),
                                  [0, 1, 2, 3, 4, 0, 1, 2])
    assert round_robin_pad(8, 1).size == 8
    assert round_robin_pad(12, 8).size == 16
    assert round_robin_pad(512, 8).size == 512


def test_execution_devices_beyond_available_raise(problem, worker_grid, prox):
    """Asking for more devices than exist fails loudly instead of running
    a smaller mesh."""
    n = len(jax.devices())
    spec = api.component_spec("piag", "sharded", problem=problem,
                              grid=worker_grid, prox=prox)
    spec = spec.replace(execution=dataclasses.replace(
        spec.execution, devices=n + 1))
    with pytest.raises(ValueError, match="devices"):
        api.run(spec)

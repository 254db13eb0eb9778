"""2-D ``(cells, data)`` sweep meshes (PR 10).

The sharded backend's second mesh axis computes each cell's per-worker
gradients data-parallel (``repro.mesh.pmean_grad``: slice the sample axis
per data shard, psum the partial gradients).  The pins here are the
tentpole acceptance criteria:

* solo vs 1-D sharded vs 2-D sharded rows are bitwise-equal on every
  integer leaf (taus, clipped, blocks, versions, fault counters) for all
  four solvers, objectives equal under jit -- including ragged bucket
  widths and faults-on chaos runs;
* ``round_robin_pad`` keys on the CELLS axis only (a (2, 4) mesh pads like
  a (2,) mesh);
* meshes key the program cache by TOPOLOGY (axis names + shape + device
  kind + process count), so a 1-D and a reshaped 2-D mesh over the same
  devices never share an executable, while same-topology rebuilds do;
* the multi-host knobs bootstrap ``jax.distributed`` exactly once and
  never reach a traced program.

Multi-device assertions activate under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (CI multi-device
lane); on fewer devices they skip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.mesh as rmesh
from repro.api import run_components
from repro.core import (Adaptive1, FixedStepSize, L1, make_logreg,
                        sample_service_times, trace_scan)
from repro.core.engine import WorkerModel, heterogeneous_workers
from repro.core.piag import piag_scan
from repro.core.stepsize import HingeWeight
from repro.faults import FaultSpec
from repro.federated.events import heterogeneous_clients
from repro.federated.server import _problem_pieces, local_prox_sgd
from repro.mesh import (DATA_AXIS, cell_axis_size, cell_mesh, data_axis_size,
                        grid_mesh, mesh_topology, pmean_grad)
from repro.sweep import (clear_program_cache, make_grid, program_cache_stats,
                         round_robin_pad, sharded_sweep_bcd,
                         sharded_sweep_fedasync, sharded_sweep_fedbuff,
                         sharded_sweep_piag, standard_topology_factories,
                         sweep_bcd_logreg, sweep_piag_logreg)
from repro.sweep.cache import IdKey, _key_fingerprints

N_DEV = len(jax.devices())
needs2 = pytest.mark.skipif(
    N_DEV < 2, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                      "device_count=N (CI multi-device lane)")
needs4 = pytest.mark.skipif(N_DEV < 4, reason="needs >= 4 forced devices")
needs8 = pytest.mark.skipif(N_DEV < 8, reason="needs >= 8 forced devices")


@pytest.fixture(scope="module")
def problem():
    # 32 samples per worker: divisible by every data-axis size used here
    return make_logreg(256, 40, n_workers=8, seed=0)


def _mesh_2d(data: int = 2):
    """(cells, data) mesh over all forced devices."""
    return grid_mesh((N_DEV // data, data))


def _grid(gp, n_events=120, widths=None):
    # ragged grids take width -> workers factories instead of worker lists
    topos = (standard_topology_factories() if widths is not None else
             {"uniform": [WorkerModel() for _ in range(8)],
              "hetero": heterogeneous_workers(8, seed=1)})
    kw = {} if widths is None else {"n_workers": list(widths)}
    return make_grid(
        policies={"a1": Adaptive1(gamma_prime=gp),
                  "fx": FixedStepSize(gamma_prime=gp, tau_bound=12)},
        seeds=[0, 1], topologies=topos, n_events=n_events, **kw)


def _assert_int_leaves_equal(a, b, fields):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


# ----------------------------------------------------- mesh construction ----

def test_grid_mesh_shapes_and_validation():
    m1 = grid_mesh((1,))
    assert tuple(m1.axis_names) == ("cells",)
    m2 = grid_mesh((1, 1))
    assert tuple(m2.axis_names) == ("cells", "data")
    assert cell_axis_size(m2) == 1 and data_axis_size(m2) == 1
    assert data_axis_size(m1) == 1
    with pytest.raises(ValueError, match="positive"):
        grid_mesh((0, 2))
    with pytest.raises(ValueError, match="mesh_shape"):
        grid_mesh((1, 2, 3))
    with pytest.raises(ValueError, match="devices"):
        grid_mesh((N_DEV + 1, 2))
    # a sweep mesh without a "cells" axis is rejected loudly
    from jax.sharding import Mesh
    with pytest.raises(ValueError, match="cells"):
        cell_axis_size(Mesh(np.array(jax.devices()[:1]), ("data",)))


def test_execution_spec_mesh_shape_validation():
    from repro.api import ExecutionSpec
    ex = ExecutionSpec(backend="sharded", mesh_shape=(1, 1))
    assert ex.mesh_shape == (1, 1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        ExecutionSpec(backend="sharded", mesh=cell_mesh(), mesh_shape=(1, 1))
    with pytest.raises(ValueError, match="sharded"):
        ExecutionSpec(backend="batched", mesh_shape=(1, 1))
    with pytest.raises(ValueError, match="positive"):
        ExecutionSpec(backend="sharded", mesh_shape=(1, 0))
    with pytest.raises(ValueError, match="process_id"):
        ExecutionSpec(backend="sharded", num_processes=2, process_id=2)
    with pytest.raises(ValueError, match="sharded"):
        ExecutionSpec(backend="batched", coordinator="localhost:1234")


# ------------------------------------------------- topology cache keying ----

def test_mesh_topology_distinct_1d_vs_2d():
    t1 = mesh_topology(cell_mesh(jax.devices()[:1]))
    t2 = mesh_topology(grid_mesh((1, 1)))
    assert t1 != t2  # same device, reshaped: must key fresh
    # same topology, distinct Mesh objects: must key equal
    assert mesh_topology(grid_mesh((1, 1))) == t2
    if N_DEV >= 8:
        tops = {mesh_topology(cell_mesh()),
                mesh_topology(grid_mesh((4, 2))),
                mesh_topology(grid_mesh((2, 4)))}
        assert len(tops) == 3


def test_key_fingerprints_meshes_by_topology():
    """Satellite: meshes inside cache keys fingerprint by (axis names,
    shape, device kind, process count) -- not value identity -- raw or
    IdKey-wrapped."""
    m_a = cell_mesh(jax.devices()[:1])
    m_b = cell_mesh(jax.devices()[:1])   # distinct object, same topology
    m_2d = grid_mesh((1, 1))
    fp = _key_fingerprints(("tag", m_a, IdKey(m_2d)))
    assert len(fp) == 2
    assert all("cells" in print_ for _, print_ in fp)
    assert _key_fingerprints(("tag", m_a)) == _key_fingerprints(("tag", m_b))
    assert _key_fingerprints(("tag", m_a)) != _key_fingerprints(("tag", m_2d))


def test_program_cache_keys_distinct_1d_vs_2d(problem):
    """A 1-D and a (reshaped) 2-D mesh over the same devices build distinct
    executables; a same-topology mesh rebuild reuses the cached one."""
    gp = 0.99 / problem.L
    prox = L1(lam=problem.lam1)
    grid = _grid(gp, n_events=30)
    # identity-keyed captures must be the SAME objects across calls (note
    # `problem.P` binds a fresh method object per access -- hoist it)
    loss = lambda x, A, b: problem.worker_loss(x, A, b)
    obj = problem.P
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    wd = problem.worker_slices()
    clear_program_cache()
    m1 = cell_mesh(jax.devices()[:1])
    sharded_sweep_piag(loss, x0, wd, grid, prox, objective=obj, mesh=m1)
    misses_1d = program_cache_stats()["misses"]
    sharded_sweep_piag(loss, x0, wd, grid, prox, objective=obj,
                       mesh=grid_mesh((1, 1)))
    stats = program_cache_stats()
    assert stats["misses"] == misses_1d + 1  # 2-D keys fresh
    sharded_sweep_piag(loss, x0, wd, grid, prox, objective=obj,
                       mesh=cell_mesh(jax.devices()[:1]))  # fresh Mesh object
    stats2 = program_cache_stats()
    assert stats2["misses"] == stats["misses"]  # same topology: cache hit
    assert stats2["hits"] > stats["hits"]


# ------------------------------------------------------ round-robin rule ----

def test_round_robin_pad_keys_on_cells_axis():
    """Padding keys on the cells axis ONLY: 3 cells on a (2, 4) mesh pad
    to 4 rows (2 shards x 2), not to a multiple of all 8 devices."""
    np.testing.assert_array_equal(round_robin_pad(3, 2), [0, 1, 2, 0])
    assert round_robin_pad(3, 2).shape == (4,)
    # a single cell-shard never pads, whatever the data axis
    np.testing.assert_array_equal(round_robin_pad(3, 1), [0, 1, 2])


@needs8
def test_round_robin_pad_2x4_mesh_regression(problem):
    """Regression (satellite): a 3-cell grid on a (2, 4) mesh on 8 forced
    host devices pads on the 2-wide cells axis and reproduces batched rows
    exactly."""
    mesh = grid_mesh((2, 4))
    assert cell_axis_size(mesh) == 2 and data_axis_size(mesh) == 4
    gp = 0.99 / problem.L
    prox = L1(lam=problem.lam1)
    grid = make_grid(
        policies={"a1": Adaptive1(gamma_prime=gp)},
        seeds=[0, 1, 2],
        topologies={"uniform": [WorkerModel() for _ in range(8)]},
        n_events=60)
    assert len(grid) == 3
    batched = sweep_piag_logreg(problem, grid, prox)
    sharded = sharded_sweep_piag(
        lambda x, A, b: problem.worker_loss(x, A, b),
        jnp.zeros((problem.dim,), jnp.float32), problem.worker_slices(),
        grid, prox, objective=problem.P, mesh=mesh)
    _assert_int_leaves_equal(batched, sharded, ("taus", "clipped"))
    np.testing.assert_array_equal(np.asarray(batched.gammas),
                                  np.asarray(sharded.gammas))
    np.testing.assert_allclose(np.asarray(batched.objective),
                               np.asarray(sharded.objective),
                               rtol=1e-6, atol=1e-7)


# --------------------------------------- solo vs 1-D vs 2-D: all solvers ----

@needs2
def test_piag_2d_rows_equal_1d_and_solo(problem):
    gp = 0.99 / problem.L
    prox = L1(lam=problem.lam1)
    grid = _grid(gp)
    loss = lambda x, A, b: problem.worker_loss(x, A, b)
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    wd = problem.worker_slices()
    one_d = sharded_sweep_piag(loss, x0, wd, grid, prox,
                               objective=problem.P, mesh=cell_mesh())
    two_d = sharded_sweep_piag(loss, x0, wd, grid, prox,
                               objective=problem.P, mesh=_mesh_2d(2))
    # 1-D vs 2-D: identical ParamPolicy arithmetic -> gammas bitwise too
    _assert_int_leaves_equal(one_d, two_d, ("taus", "clipped"))
    np.testing.assert_array_equal(np.asarray(one_d.gammas),
                                  np.asarray(two_d.gammas))
    np.testing.assert_allclose(np.asarray(one_d.objective),
                               np.asarray(two_d.objective),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(one_d.x), np.asarray(two_d.x),
                               rtol=1e-6, atol=1e-7)
    # 2-D vs solo (dataclass policy): taus exact, floats to the usual
    # cross-path envelope
    Aw, bw = wd
    for i in (0, len(grid) // 2, len(grid) - 1):
        cell = grid.cells[i]
        T = sample_service_times(cell.workers, grid.n_events + 1,
                                 seed=cell.seed)
        tr = trace_scan(jnp.asarray(T))
        solo = jax.jit(lambda ev: piag_scan(
            loss, x0, (Aw, bw), ev, cell.policy, prox,
            objective=problem.P))((tr.worker, tr.tau_max))
        np.testing.assert_array_equal(np.asarray(solo.taus),
                                      np.asarray(two_d.taus[i]))
        np.testing.assert_array_equal(np.asarray(solo.clipped),
                                      np.asarray(two_d.clipped[i]))
        np.testing.assert_allclose(np.asarray(solo.objective),
                                   np.asarray(two_d.objective[i]),
                                   rtol=1e-5, atol=1e-6)


@needs2
def test_piag_2d_ragged_and_chaos_rows_equal(problem):
    """Ragged bucket widths AND faults-on chaos: every integer output --
    taus, clipped, the FaultState counter tuple -- bitwise across 1-D vs
    2-D meshes."""
    gp = 0.99 / problem.L
    prox = L1(lam=problem.lam1)
    chaos = FaultSpec(p_crash=0.05, p_rejoin=0.3, crash_scale=20.0,
                      p_spike=0.1, spike_scale=10.0, p_drop=0.1,
                      p_dup=0.05, p_corrupt=0.05, seed=7)
    grid = _grid(gp, n_events=100, widths=(4, 8))
    loss = lambda x, A, b: problem.worker_loss(x, A, b)
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    wd = problem.worker_slices()
    one_d = sharded_sweep_piag(loss, x0, wd, grid, prox,
                               objective=problem.P, mesh=cell_mesh(),
                               faults=chaos)
    two_d = sharded_sweep_piag(loss, x0, wd, grid, prox,
                               objective=problem.P, mesh=_mesh_2d(2),
                               faults=chaos)
    _assert_int_leaves_equal(one_d, two_d, ("taus", "clipped"))
    np.testing.assert_array_equal(np.asarray(one_d.gammas),
                                  np.asarray(two_d.gammas))
    for la, lb in zip(jax.tree_util.tree_leaves(one_d.faults),
                      jax.tree_util.tree_leaves(two_d.faults)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_allclose(np.asarray(one_d.objective),
                               np.asarray(two_d.objective),
                               rtol=1e-6, atol=1e-7)


@needs2
def test_bcd_2d_rows_equal(problem):
    m = 8
    gp = 0.99 / problem.block_smoothness(m)
    prox = L1(lam=problem.lam1)
    grid = _grid(gp, n_events=80)
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    mesh2 = _mesh_2d(2)
    dp = pmean_grad(lambda x, A, b: problem.worker_loss(x, A, b),
                    DATA_AXIS, data_axis_size(mesh2))
    dp_grad_f = lambda x: dp(x, problem.A, problem.b)
    batched = sweep_bcd_logreg(problem, grid, prox, m=m)
    two_d = sharded_sweep_bcd(problem.grad_f, problem.P, x0, m, grid, prox,
                              mesh=mesh2, dp_grad_f=dp_grad_f)
    _assert_int_leaves_equal(batched, two_d, ("taus", "blocks", "clipped"))
    np.testing.assert_array_equal(np.asarray(batched.gammas),
                                  np.asarray(two_d.gammas))
    # dp grad is grad(worker_loss) vs the analytic grad_f: same math,
    # different float path -> objectives to the cross-path envelope
    np.testing.assert_allclose(np.asarray(batched.objective),
                               np.asarray(two_d.objective),
                               rtol=1e-4, atol=1e-5)


@needs2
def test_bcd_2d_without_dp_grad_warns_but_matches(problem):
    """A 2-D mesh with an opaque grad_f degrades to replicated compute:
    a RuntimeWarning fires and the rows are bitwise the 1-D mesh rows."""
    m = 8
    gp = 0.99 / problem.block_smoothness(m)
    prox = L1(lam=problem.lam1)
    grid = _grid(gp, n_events=60)
    x0 = jnp.zeros((problem.dim,), jnp.float32)
    one_d = sharded_sweep_bcd(problem.grad_f, problem.P, x0, m, grid, prox,
                              mesh=cell_mesh())
    with pytest.warns(RuntimeWarning, match="replicated"):
        two_d = sharded_sweep_bcd(problem.grad_f, problem.P, x0, m, grid,
                                  prox, mesh=_mesh_2d(2))
    _assert_int_leaves_equal(one_d, two_d, ("taus", "blocks", "clipped"))
    np.testing.assert_allclose(np.asarray(one_d.objective),
                               np.asarray(two_d.objective),
                               rtol=1e-6, atol=1e-7)


@needs2
def test_fed_2d_rows_equal(problem):
    prox = L1(lam=problem.lam1)
    lr = 0.5 / problem.L
    grid = make_grid(
        policies={"hinge": HingeWeight(gamma_prime=0.6)},
        seeds=[0, 1, 2],
        topologies={"edge": heterogeneous_clients(8, seed=5)},
        n_events=80)
    update, x0, data = _problem_pieces(problem, prox, lr)
    mesh2 = _mesh_2d(2)
    dp_update = local_prox_sgd(
        lambda x, A, b: problem.worker_loss(x, A, b), prox, lr,
        grad_fn=pmean_grad(lambda x, A, b: problem.worker_loss(x, A, b),
                           DATA_AXIS, data_axis_size(mesh2)))
    for solver, kw in (("fedasync", {}), ("fedbuff",
                                          dict(eta=0.4, buffer_size=2))):
        runner = (sharded_sweep_fedasync if solver == "fedasync"
                  else sharded_sweep_fedbuff)
        one_d = runner(update, x0, data, grid, objective=problem.P,
                       mesh=cell_mesh(), **kw)
        two_d = runner(dp_update, x0, data, grid, objective=problem.P,
                       mesh=mesh2, **kw)
        _assert_int_leaves_equal(one_d, two_d,
                                 ("taus", "versions", "clipped"))
        np.testing.assert_array_equal(np.asarray(one_d.weights),
                                      np.asarray(two_d.weights))
        np.testing.assert_allclose(np.asarray(one_d.objective),
                                   np.asarray(two_d.objective),
                                   rtol=1e-6, atol=1e-7, err_msg=solver)


@needs2
@pytest.mark.parametrize("solver", ["piag", "bcd", "fedasync", "fedbuff"])
def test_api_mesh_shape_routes_2d_for_all_solvers(problem, solver):
    """ExecutionSpec.mesh_shape end-to-end: the spec path builds the 2-D
    mesh, injects the data-parallel gradient (pmean_grad for PIAG, the
    worker_loss-derived dp grad for BCD, the dp client update for the
    federated servers), and reproduces the 1-D rows with bitwise integer
    leaves."""
    prox = L1(lam=problem.lam1)
    if solver == "bcd":
        gp = 0.99 / problem.block_smoothness(8)
    elif solver == "piag":
        gp = 0.99 / problem.L
    else:
        gp = 0.6
    if solver in ("fedasync", "fedbuff"):
        grid = make_grid(
            policies={"hinge": HingeWeight(gamma_prime=gp)},
            seeds=[0, 1],
            topologies={"edge": heterogeneous_clients(8, seed=5)},
            n_events=60)
    else:
        grid = _grid(gp, n_events=60)
    kw = {"m": 8} if solver == "bcd" else {}
    if solver == "fedbuff":
        kw = dict(eta=0.4, buffer_size=2)
    one_d = run_components(solver, "sharded", problem=problem, grid=grid,
                           prox=prox, mesh_shape=(N_DEV,), **kw)
    two_d = run_components(solver, "sharded", problem=problem, grid=grid,
                           prox=prox, mesh_shape=(N_DEV // 2, 2), **kw)
    ints = {"piag": ("taus", "clipped"), "bcd": ("taus", "blocks", "clipped"),
            "fedasync": ("taus", "versions", "clipped"),
            "fedbuff": ("taus", "versions", "clipped")}[solver]
    _assert_int_leaves_equal(one_d.raw, two_d.raw, ints)
    rtol, atol = (1e-4, 1e-5) if solver == "bcd" else (1e-6, 1e-7)
    np.testing.assert_allclose(np.asarray(one_d.raw.objective),
                               np.asarray(two_d.raw.objective),
                               rtol=rtol, atol=atol)


# ------------------------------------------------------------ guard rails ----

@needs4
def test_pmean_grad_rejects_indivisible_sample_axis():
    """30 samples per worker on a 4-wide data axis: loud trace-time error,
    never a silent sample drop."""
    prob = make_logreg(240, 20, n_workers=8, seed=0)  # 30 per worker
    prox = L1(lam=prob.lam1)
    grid = _grid(0.99 / prob.L, n_events=20)
    with pytest.raises(ValueError, match="divide"):
        sharded_sweep_piag(lambda x, A, b: prob.worker_loss(x, A, b),
                           jnp.zeros((prob.dim,), jnp.float32),
                           prob.worker_slices(), grid, prox,
                           objective=prob.P, mesh=grid_mesh((1, 4)))


def test_maybe_init_distributed_consumes_knobs_once(monkeypatch):
    """The multi-host knobs call jax.distributed.initialize exactly once
    per process and are otherwise inert (no coordinator -> no-op)."""
    calls = []
    monkeypatch.setattr(rmesh, "_DISTRIBUTED_INITIALIZED", False)
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda coordinator_address, num_processes, process_id:
            calls.append((coordinator_address, num_processes, process_id)))
    from repro.api import ExecutionSpec
    ex = ExecutionSpec(backend="sharded", coordinator="localhost:9876",
                       num_processes=1, process_id=0)
    assert rmesh.maybe_init_distributed(ex) is True
    assert calls == [("localhost:9876", 1, 0)]
    assert rmesh.maybe_init_distributed(ex) is True  # idempotent
    assert len(calls) == 1
    assert rmesh.maybe_init_distributed(
        ExecutionSpec(backend="sharded")) is False
    assert len(calls) == 1

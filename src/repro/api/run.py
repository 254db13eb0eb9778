"""Compile an ``ExperimentSpec`` down to the existing runners.

``resolve(spec)`` materializes the declarative axes -- problem, prox,
policies (with the paper's tau-bar tuning protocol for the fixed family),
topology factories, the ``SweepGrid`` -- and performs the build-time
horizon validation.  ``run(spec)`` then dispatches on
(solver, backend) to EXACTLY the code path that existed before the
redesign:

=========  ==========================  ===========================  =========================
solver     solo                        batched                      sharded
=========  ==========================  ===========================  =========================
piag       ``core.piag.run_piag``      ``sweep.sweep_piag``         ``shard.sharded_sweep_piag``
bcd        ``core.bcd.run_async_bcd``  ``sweep.sweep_bcd``          ``shard.sharded_sweep_bcd``
fedasync   ``federated.run_fedasync``  ``sweep.sweep_fedasync``     ``shard.sharded_sweep_fedasync``
fedbuff    ``federated.run_fedbuff``   ``sweep.sweep_fedbuff``      ``shard.sharded_sweep_fedbuff``
=========  ==========================  ===========================  =========================

The spec layer only routes -- argument-for-argument the calls match what
the legacy conveniences (``sweep_piag_logreg`` etc.) made, so spec-routed
rows are bitwise-identical to the runner they dispatch to
(``tests/test_api.py`` pins all twelve combinations).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import SweepCheckpoint
from repro.core.bcd import run_async_bcd, sample_blocks
from repro.core.engine import generate_trace, sample_service_times
from repro.core.piag import run_piag
from repro.faults.guards import summarize_faults
from repro.faults.inject import inject_service_times
from repro.core.problems import make_lasso, make_logreg
from repro.core.prox import make_prox
from repro.core.stepsize import make_policy
from repro.federated.events import (generate_federated_trace,
                                    heterogeneous_clients)
from repro.federated.server import (_problem_pieces, run_fedasync,
                                    run_fedbuff)
from repro.sweep.cache import LRU, IdKey, program_cache_stats
from repro.sweep.grid import (SweepGrid, make_grid, measure_tau_bar,
                              standard_topology_factories)
from repro.telemetry.accumulators import TelemetryConfig, summarize_telemetry
from repro.telemetry.ledger import (RunRecord, append_record, cache_delta,
                                    estimate_carry_bytes, spec_fingerprint)
from repro.telemetry.timing import COMPILE_EVENT_NAMES, drain_timings
from repro.sweep.runners import (resolve_grid_horizon, sweep_bcd,
                                 sweep_fedasync, sweep_fedbuff, sweep_piag)
from repro.mesh import (DATA_AXIS, data_axis_size, grid_mesh,
                        maybe_init_distributed, pmean_grad)
from repro.sweep.shard import (cell_mesh, sharded_sweep_bcd,
                               sharded_sweep_fedasync,
                               sharded_sweep_fedbuff, sharded_sweep_piag)

from .results import Results
from .spec import (FIXED_FAMILY, ExecutionSpec, ExperimentSpec, ProblemSpec,
                   SolverSpec, check_horizon)

__all__ = ["Resolved", "resolve", "run", "run_components", "component_spec"]

_tmap = jax.tree_util.tree_map

# resolve-time memoization: repeated api.run calls of value-equal specs
# reuse the SAME problem/prox/runner-piece objects, which is what lets the
# sweep-program cache (repro.sweep.cache) recognize the executables as
# identical instead of re-tracing per call
_PROBLEM_MEMO = LRU(16)
_PROX_MEMO = LRU(32)
_PIECES_MEMO = LRU(32)


class Resolved(NamedTuple):
    """The concrete objects a spec compiles to (pre-dispatch).

    ``horizon`` is the CONCRETE window-buffer size the dispatch uses: the
    spec's integer horizon verbatim, or -- for ``horizon='auto'`` -- the
    measured-delay sizing ``next_pow2(bound + slack)``."""

    spec: ExperimentSpec
    problem: Any
    prox: Any
    grid: SweepGrid
    tau_bar: Optional[int]
    horizon: int


# -------------------------------------------------------------- resolve ----

def _build_problem(spec: ExperimentSpec):
    ps = spec.problem
    if ps.problem is not None:
        return ps.problem
    maker = make_logreg if ps.kind == "logreg" else make_lasso
    kwargs = dict(ps.params)
    kwargs.setdefault("n_workers", spec.topology.width_max)
    try:
        key = (ps.kind, tuple(sorted(kwargs.items())))
        hash(key)
    except TypeError:  # exotic params: build fresh, skip memoization
        return maker(**kwargs)
    return _PROBLEM_MEMO.get(key, lambda: maker(**kwargs))


def _build_prox(spec: ExperimentSpec, problem):
    ps = spec.problem
    if ps.prox_op is not None:
        return ps.prox_op
    kwargs = dict(ps.prox_params)
    if ps.prox == "l1":
        kwargs.setdefault("lam", problem.lam1)
    try:
        key = (ps.prox, tuple(sorted(kwargs.items())))
        hash(key)
    except TypeError:
        return make_prox(ps.prox, **kwargs)
    return _PROX_MEMO.get(key, lambda: make_prox(ps.prox, **kwargs))


def _build_topologies(spec: ExperimentSpec) -> Dict[str, Any]:
    ts = spec.topology
    if ts.kind == "custom":
        topos = dict(ts.topologies)
    elif ts.kind == "edge":
        params = dict(ts.params)
        seed = params.pop("seed", ts.seed)  # params may pin its own seed
        topos = {"edge": lambda n, _p=params: heterogeneous_clients(
            n, seed=seed, **_p)}
    else:
        topos = standard_topology_factories(ts.seed)
    if ts.names is not None:
        unknown = set(ts.names) - set(topos)
        if unknown:
            raise ValueError(f"unknown topology names {sorted(unknown)}; "
                             f"available: {sorted(topos)}")
        topos = {n: topos[n] for n in ts.names}
    return topos


def _auto_gamma_prime(spec: ExperimentSpec, problem) -> float:
    if spec.solver.name == "piag":
        return 0.99 / problem.L
    if spec.solver.name == "bcd":
        return 0.99 / problem.block_smoothness(spec.solver.m)
    return 0.6  # federated base mixing weight alpha


def _measure_tau_bar(spec: ExperimentSpec, topos) -> int:
    """Worst-case trace delay over every (topology, width, seed) cell --
    the paper's protocol for tuning the fixed family, reused for horizon
    validation.  Worker traces only (federated staleness is not a
    service-time trace property)."""
    ts = spec.topology
    if ts.n_workers is not None:
        menu = {f"{tn}/w{int(w)}": f(int(w))
                for tn, f in topos.items() for w in ts.n_workers}
    else:
        menu = {tn: ws for tn, ws in topos.items()}
    return measure_tau_bar(menu, list(spec.policies.seeds), spec.n_events)


def _build_policies(spec: ExperimentSpec, problem, tau_bar: Optional[int]):
    pg = spec.policies
    if pg.policies is not None:
        return dict(pg.policies)
    gp = pg.gamma_prime if pg.gamma_prime is not None \
        else _auto_gamma_prime(spec, problem)
    out = {}
    for name in pg.names:
        kwargs = dict(pg.policy_kwargs.get(name, {}))
        if name in FIXED_FAMILY and "tau_bound" not in kwargs:
            bound = pg.tau_bound if pg.tau_bound is not None else tau_bar
            if bound is None:
                raise ValueError(
                    f"policy {name!r} needs a worst-case delay bound: set "
                    "PolicyGridSpec.tau_bound or enable DelaySpec.measure")
            kwargs["tau_bound"] = int(bound)
        out[name] = make_policy(name, gp, **kwargs)
    return out


def _validate_horizon(spec: ExperimentSpec, tau_bar: Optional[int]) -> None:
    exp = spec.delay.expected_max_delay
    check_horizon(spec.solver.horizon, tau_bar if exp is None else exp)


def _resolve_horizon(spec: ExperimentSpec, grid: SweepGrid,
                     tau_bar: Optional[int]) -> int:
    """The concrete window-buffer size for the dispatch.

    A thin adapter over the one shared rule
    (``sweep.runners.resolve_grid_horizon``): integer horizons pass through
    verbatim, ``'auto'`` sizes from the declared ``expected_max_delay`` or
    the already-measured worker tau-bar when available (a fresh
    measurement otherwise), with the spec's ``DelaySpec.horizon_slack``."""
    sv = spec.solver
    bound = spec.delay.expected_max_delay
    if bound is None and not sv.federated:
        bound = tau_bar  # reuse the fixed-family/validation measurement
    return resolve_grid_horizon(
        sv.horizon, grid, fed=sv.federated,
        buffer_size=sv.buffer_size if sv.name == "fedbuff" else 1,
        n_steps=sv.n_steps, slack=spec.delay.horizon_slack, bound=bound)


def resolve(spec: ExperimentSpec) -> Resolved:
    """Materialize problem, prox, policies and grid; validate the horizon.

    Fixed-family policies without an explicit ``tau_bound`` trigger a
    tau-bar measurement over the grid's own traces; so do horizon
    validation for PIAG/BCD when no ``expected_max_delay`` is declared and
    ``horizon='auto'`` sizing (one measurement serves all three).
    """
    problem = _build_problem(spec)
    prox = _build_prox(spec, problem)

    if spec.grid is not None:
        tau_bar = None
        if spec.validate_horizon:
            _validate_horizon(spec, tau_bar)
        horizon = _resolve_horizon(spec, spec.grid, tau_bar)
        return Resolved(spec, problem, prox, spec.grid, tau_bar, horizon)

    topos = _build_topologies(spec)
    pg = spec.policies
    needs_bound = (pg.policies is None and pg.tau_bound is None
                   and any(n in FIXED_FAMILY for n in pg.names))
    worker_solver = not spec.solver.federated
    auto = spec.solver.horizon == "auto"
    needs_measure = worker_solver and (
        (needs_bound and spec.delay.measure)
        or (spec.validate_horizon and spec.delay.measure
            and spec.delay.expected_max_delay is None)
        or (auto and spec.delay.expected_max_delay is None))
    tau_bar = _measure_tau_bar(spec, topos) if needs_measure else None
    if spec.solver.federated:
        tau_bar = 0  # fixed baselines are not the federated story
    elif needs_bound and tau_bar is None:
        raise ValueError(
            "fixed-family policies need tau_bound (or DelaySpec.measure)")

    policies = _build_policies(spec, problem, tau_bar)
    grid = make_grid(policies, list(pg.seeds), topos, spec.n_events,
                     n_workers=(list(spec.topology.n_workers)
                                if spec.topology.n_workers is not None
                                else None))
    if spec.validate_horizon and worker_solver:
        _validate_horizon(spec, tau_bar)
    elif spec.validate_horizon:
        _validate_horizon(spec, None)  # declared bound only
    horizon = _resolve_horizon(
        spec, grid, tau_bar if worker_solver else None)
    return Resolved(spec, problem, prox, grid, tau_bar, horizon)


# ------------------------------------------------------------- dispatch ----

def _slice_rows(tree, n: int):
    return _tmap(lambda leaf: leaf[:n], tree)


def _stack_results(rows):
    return _tmap(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *rows)


def _mesh_for(spec: ExperimentSpec):
    ex = spec.execution
    # multi-host bootstrap must precede the first jax.devices() call so the
    # mesh spans every process; no-op unless ex.coordinator is set
    maybe_init_distributed(ex)
    if ex.mesh is not None:
        return ex.mesh
    devices = None
    if ex.devices is not None:
        devices = jax.devices()
        if len(devices) < int(ex.devices):
            raise ValueError(
                f"ExecutionSpec.devices={ex.devices} but only "
                f"{len(devices)} {devices[0].platform} device(s) exist; a "
                "smaller mesh would silently serialize the cells axis")
        devices = devices[:int(ex.devices)]
    if ex.mesh_shape is not None:
        return grid_mesh(ex.mesh_shape, devices)
    return cell_mesh(devices)


def _piag_pieces(r: Resolved):
    """(loss, x0, worker_data, objective) for PIAG, memoized per problem so
    repeated runs hand the sweep-program cache identical captured objects."""
    problem = r.problem

    def build():
        Aw, bw = problem.worker_slices()
        x0 = jnp.zeros((problem.dim,), jnp.float32)
        loss = lambda x, A, b: problem.worker_loss(x, A, b)
        return loss, x0, (Aw, bw), problem.P

    return _PIECES_MEMO.get(("piag", IdKey(problem)), build)


def _bcd_pieces(problem):
    def build():
        return (problem.grad_f, problem.P,
                jnp.zeros((problem.dim,), jnp.float32))

    return _PIECES_MEMO.get(("bcd", IdKey(problem)), build)


def _bcd_dp_grad(problem, size: int):
    """Data-parallel full gradient for sharded BCD on a (cells, data) mesh.

    BCD's ``grad_f`` is an opaque closure, so the data-parallel variant is
    rebuilt from ``problem.worker_loss`` on the problem's FULL data (for
    both built-in problem classes ``worker_loss(x, A_full, b_full) == f(x)``
    exactly) with ``pmean_grad`` psumming partial gradients over "data".
    Returns None -- replicated-compute fallback, the sharded runner warns --
    for custom problems without ``worker_loss`` + ``A``/``b``(``y``)."""
    def build():
        A = getattr(problem, "A", None)
        b = getattr(problem, "b", getattr(problem, "y", None))
        if A is None or b is None or not hasattr(problem, "worker_loss"):
            return None
        g = pmean_grad(lambda x, A_, b_: problem.worker_loss(x, A_, b_),
                       DATA_AXIS, size)
        return lambda x: g(x, A, b)

    return _PIECES_MEMO.get(("bcd/dp", IdKey(problem), size), build)


def _fed_pieces(problem, prox, local_lr, dp_size: int = 1):
    def build():
        grad_fn = None
        if dp_size > 1:
            # 2-D mesh: client gradients psum over the mesh's data axis
            grad_fn = pmean_grad(
                lambda x, A, b: problem.worker_loss(x, A, b),
                DATA_AXIS, dp_size)
        update, x0, data = _problem_pieces(problem, prox, local_lr,
                                           grad_fn=grad_fn)
        return update, x0, data, problem.P

    return _PIECES_MEMO.get(("fed", IdKey(problem), IdKey(prox), local_lr,
                             dp_size), build)


def _telemetry_cfg(spec: ExperimentSpec) -> Optional[TelemetryConfig]:
    """The scan-carry accumulator config: None (exactly the pre-telemetry
    code path) unless the spec opted in."""
    ex = spec.execution
    return TelemetryConfig(delay_bins=ex.telemetry_bins) \
        if ex.telemetry else None


# solo fault injection: the same jitted service-time transform the batched
# cells run, applied host-side before generate_trace -- threefry bits are a
# pure function of (fault seed, cell seed), so the injected matrix (and hence
# the trace and every downstream row) is bitwise the batched cell's
_INJECT_JIT = LRU(16)


def _inject_T(T, faults, cell_seed: int):
    fn = _INJECT_JIT.get(faults, lambda: jax.jit(
        lambda t, s: inject_service_times(t, faults, s)))
    return np.asarray(fn(jnp.asarray(T, jnp.float32), jnp.int32(cell_seed)))


def _solo_cells(grid, ckpt, run_cell):
    """The solo per-cell loop with optional per-cell checkpointing (cell
    files keyed on (width=n_workers, idx=cell index) through the same
    ``SweepCheckpoint`` the bucketed runners use)."""
    rows = []
    for i, c in enumerate(grid.cells):
        if ckpt is not None:
            cached = ckpt.load_bucket(c.n_workers, i)
            if cached is not None:
                rows.append(cached)
                continue
        row = run_cell(i, c)
        if ckpt is not None:
            row = jax.block_until_ready(row)
            ckpt.save_bucket(c.n_workers, i, row)
        rows.append(row)
    return _stack_results(rows)


def _run_piag(r: Resolved, ckpt=None):
    spec = r.spec
    loss, x0, wd, objective = _piag_pieces(r)
    h, utm = r.horizon, spec.delay.use_tau_max
    bw = spec.execution.bucket_widths
    s = spec.execution.record_every
    tel = _telemetry_cfg(spec)
    eng = spec.execution.engine
    fl = spec.faults
    backend = spec.execution.backend
    if backend == "batched":
        return sweep_piag(loss, x0, wd, r.grid, r.prox,
                          objective=objective, horizon=h, use_tau_max=utm,
                          bucket_widths=bw, record_every=s, telemetry=tel,
                          engine=eng, faults=fl, checkpoint=ckpt)
    if backend == "sharded":
        return sharded_sweep_piag(loss, x0, wd, r.grid, r.prox,
                                  objective=objective, horizon=h,
                                  use_tau_max=utm, mesh=_mesh_for(spec),
                                  bucket_widths=bw, record_every=s,
                                  telemetry=tel, engine=eng, faults=fl,
                                  checkpoint=ckpt)

    def run_cell(i, c):
        T = sample_service_times(c.workers, r.grid.n_events + 1, seed=c.seed)
        if fl is not None:
            T = _inject_T(T, fl, c.seed)
        tr = generate_trace(T)
        return run_piag(loss, x0, _slice_rows(wd, c.n_workers), tr,
                        c.policy, r.prox, objective=objective,
                        horizon=h, use_tau_max=utm, record_every=s,
                        telemetry=tel, engine=eng, faults=fl,
                        fault_seed=c.seed)

    return _solo_cells(r.grid, ckpt, run_cell)


def _run_bcd(r: Resolved, ckpt=None):
    spec = r.spec
    problem, m, h = r.problem, spec.solver.m, r.horizon
    grad_f, objective, x0 = _bcd_pieces(problem)
    bw = spec.execution.bucket_widths
    s = spec.execution.record_every
    tel = _telemetry_cfg(spec)
    eng = spec.execution.engine
    fl = spec.faults
    backend = spec.execution.backend
    if backend == "batched":
        return sweep_bcd(grad_f, objective, x0, m, r.grid, r.prox,
                         horizon=h, bucket_widths=bw, record_every=s,
                         telemetry=tel, engine=eng, faults=fl,
                         checkpoint=ckpt)
    if backend == "sharded":
        mesh = _mesh_for(spec)
        dp_grad_f = (_bcd_dp_grad(problem, data_axis_size(mesh))
                     if data_axis_size(mesh) > 1 else None)
        return sharded_sweep_bcd(grad_f, objective, x0, m, r.grid,
                                 r.prox, horizon=h, mesh=mesh,
                                 bucket_widths=bw, record_every=s,
                                 telemetry=tel, engine=eng, faults=fl,
                                 checkpoint=ckpt, dp_grad_f=dp_grad_f)

    def run_cell(i, c):
        T = sample_service_times(c.workers, r.grid.n_events + 1, seed=c.seed)
        if fl is not None:
            T = _inject_T(T, fl, c.seed)
        tr = generate_trace(T, kind="shared_memory")
        blocks = sample_blocks(m, r.grid.n_events, seed=c.seed)
        return run_async_bcd(grad_f, objective, x0, m, tr,
                             blocks, c.policy, r.prox, horizon=h,
                             record_every=s, telemetry=tel, engine=eng,
                             faults=fl, fault_seed=c.seed)

    return _solo_cells(r.grid, ckpt, run_cell)


def _run_fed(r: Resolved, ckpt=None):
    spec = r.spec
    sv = spec.solver
    backend = spec.execution.backend
    mesh = _mesh_for(spec) if backend == "sharded" else None
    dpn = data_axis_size(mesh) if mesh is not None else 1
    update, x0, data, objective = _fed_pieces(r.problem, r.prox, sv.local_lr,
                                              dp_size=dpn)
    h, n_steps = r.horizon, sv.n_steps
    bs = sv.buffer_size if sv.name == "fedbuff" else 1
    bw = spec.execution.bucket_widths
    s = spec.execution.record_every
    tel = _telemetry_cfg(spec)
    eng = spec.execution.engine
    fl = spec.faults
    if backend == "batched":
        if sv.name == "fedasync":
            return sweep_fedasync(update, x0, data, r.grid,
                                  objective=objective, horizon=h,
                                  reference=spec.execution.reference,
                                  n_steps=n_steps, bucket_widths=bw,
                                  record_every=s, telemetry=tel, engine=eng,
                                  faults=fl, checkpoint=ckpt)
        return sweep_fedbuff(update, x0, data, r.grid, eta=sv.eta,
                             buffer_size=bs, objective=objective,
                             horizon=h, reference=spec.execution.reference,
                             n_steps=n_steps, bucket_widths=bw,
                             record_every=s, telemetry=tel, engine=eng,
                             faults=fl, checkpoint=ckpt)
    if backend == "sharded":
        if sv.name == "fedasync":
            return sharded_sweep_fedasync(update, x0, data, r.grid,
                                          objective=objective,
                                          buffer_size=1, horizon=h,
                                          n_steps=n_steps, mesh=mesh,
                                          bucket_widths=bw, record_every=s,
                                          telemetry=tel, engine=eng,
                                          faults=fl, checkpoint=ckpt)
        return sharded_sweep_fedbuff(update, x0, data, r.grid, eta=sv.eta,
                                     buffer_size=bs, objective=objective,
                                     horizon=h, n_steps=n_steps, mesh=mesh,
                                     bucket_widths=bw, record_every=s,
                                     telemetry=tel, engine=eng, faults=fl,
                                     checkpoint=ckpt)

    def run_cell(i, c):
        tr = generate_federated_trace(c.n_workers, r.grid.n_events,
                                      clients=list(c.workers),
                                      buffer_size=bs, seed=c.seed,
                                      n_steps=n_steps, faults=fl)
        cd = _slice_rows(data, c.n_workers)
        if sv.name == "fedasync":
            return run_fedasync(update, x0, cd, tr, c.policy,
                                objective=objective, horizon=h,
                                record_every=s, telemetry=tel,
                                engine=eng, faults=fl, fault_seed=c.seed)
        return run_fedbuff(update, x0, cd, tr, c.policy, eta=sv.eta,
                           buffer_size=bs, objective=objective,
                           horizon=h, record_every=s,
                           telemetry=tel, engine=eng, faults=fl,
                           fault_seed=c.seed)

    return _solo_cells(r.grid, ckpt, run_cell)


_SOLVER_DISPATCH: Dict[str, Callable[..., Any]] = {
    "piag": _run_piag,
    "bcd": _run_bcd,
    "fedasync": _run_fed,
    "fedbuff": _run_fed,
}


def _build_record(spec: ExperimentSpec, r: Resolved, raw: Any,
                  elapsed: float, cache: Dict[str, Any],
                  timings) -> RunRecord:
    """Fold one dispatched run into the ledger's ``RunRecord`` shape.

    Host-side bookkeeping only: everything read off ``raw`` is already on
    the host after ``block_until_ready``; nothing here re-enters jit."""
    from repro import analysis

    grid, bins = r.grid, spec.execution.telemetry_bins
    tel = getattr(raw, "telemetry", None)
    if tel is not None:
        summ = summarize_telemetry(tel)
        delay_hist, hist_source = summ["hist"], "accumulator"
        tau_stats, gamma_stats = summ["tau"], summ["gamma"]
    else:
        taus = np.asarray(raw.taus).reshape(-1)
        gam = np.asarray(raw.weights if "weights" in raw._fields
                         else raw.gammas, np.float64).reshape(-1)
        delay_hist = np.bincount(np.clip(taus, 0, bins - 1),
                                 minlength=bins).astype(np.int64).tolist()
        hist_source = "recorded"
        tau_stats = {"min": int(taus.min()), "max": int(taus.max()),
                     "mean": float(taus.mean()), "std": float(taus.std())}
        gamma_stats = {"min": float(gam.min()), "max": float(gam.max()),
                       "mean": float(gam.mean()), "std": float(gam.std())}

    if spec.execution.backend == "sharded":
        mesh = _mesh_for(spec)
        devices, mesh_shape = int(mesh.devices.size), \
            [int(d) for d in mesh.devices.shape]
    else:
        devices, mesh_shape = 1, None

    compile_ms = sum(ev["ms"] for ev in timings
                     if ev["name"] in COMPILE_EVENT_NAMES)
    width = max(c.n_workers for c in grid.cells)
    return RunRecord(
        ts=time.time(),
        fingerprint=spec_fingerprint(spec, grid),
        solver=spec.solver.name,
        backend=spec.execution.backend,
        n_cells=len(grid.cells),
        n_events=int(grid.n_events),
        record_every=int(spec.execution.record_every),
        horizon=int(r.horizon),
        tau_bar=None if r.tau_bar is None else int(r.tau_bar),
        devices=devices,
        mesh_shape=mesh_shape,
        carry_bytes=estimate_carry_bytes(spec.solver.name,
                                         int(getattr(r.problem, "dim", 0)),
                                         width, r.horizon, len(grid.cells)),
        elapsed_ms=elapsed * 1e3,
        compile_ms=float(compile_ms),
        warm_ms=max(elapsed * 1e3 - compile_ms, 0.0),
        cache=cache,
        delay_hist=list(delay_hist),
        hist_source=hist_source,
        tau_stats=tau_stats,
        gamma_stats=gamma_stats,
        clipped=analysis.clipped_summary(raw.clipped),
        policies=sorted({c.policy_name for c in grid.cells}),
        timings=list(timings),
        faults=summarize_faults(getattr(raw, "faults", None)) or None,
    )


def run(spec: ExperimentSpec, resume=None) -> Results:
    """The single entry point: resolve the spec, dispatch to the runner for
    (solver, backend), return the unified ``Results`` table.

    Every run also builds a ``repro.telemetry.RunRecord`` (surfaced on
    ``Results.telemetry``; appended to the JSONL ledger when one is
    configured): the timing buffer is drained around the dispatch so
    compile-side events attribute to THIS run, and the program-cache
    counters are snapshotted for a reset-scoped hit/miss delta.

    ``resume`` names a checkpoint directory: buckets (batched/sharded) or
    cells (solo) finished by an earlier -- possibly killed -- run of the
    SAME spec are loaded from disk instead of recomputed, and fresh ones
    are persisted there as they complete.  Files are fingerprint-stamped;
    resuming a different spec into the same directory raises."""
    r = resolve(spec)
    ckpt = None
    if resume is not None:
        ckpt = SweepCheckpoint(
            resume, spec_fingerprint(spec, r.grid),
            tag=f"{spec.solver.name}_{spec.execution.backend}")
    drain_timings()  # drop events from unrelated earlier activity
    cache_before = program_cache_stats()
    t0 = time.perf_counter()
    raw = jax.block_until_ready(_SOLVER_DISPATCH[spec.solver.name](r, ckpt))
    elapsed = time.perf_counter() - t0
    record = _build_record(
        spec, r, raw, elapsed,
        cache_delta(cache_before, program_cache_stats()), drain_timings())
    append_record(record)
    return Results(solver=spec.solver.name, backend=spec.execution.backend,
                   grid=r.grid, raw=raw, elapsed_s=elapsed,
                   tau_bar=r.tau_bar, spec=spec, horizon=r.horizon,
                   record_every=spec.execution.record_every,
                   telemetry=record, cache_stats=record.cache)


# -------------------------------------------------- component escape ----

def component_spec(solver: str, backend: str, *, problem, grid, prox,
                   mesh=None, mesh_shape=None, reference: bool = False,
                   record_every: int = 1, telemetry: bool = False,
                   telemetry_bins: int = 64, engine: str = "scan",
                   faults=None, **solver_kwargs) -> ExperimentSpec:
    """A spec from prebuilt components (problem + grid + prox), bypassing
    the declarative build.  This is the form the legacy shims use; horizon
    validation and tau-bar measurement are off so shim behavior matches the
    pre-redesign runners exactly (including deliberate tiny-horizon runs).
    """
    from .spec import DelaySpec
    return ExperimentSpec(
        problem=ProblemSpec(kind="custom", problem=problem, prox_op=prox),
        solver=SolverSpec(name=solver, **solver_kwargs),
        execution=ExecutionSpec(backend=backend, mesh=mesh,
                                mesh_shape=mesh_shape,
                                reference=reference,
                                record_every=record_every,
                                telemetry=telemetry,
                                telemetry_bins=telemetry_bins,
                                engine=engine),
        delay=DelaySpec(measure=False),
        n_events=grid.n_events,
        grid=grid,
        validate_horizon=False,
        faults=faults,
    )


def run_components(solver: str, backend: str, *, problem, grid, prox,
                   mesh=None, mesh_shape=None, reference: bool = False,
                   record_every: int = 1, telemetry: bool = False,
                   telemetry_bins: int = 64, engine: str = "scan",
                   faults=None, resume=None, **solver_kwargs) -> Results:
    """``run`` over prebuilt components (see ``component_spec``)."""
    return run(component_spec(solver, backend, problem=problem, grid=grid,
                              prox=prox, mesh=mesh, mesh_shape=mesh_shape,
                              reference=reference,
                              record_every=record_every, telemetry=telemetry,
                              telemetry_bins=telemetry_bins, engine=engine,
                              faults=faults, **solver_kwargs),
               resume=resume)

"""Batched sweep runners: whole policy x seed x topology (x worker-count)
grids as ONE program per bucket.

Each ``make_sweep_*`` builder returns a single jitted function mapping the
grid's stacked inputs -- a (B, width, K+1) service-time tensor and (B,)
``PolicyParams`` -- to a batched result.  Inside, ``jax.vmap`` composes the
jitted trace generator (``core.engine.trace_scan`` for PIAG/BCD,
``federated.events.federated_trace_scan`` for FedAsync/FedBuff) with the
corresponding solver scan (``core.piag.piag_scan`` / ``core.bcd.bcd_scan`` /
``federated.server.fedasync_scan`` / ``fedbuff_scan``), so trace generation
AND optimization for every cell run in one XLA executable with one compile.

Row semantics: cell ``i`` of a sweep is the SAME computation as a solo run
of that cell's config (same trace bitwise, same step code via the shared
scan cores, same policy arithmetic via ``ParamPolicy``); only XLA's batching
of the gradient linear algebra can differ, at the last-ulp level.
``sweep_*`` convenience wrappers build + call in one shot; keep the builder
when you need to amortize the compile across repeated calls (benchmarks).

Ragged grids (mixed worker counts) dispatch per ``SweepGrid.buckets()``:
each bucket pads cells to a common width, runs the ``masked=True`` builder
(trace + PIAG aggregation take the ``active_workers`` mask so padded rows
never win the event race or contribute gradients), and rows are stitched
back into grid order.  A homogeneous grid is one exact-width bucket running
the unmasked builder -- the PR 2 program, unchanged.  ``repro.sweep.shard``
wraps the same vmapped cell functions in ``shard_map`` to spread the cell
axis across devices.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bcd import BCDResult, bcd_scan, sample_blocks
from repro.core.engine import trace_scan
from repro.core.piag import PIAGResult, piag_scan
from repro.core.prox import ProxOp
from repro.core.stepsize import auto_horizon
from repro.federated.events import (ClientRounds, client_arrays,
                                    default_fed_steps, federated_trace_scan,
                                    sample_client_rounds, simulate_federated)
from repro.federated.server import (FedResult, fedasync_scan, fedbuff_scan)
from repro.faults.spec import normalize_faults
from repro.faults.inject import (inject_client_rounds, inject_service_times,
                                 update_fault_codes)

from repro.telemetry.timing import run_number, timed

from .cache import IdKey, LRU, cached_program, tree_key
from .grid import SweepBucket, SweepGrid
from .policies import ParamPolicy

__all__ = ["make_sweep_piag", "sweep_piag", "sweep_piag_logreg",
           "make_sweep_bcd", "sweep_bcd", "sweep_bcd_logreg",
           "make_sweep_fedasync", "sweep_fedasync", "sweep_fedasync_problem",
           "make_sweep_fedbuff", "sweep_fedbuff", "sweep_fedbuff_problem",
           "run_bucketed", "resolve_grid_horizon", "measure_fed_tau_bar",
           "pick_grad_layout"]

Horizon = Union[int, str]  # a concrete H or "auto" (measured-delay sizing)


# ------------------------------------------------------------- plumbing ----

# grids are frozen dataclasses and their traces are pure functions of the
# pre-sampled randomness, so the measured bound is memoized per grid --
# repeated 'auto' sweeps skip the O(B*K) re-measurement, like the programs
_TAU_BAR_MEMO = LRU(64)


def _donate_default() -> bool:
    """Donation of the stacked input tensors is a real memory win on
    accelerators but a no-op plus a per-compile warning on the CPU backend
    -- gate it (evaluated at build time, after any forced-device flags)."""
    return jax.default_backend() != "cpu"


def resolve_grid_horizon(horizon: Horizon, grid: SweepGrid, *,
                         fed: bool = False, buffer_size: int = 1,
                         n_steps: Optional[int] = None,
                         slack: int = 1,
                         bound: Optional[int] = None) -> int:
    """THE one home of the ``horizon='auto'|int`` -> concrete-H rule
    (shared by every runner here, ``.shard``, and ``api.run``'s resolver,
    which passes its declared/already-measured ``bound`` and spec slack).

    ``'auto'`` measures the grid's own worst-case delay (service-time trace
    delays for PIAG/BCD, upload staleness for the federated servers;
    memoized per grid) and sizes the circular window buffer to
    ``next_pow2(bound + slack)`` -- bitwise-identical results to any larger
    horizon, at a fraction of the scan carry (``core.stepsize.auto_horizon``).
    """
    if horizon != "auto":
        return int(horizon)
    if bound is None:
        key = (IdKey(grid), fed, buffer_size if fed else 0,
               n_steps if fed else None)
        bound = _TAU_BAR_MEMO.get(
            key,
            lambda: (measure_fed_tau_bar(grid, buffer_size=buffer_size,
                                         n_steps=n_steps)
                     if fed else grid.measure_tau_bar()))
    return auto_horizon(bound, slack)


def _warn_legacy(name: str) -> None:
    """The problem-level conveniences are shims over ``repro.api`` now; the
    spec API is the documented entry point.  Rows stay bitwise-equal (the
    shim routes to the exact same runner), only the surface is deprecated."""
    warnings.warn(
        f"repro.sweep.{name} is deprecated; build an "
        "api.ExperimentSpec (or api.component_spec) and call repro.api.run "
        "instead", DeprecationWarning, stacklevel=3)


def run_bucketed(grid: SweepGrid, run_bucket: Callable,
                 bucket_widths: Optional[Sequence[int]] = None,
                 checkpoint=None, span_meta: Optional[Callable] = None):
    """Run ``run_bucket(bucket) -> result (leading B_bucket)`` over every
    bucket of ``grid`` and stitch rows back into grid cell order.  Shared by
    the single-device runners here and the sharded runners in ``.shard``.

    ``checkpoint`` (a ``repro.checkpoint.SweepCheckpoint``) makes the loop
    resumable at bucket granularity: a bucket already on disk is loaded
    instead of run, and each freshly-computed bucket is persisted (with a
    device sync first -- a checkpoint must never record an enqueued-but-
    unfinished computation) before the next one starts, so a killed
    mega-grid sweep resumes at its first unfinished bucket.
    ``span_meta(bucket) -> dict`` adds the runner's own fields to each
    bucket's ``bucket_dispatch`` span."""
    buckets = grid.buckets(bucket_widths)
    parts = []
    for i, b in enumerate(buckets):
        if checkpoint is not None:
            cached = checkpoint.load_bucket(b.width, i)
            if cached is not None:
                parts.append(cached)
                continue
        # telemetry: per-bucket dispatch wall time (build + trace + enqueue;
        # execution may still be async -- api.run's block covers that)
        meta = {} if span_meta is None else span_meta(b)
        with timed("bucket_dispatch", width=b.width, cells=len(b.index),
                   **meta):
            part = run_bucket(b)
        if checkpoint is not None:
            part = jax.block_until_ready(part)
            checkpoint.save_bucket(b.width, i, part)
        parts.append(part)
    if len(parts) == 1:
        return parts[0]
    order = np.concatenate([b.index for b in buckets])
    inv = np.argsort(order)
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0)[inv], *parts)


def _slice_workers(worker_data, width: int):
    """Rows 0..width-1 of every leaf: the bucket's view of the shared
    worker population (ragged cells use a prefix of it -- participation
    semantics, see ``sweep.grid``)."""
    leaves = jax.tree_util.tree_leaves(worker_data)
    if leaves and leaves[0].shape[0] < width:
        raise ValueError(
            f"worker_data has {leaves[0].shape[0]} rows < bucket width "
            f"{width}; provide data for the widest cell")
    return jax.tree_util.tree_map(lambda leaf: leaf[:width], worker_data)


# ---------------------------------------------------------------- PIAG ----

def _service_times(b: SweepBucket) -> jnp.ndarray:
    """The bucket's (B, width, K+1) service times, drawn on the host."""
    with timed("sweep.service_times", run=run_number(), cells=len(b.index)):
        return jnp.asarray(b.grid.service_times(b.width))


def _cell_seeds(b: SweepBucket) -> jnp.ndarray:
    """(B,) per-cell seeds -- the traced argument keying the fault streams
    (fold_in inside the jit, so solo/batched/sharded rows stay bitwise)."""
    return jnp.asarray([c.seed for c in b.grid.cells], jnp.int32)


def pick_grad_layout(n_cells: int, width: int,
                     grad_fn: Optional[Callable] = None) -> str:
    """The worker-gradient layout (``core.piag.piag_scan``'s
    ``grad_layout``) of a batched PIAG program whose one device runs
    ``n_cells`` cells of ``width`` workers each.

    Per event, gathered reads and writes a copy of one shard per cell and
    reads the copy twice (4 shard passes a cell); grouped reads all
    ``width`` shards twice.  Their bytes meet at half the width, where the
    chip still measured gathered ahead; so grouped is taken above half the
    width.  An injected ``grad_fn`` (the 2-D mesh's ``pmean_grad``) keeps
    gathered: its psum is written for one worker's slice."""
    if grad_fn is None and 2 * n_cells > width:
        return "grouped"
    return "gathered"


def _piag_cell(worker_loss, x0, worker_data, prox, objective, horizon,
               use_tau_max, masked, record_every=1, telemetry=None,
               engine="scan", faults=None, grad_fn=None,
               grad_layout="gathered"):
    """The per-cell program (trace generation fused with the solver scan);
    ``jax.vmap`` of this is the batched program, ``shard_map(vmap(...))``
    the sharded one.  With ``faults`` the cell signature grows a trailing
    per-cell ``seed`` (i32 scalar): service times are fault-injected before
    the trace scan and the per-event codes drawn from the same seed, all
    inside the one executable.  ``grad_fn`` is the 2-D mesh seam: the
    sharded runner injects ``pmean_grad`` so worker gradients psum over the
    mesh's data axis (None everywhere else -- off-is-absent).
    ``grad_layout`` is ``piag_scan``'s (see ``pick_grad_layout``)."""
    if faults is not None:
        def faulted(T, active, pp, seed):
            T = inject_service_times(T, faults, seed)
            tr = trace_scan(T, active=active) if active is not None \
                else trace_scan(T)
            events = (tr.worker, tr.tau_max if use_tau_max else tr.tau)
            codes = update_fault_codes(faults, events[0].shape[0], seed)
            return piag_scan(worker_loss, x0, worker_data, events,
                             ParamPolicy(pp), prox, objective=objective,
                             horizon=horizon, active=active,
                             record_every=record_every, telemetry=telemetry,
                             engine=engine, faults=faults, fault_codes=codes,
                             grad_fn=grad_fn, grad_layout=grad_layout)
        if masked:
            return lambda T, active, pp, seed: faulted(T, active, pp, seed)
        return lambda T, pp, seed: faulted(T, None, pp, seed)
    if masked:
        def cell(T, active, pp):
            tr = trace_scan(T, active=active)
            events = (tr.worker, tr.tau_max if use_tau_max else tr.tau)
            return piag_scan(worker_loss, x0, worker_data, events,
                             ParamPolicy(pp), prox, objective=objective,
                             horizon=horizon, active=active,
                             record_every=record_every, telemetry=telemetry,
                             engine=engine, grad_fn=grad_fn,
                             grad_layout=grad_layout)
    else:
        def cell(T, pp):
            tr = trace_scan(T)
            events = (tr.worker, tr.tau_max if use_tau_max else tr.tau)
            return piag_scan(worker_loss, x0, worker_data, events,
                             ParamPolicy(pp), prox, objective=objective,
                             horizon=horizon, record_every=record_every,
                             telemetry=telemetry, engine=engine,
                             grad_fn=grad_fn, grad_layout=grad_layout)
    return cell


def make_sweep_piag(worker_loss: Callable, x0, worker_data, prox: ProxOp,
                    objective: Optional[Callable] = None, horizon: int = 4096,
                    use_tau_max: bool = True, masked: bool = False,
                    record_every: int = 1, donate: bool = False,
                    telemetry=None, engine: str = "scan",
                    faults=None, grad_layout: str = "gathered") -> Callable:
    """Build the batched PIAG program.

    Returns jitted ``fn(service_times (B, n, K+1), params (B,)) ->
    PIAGResult`` with a leading B on every leaf; with ``masked=True`` the
    signature grows an ``active (B, n) bool`` argument between the two (the
    ragged-bucket form).  ``donate=True`` donates the stacked service-time
    tensor (arg 0) so its buffer is reused in place -- pass a fresh array
    per call (the ``sweep_*`` runners do).  ``engine='fused'`` selects the
    Pallas fused per-event kernel inside the scan core (bitwise-equal).
    ``grad_layout`` is the worker-gradient layout; ``sweep_piag`` picks it
    per bucket with ``pick_grad_layout``.
    """
    return jax.jit(jax.vmap(_piag_cell(
        worker_loss, x0, worker_data, prox, objective, horizon, use_tau_max,
        masked, record_every, telemetry, engine, normalize_faults(faults),
        grad_layout=grad_layout)),
        donate_argnums=(0,) if donate else ())


def sweep_piag(worker_loss: Callable, x0, worker_data, grid: SweepGrid,
               prox: ProxOp, objective: Optional[Callable] = None,
               horizon: Horizon = 4096, use_tau_max: bool = True,
               bucket_widths: Optional[Sequence[int]] = None,
               record_every: int = 1, telemetry=None,
               engine: str = "scan", faults=None,
               checkpoint=None) -> PIAGResult:
    """Run PIAG on every cell of ``grid`` in one batched program per
    bucket (a homogeneous grid is exactly one program).  ``bucket_widths``
    overrides the ragged grid's padded-width menu (``SweepGrid.buckets``).

    Per-bucket executables are cached (``sweep.cache``) keyed on the static
    configuration and the identity of the captured objects, so repeated
    calls -- and every bucket after the first sweep of a ragged grid --
    skip rebuild+retrace entirely.  ``horizon='auto'`` sizes the window
    buffer from the grid's measured tau-bar (``resolve_grid_horizon``).
    ``faults`` (a ``FaultSpec``) rides the cache key and switches the cell
    program to the fault-injected form (extra per-cell seed argument);
    ``checkpoint`` makes the bucket loop resumable (``run_bucketed``).
    Each bucket's worker-gradient layout follows its cell count and width
    (``pick_grad_layout``) and rides the cache key."""
    horizon = resolve_grid_horizon(horizon, grid)
    faults = normalize_faults(faults)

    def layout_of(b: SweepBucket) -> str:
        return pick_grad_layout(len(b.index), b.width)

    def run_bucket(b: SweepBucket):
        layout = layout_of(b)
        key = ("piag", b.width, not b.uniform, horizon, use_tau_max,
               record_every, telemetry, engine, faults, layout,
               IdKey(worker_loss), tree_key(x0), tree_key(worker_data),
               IdKey(prox), IdKey(objective))
        fn = cached_program(key, lambda: make_sweep_piag(
            worker_loss, x0, _slice_workers(worker_data, b.width), prox,
            objective=objective, horizon=horizon, use_tau_max=use_tau_max,
            masked=not b.uniform, record_every=record_every,
            donate=_donate_default(), telemetry=telemetry, engine=engine,
            faults=faults, grad_layout=layout))
        T = _service_times(b)
        pp = b.grid.policy_params()
        tail = (_cell_seeds(b),) if faults is not None else ()
        if b.uniform:
            return fn(T, pp, *tail)
        return fn(T, jnp.asarray(b.grid.active_masks(b.width)), pp, *tail)

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint,
                        span_meta=lambda b: {"grad": layout_of(b)})


def sweep_piag_logreg(problem, grid: SweepGrid, prox: ProxOp,
                      horizon: int = 4096) -> PIAGResult:
    """DEPRECATED shim over ``repro.api`` (grid analogue of
    ``core.piag.run_piag_logreg``); rows are bitwise-equal to the
    spec-routed run, which dispatches back to ``sweep_piag`` with the same
    arguments.

    For ragged grids the problem must be built with ``n_workers`` >= the
    grid's widest cell; a cell with ``w`` workers runs on the first ``w``
    shards of that fixed partition (worker-participation semantics)."""
    _warn_legacy("sweep_piag_logreg")
    from repro.api import run_components
    return run_components("piag", "batched", problem=problem, grid=grid,
                          prox=prox, horizon=horizon).raw


# ----------------------------------------------------------- Async-BCD ----

def _bcd_cell(grad_f, objective, x0, m, n_workers, prox, horizon, masked,
              record_every=1, telemetry=None, engine="scan", faults=None):
    if faults is not None:
        def faulted(T, active, blocks, pp, seed):
            T = inject_service_times(T, faults, seed)
            tr = trace_scan(T, active=active) if active is not None \
                else trace_scan(T)
            events = (tr.worker, tr.tau, blocks)
            codes = update_fault_codes(faults, events[0].shape[0], seed)
            return bcd_scan(grad_f, objective, x0, m, n_workers, events,
                            ParamPolicy(pp), prox, horizon=horizon,
                            record_every=record_every, telemetry=telemetry,
                            engine=engine, faults=faults, fault_codes=codes)
        if masked:
            return lambda T, active, blocks, pp, seed: \
                faulted(T, active, blocks, pp, seed)
        return lambda T, blocks, pp, seed: faulted(T, None, blocks, pp, seed)
    if masked:
        def cell(T, active, blocks, pp):
            tr = trace_scan(T, active=active)
            events = (tr.worker, tr.tau, blocks)
            return bcd_scan(grad_f, objective, x0, m, n_workers, events,
                            ParamPolicy(pp), prox, horizon=horizon,
                            record_every=record_every, telemetry=telemetry,
                            engine=engine)
    else:
        def cell(T, blocks, pp):
            tr = trace_scan(T)
            events = (tr.worker, tr.tau, blocks)
            return bcd_scan(grad_f, objective, x0, m, n_workers, events,
                            ParamPolicy(pp), prox, horizon=horizon,
                            record_every=record_every, telemetry=telemetry,
                            engine=engine)
    return cell


def make_sweep_bcd(grad_f: Callable, objective: Callable, x0, m: int,
                   n_workers: int, prox: ProxOp, horizon: int = 4096,
                   masked: bool = False, record_every: int = 1,
                   donate: bool = False, telemetry=None,
                   engine: str = "scan", faults=None) -> Callable:
    """Build the batched Async-BCD program: jitted ``fn(service_times
    (B, n, K+1)[, active (B, n)], blocks (B, K), params (B,)) ->
    BCDResult``.  BCD has no cross-worker reduction, so the mask only
    guards the trace (see ``core.bcd.bcd_scan``).  With ``faults`` the
    signature grows a trailing per-cell ``seeds (B,)`` argument."""
    return jax.jit(jax.vmap(_bcd_cell(
        grad_f, objective, x0, m, n_workers, prox, horizon, masked,
        record_every, telemetry, engine, normalize_faults(faults))),
        donate_argnums=(0,) if donate else ())


def sweep_bcd(grad_f: Callable, objective: Callable, x0, m: int,
              grid: SweepGrid, prox: ProxOp, horizon: Horizon = 4096,
              bucket_widths: Optional[Sequence[int]] = None,
              record_every: int = 1, telemetry=None,
              engine: str = "scan", faults=None,
              checkpoint=None) -> BCDResult:
    """Run Async-BCD on every cell; block choices replay the solo sampling
    (``core.bcd.sample_blocks`` with the cell's seed) so rows match solo
    runs.  Per-bucket executables are cached; ``horizon='auto'`` sizes the
    window buffer from the grid's measured tau-bar.  ``faults`` /
    ``checkpoint`` as in ``sweep_piag``."""
    horizon = resolve_grid_horizon(horizon, grid)
    faults = normalize_faults(faults)

    def run_bucket(b: SweepBucket):
        key = ("bcd", b.width, not b.uniform, horizon, m, record_every,
               telemetry, engine, faults, IdKey(grad_f), IdKey(objective),
               tree_key(x0), IdKey(prox))
        fn = cached_program(key, lambda: make_sweep_bcd(
            grad_f, objective, x0, m, b.width, prox, horizon=horizon,
            masked=not b.uniform, record_every=record_every,
            donate=_donate_default(), telemetry=telemetry, engine=engine,
            faults=faults))
        T = _service_times(b)
        blocks = jnp.asarray(np.stack([
            sample_blocks(m, grid.n_events, seed=c.seed)
            for c in b.grid.cells]))
        pp = b.grid.policy_params()
        tail = (_cell_seeds(b),) if faults is not None else ()
        if b.uniform:
            return fn(T, blocks, pp, *tail)
        return fn(T, jnp.asarray(b.grid.active_masks(b.width)), blocks, pp,
                  *tail)

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint)


def sweep_bcd_logreg(problem, grid: SweepGrid, prox: ProxOp, m: int = 20,
                     horizon: int = 4096) -> BCDResult:
    """DEPRECATED shim over ``repro.api``; bitwise-equal rows (the spec
    routes back to ``sweep_bcd`` with the same arguments)."""
    _warn_legacy("sweep_bcd_logreg")
    from repro.api import run_components
    return run_components("bcd", "batched", problem=problem, grid=grid,
                          prox=prox, m=m, horizon=horizon).raw


# ------------------------------------------------- FedAsync / FedBuff ----

def _stack_fed_rounds(grid: SweepGrid, width: int, n_steps: int):
    """Stack per-cell pre-sampled client rounds + lifecycle constants +
    active masks to the bucket width -- the inputs of the fused federated
    runners.  Padded client rows carry benign constants (they never run:
    the ``active`` mask keeps them out of the event race entirely)."""
    B = len(grid.cells)
    drop_u = np.zeros((B, width, n_steps), np.float32)
    dur = np.ones((B, width, n_steps), np.float32)
    p_drop = np.zeros((B, width), np.float32)
    rejoin = np.ones((B, width), np.float32)
    epochs = np.ones((B, width), np.int32)
    with timed("sweep.service_times", run=run_number(), cells=B):
        for i, c in enumerate(grid.cells):
            n = c.n_workers
            r = sample_client_rounds(list(c.workers), n_steps, seed=c.seed)
            drop_u[i, :n], dur[i, :n] = r.drop_u, r.duration
            p_drop[i, :n], rejoin[i, :n], epochs[i, :n] = client_arrays(
                list(c.workers))
    rounds = ClientRounds(jnp.asarray(drop_u), jnp.asarray(dur))
    cparams = (jnp.asarray(p_drop), jnp.asarray(rejoin), jnp.asarray(epochs))
    return rounds, cparams, jnp.asarray(grid.active_masks(width))


def _fed_cell(server_scan, n_uploads, buffer_size, n_steps, faults=None):
    """One federated cell: the jitted trace scan fused with a server scan
    (``server_scan(events, pp[, fault_codes]) -> FedResult``), like PIAG/BCD
    fuse ``trace_scan`` with their solver scans.  Returns the result plus
    the trace diagnostics the host must check (uploads emitted, attempt
    exhaustion).  With ``faults`` the cell signature grows a trailing
    per-cell ``seed``: client round durations are fault-injected before the
    trace scan and the per-upload codes drawn from the same seed."""

    def run(rounds, cparams, active, pp, seed=None):
        if faults is not None:
            rounds = inject_client_rounds(rounds, faults, seed)
        p_drop, rejoin, epochs = cparams
        ftr = federated_trace_scan(rounds, p_drop, rejoin, epochs, n_uploads,
                                   buffer_size=buffer_size, n_steps=n_steps,
                                   active=active)
        events = (ftr.client, ftr.tau, ftr.local_steps,
                  jnp.asarray(ftr.aggregate, jnp.float32), ftr.version)
        if faults is not None:
            codes = update_fault_codes(faults, n_uploads, seed)
            return server_scan(events, pp, codes), ftr.n_uploads, ftr.exhausted
        return server_scan(events, pp), ftr.n_uploads, ftr.exhausted

    if faults is not None:
        return lambda rounds, cparams, active, pp, seed: \
            run(rounds, cparams, active, pp, seed)
    return lambda rounds, cparams, active, pp: run(rounds, cparams, active, pp)


def _check_fed_diag(n_up, exhausted, n_uploads: int, n_steps: int) -> None:
    n_up, exhausted = np.asarray(n_up), np.asarray(exhausted)
    if bool(np.any(n_up < n_uploads)) or bool(np.any(exhausted)):
        short = int(np.sum(n_up < n_uploads))
        raise RuntimeError(
            f"{short} cell(s) produced fewer than {n_uploads} uploads within "
            f"{n_steps} pops (or exhausted pre-sampled attempts): "
            "dropout/rejoin chains exceeded the scan budget -- pass a larger "
            "n_steps")


def make_sweep_fedasync(client_update: Callable, x0, client_data,
                        objective: Optional[Callable] = None,
                        horizon: int = 4096,
                        record_every: int = 1, telemetry=None,
                        engine: str = "scan") -> Callable:
    """Build the events-driven batched FedAsync program: jitted
    ``fn(events (5 x (B, K)), params (B,)) -> FedResult``.  This is the
    reference-path entry (events stacked on host, e.g. by
    ``_stack_fed_events``); the default sweep path fuses trace generation
    via ``make_sweep_fedasync_fused``."""

    def cell(events, pp):
        return fedasync_scan(client_update, x0, client_data, events,
                             ParamPolicy(pp), objective=objective,
                             horizon=horizon, record_every=record_every,
                             telemetry=telemetry, engine=engine)

    return jax.jit(jax.vmap(cell))


def _fedasync_scan_adapter(client_update, x0, client_data, objective, horizon,
                           record_every=1, telemetry=None, engine="scan",
                           faults=None):
    def server_scan(events, pp, fault_codes=None):
        return fedasync_scan(client_update, x0, client_data, events,
                             ParamPolicy(pp), objective=objective,
                             horizon=horizon, record_every=record_every,
                             telemetry=telemetry, engine=engine,
                             faults=faults, fault_codes=fault_codes)
    return server_scan


def _fedbuff_scan_adapter(client_update, x0, client_data, objective, horizon,
                          eta, buffer_size, record_every=1, telemetry=None,
                          engine="scan", faults=None):
    def server_scan(events, pp, fault_codes=None):
        return fedbuff_scan(client_update, x0, client_data, events,
                            ParamPolicy(pp), eta=eta,
                            buffer_size=buffer_size, objective=objective,
                            horizon=horizon, record_every=record_every,
                            telemetry=telemetry, engine=engine,
                            faults=faults, fault_codes=fault_codes)
    return server_scan


def make_sweep_fedasync_fused(client_update: Callable, x0, client_data,
                              n_uploads: int, buffer_size: int = 1,
                              objective: Optional[Callable] = None,
                              horizon: int = 4096,
                              n_steps: Optional[int] = None,
                              record_every: int = 1,
                              donate: bool = False, telemetry=None,
                              engine: str = "scan", faults=None) -> Callable:
    """Build the fused batched FedAsync program: jitted ``fn(rounds,
    cparams, active, params) -> (FedResult, n_uploads (B,), exhausted (B,))``
    with trace generation (``federated_trace_scan``) and the server scan in
    ONE executable, like the PIAG/BCD runners.  ``donate=True`` donates the
    stacked client-rounds tensors (arg 0) -- pass fresh arrays per call.
    With ``faults`` the signature grows a trailing ``seeds (B,)``."""
    n_steps = default_fed_steps(n_uploads) if n_steps is None else int(n_steps)
    faults = normalize_faults(faults)
    return jax.jit(jax.vmap(_fed_cell(
        _fedasync_scan_adapter(client_update, x0, client_data, objective,
                               horizon, record_every, telemetry, engine,
                               faults),
        n_uploads, buffer_size, n_steps, faults)),
        donate_argnums=(0,) if donate else ())


def make_sweep_fedbuff(client_update: Callable, x0, client_data,
                       n_uploads: int, eta: float = 1.0, buffer_size: int = 1,
                       objective: Optional[Callable] = None,
                       horizon: int = 4096,
                       n_steps: Optional[int] = None,
                       record_every: int = 1,
                       donate: bool = False, telemetry=None,
                       engine: str = "scan", faults=None) -> Callable:
    """Build the fused batched FedBuff program (same shape as
    ``make_sweep_fedasync_fused`` with the buffered-delta server scan)."""
    n_steps = default_fed_steps(n_uploads) if n_steps is None else int(n_steps)
    faults = normalize_faults(faults)
    return jax.jit(jax.vmap(_fed_cell(
        _fedbuff_scan_adapter(client_update, x0, client_data, objective,
                              horizon, eta, buffer_size, record_every,
                              telemetry, engine, faults),
        n_uploads, buffer_size, n_steps, faults)),
        donate_argnums=(0,) if donate else ())


@partial(jax.jit, static_argnames=("n_uploads", "buffer_size", "n_steps"))
def _fed_taus_jit(rounds, cparams, active, n_uploads, buffer_size, n_steps):
    def one(r, cp, a):
        p_drop, rejoin, epochs = cp
        return federated_trace_scan(r, p_drop, rejoin, epochs, n_uploads,
                                    buffer_size=buffer_size, n_steps=n_steps,
                                    active=a).tau
    return jax.vmap(one)(rounds, cparams, active)


def measure_fed_tau_bar(grid: SweepGrid, buffer_size: int = 1,
                        n_steps: Optional[int] = None) -> int:
    """Worst-case upload staleness over a federated grid's pre-sampled
    traces -- the federated analogue of ``SweepGrid.measure_tau_bar``, and
    what ``horizon='auto'`` sizes the weight-policy buffer from.  Runs only
    the jitted trace scan (no client updates), one vmapped program per
    bucket."""
    K = grid.n_events
    S = default_fed_steps(K) if n_steps is None else int(n_steps)
    worst = 0
    for b in grid.buckets():
        rounds, cparams, active = _stack_fed_rounds(b.grid, b.width, S)
        taus = _fed_taus_jit(rounds, cparams, active, K, buffer_size, S)
        worst = max(worst, int(np.max(np.asarray(taus), initial=0)))
    return worst


def _stack_fed_events(grid: SweepGrid, buffer_size: int,
                      n_steps: Optional[int] = None):
    """REFERENCE TWIN of the fused path: simulate one federated trace per
    cell with the heapq reference driven by the SAME pre-sampled client
    rounds the jitted ``federated_trace_scan`` consumes, and stack the event
    columns the server scan expects.  Kept for validation (bitwise-equal
    events to the fused path) and as the ``reference=True`` escape hatch of
    ``sweep_fedasync`` / ``sweep_fedbuff``; it costs Python time per event
    and cannot shard."""
    S = default_fed_steps(grid.n_events) if n_steps is None else int(n_steps)
    traces = [simulate_federated(
        c.n_workers, grid.n_events, clients=list(c.workers),
        buffer_size=buffer_size, seed=c.seed,
        client_rounds=sample_client_rounds(list(c.workers), S, seed=c.seed))
        for c in grid.cells]
    return tuple(
        jnp.stack([jnp.asarray(getattr(t, f), dt) for t in traces])
        for f, dt in [("client", jnp.int32), ("tau", jnp.int32),
                      ("local_steps", jnp.int32), ("aggregate", jnp.float32),
                      ("version", jnp.int32)])


def _sweep_fed(server_adapter, make_fused, grid: SweepGrid, client_data,
               buffer_size: int, reference: bool, n_steps: Optional[int],
               bucket_widths: Optional[Sequence[int]] = None,
               cache_key: Optional[Tuple] = None, faults=None,
               checkpoint=None) -> FedResult:
    """Shared driver for ``sweep_fedasync`` / ``sweep_fedbuff``.

    ``cache_key`` is the wrapper's static-configuration tuple; per-bucket
    fused executables are cached under ``cache_key + (width,)`` so repeated
    sweeps (and later buckets of ragged grids) skip rebuild+retrace."""
    K = grid.n_events
    S = default_fed_steps(K) if n_steps is None else int(n_steps)
    if reference:
        if faults is not None:
            raise TypeError(
                "reference=True does not support fault injection (the heapq "
                "reference path has no per-cell seed stream); use the fused "
                "path")
        fn = jax.jit(jax.vmap(server_adapter))
        return fn(_stack_fed_events(grid, buffer_size, n_steps=S),
                  grid.policy_params())

    def run_bucket(b: SweepBucket):
        def build():
            return make_fused(_slice_workers(client_data, b.width), S)
        fn = build() if cache_key is None else cached_program(
            cache_key + (b.width, S), build)
        rounds, cparams, active = _stack_fed_rounds(b.grid, b.width, S)
        tail = (_cell_seeds(b),) if faults is not None else ()
        res, n_up, exhausted = fn(rounds, cparams, active,
                                  b.grid.policy_params(), *tail)
        _check_fed_diag(n_up, exhausted, K, S)
        return res

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint)


def sweep_fedasync(client_update: Callable, x0, client_data, grid: SweepGrid,
                   objective: Optional[Callable] = None,
                   buffer_size: int = 1, horizon: Horizon = 4096,
                   reference: bool = False,
                   n_steps: Optional[int] = None,
                   bucket_widths: Optional[Sequence[int]] = None,
                   record_every: int = 1, telemetry=None,
                   engine: str = "scan", faults=None,
                   checkpoint=None) -> FedResult:
    """Run FedAsync on every cell of a grid whose topologies are
    ``ClientModel`` lists.

    Default path: client round-trip traces AND server mixing run fused in
    one jitted program per bucket (``federated_trace_scan`` +
    ``fedasync_scan``), so the whole sweep is XLA end-to-end like PIAG/BCD.
    ``reference=True`` routes trace generation through the Python heapq
    reference instead (same pre-sampled rounds, bitwise-equal events) --
    the escape hatch for validating the fused path or debugging host-side.
    ``horizon='auto'`` sizes the weight-policy buffer from the grid's
    measured upload staleness (``measure_fed_tau_bar``).
    """
    horizon = resolve_grid_horizon(horizon, grid, fed=True,
                                   buffer_size=buffer_size, n_steps=n_steps)
    faults = normalize_faults(faults)
    adapter = _fedasync_scan_adapter(client_update, x0, client_data,
                                     objective, horizon, record_every,
                                     telemetry, engine)

    def make_fused(cd, S):
        return make_sweep_fedasync_fused(client_update, x0, cd, grid.n_events,
                                         buffer_size=buffer_size,
                                         objective=objective, horizon=horizon,
                                         n_steps=S, record_every=record_every,
                                         donate=_donate_default(),
                                         telemetry=telemetry, engine=engine,
                                         faults=faults)

    key = ("fedasync", grid.n_events, buffer_size, horizon, record_every,
           telemetry, engine, faults, IdKey(client_update), tree_key(x0),
           tree_key(client_data), IdKey(objective))
    return _sweep_fed(adapter, make_fused, grid, client_data, buffer_size,
                      reference, n_steps, bucket_widths=bucket_widths,
                      cache_key=key, faults=faults, checkpoint=checkpoint)


def sweep_fedbuff(client_update: Callable, x0, client_data, grid: SweepGrid,
                  eta: float = 1.0, buffer_size: int = 1,
                  objective: Optional[Callable] = None,
                  horizon: Horizon = 4096,
                  reference: bool = False,
                  n_steps: Optional[int] = None,
                  bucket_widths: Optional[Sequence[int]] = None,
                  record_every: int = 1, telemetry=None,
                  engine: str = "scan", faults=None,
                  checkpoint=None) -> FedResult:
    """Run FedBuff on every cell: fused jitted trace generation + buffered
    delta aggregation (``federated_trace_scan`` + ``fedbuff_scan``), one
    program per bucket; ``reference=True`` / ``horizon='auto'`` as in
    ``sweep_fedasync``."""
    horizon = resolve_grid_horizon(horizon, grid, fed=True,
                                   buffer_size=buffer_size, n_steps=n_steps)
    faults = normalize_faults(faults)
    adapter = _fedbuff_scan_adapter(client_update, x0, client_data, objective,
                                    horizon, eta, buffer_size, record_every,
                                    telemetry, engine)

    def make_fused(cd, S):
        return make_sweep_fedbuff(client_update, x0, cd, grid.n_events,
                                  eta=eta, buffer_size=buffer_size,
                                  objective=objective, horizon=horizon,
                                  n_steps=S, record_every=record_every,
                                  donate=_donate_default(),
                                  telemetry=telemetry, engine=engine,
                                  faults=faults)

    key = ("fedbuff", grid.n_events, eta, buffer_size, horizon, record_every,
           telemetry, engine, faults, IdKey(client_update), tree_key(x0),
           tree_key(client_data), IdKey(objective))
    return _sweep_fed(adapter, make_fused, grid, client_data, buffer_size,
                      reference, n_steps, bucket_widths=bucket_widths,
                      cache_key=key, faults=faults, checkpoint=checkpoint)


def sweep_fedasync_problem(problem, grid: SweepGrid, prox: ProxOp,
                           local_lr: Optional[float] = None,
                           horizon: int = 4096, reference: bool = False,
                           n_steps: Optional[int] = None) -> FedResult:
    """DEPRECATED shim over ``repro.api`` (grid analogue of
    ``federated.server.run_fedasync_problem``); bitwise-equal rows."""
    _warn_legacy("sweep_fedasync_problem")
    from repro.api import run_components
    return run_components("fedasync", "batched", problem=problem, grid=grid,
                          prox=prox, local_lr=local_lr, horizon=horizon,
                          reference=reference, n_steps=n_steps).raw


def sweep_fedbuff_problem(problem, grid: SweepGrid, prox: ProxOp,
                          eta: float = 1.0, buffer_size: int = 1,
                          local_lr: Optional[float] = None,
                          horizon: int = 4096, reference: bool = False,
                          n_steps: Optional[int] = None) -> FedResult:
    """DEPRECATED shim over ``repro.api`` (grid analogue of
    ``federated.server.run_fedbuff_problem``); bitwise-equal rows."""
    _warn_legacy("sweep_fedbuff_problem")
    from repro.api import run_components
    return run_components("fedbuff", "batched", problem=problem, grid=grid,
                          prox=prox, eta=eta, buffer_size=buffer_size,
                          local_lr=local_lr, horizon=horizon,
                          reference=reference, n_steps=n_steps).raw

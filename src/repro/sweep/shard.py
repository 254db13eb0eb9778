"""Device-sharded mega-grid sweeps (`repro.sweep.shard`).

The batched runners in ``.runners`` collapse a whole grid into one XLA
program -- but that program lives on ONE device.  This module partitions the
**cell axis** of a mega-grid across devices with
``jax.shard_map`` over a ``("cells",)`` or 2-D
``("cells", "data")`` ``Mesh`` (see :mod:`repro.mesh`):

* the per-cell program is the SAME vmapped cell function the single-device
  runners use (``_piag_cell`` / ``_bcd_cell`` / ``_fed_cell``), so a sharded
  row is the same computation as a batched row is the same computation as a
  solo run -- the equivalence chain tested end-to-end;
* cells are embarrassingly parallel (no cross-cell communication) on the
  cells axis: ``shard_map`` pins cell-shard ``d`` of the stacked inputs to
  the ``d``-th mesh row and runs the batched program there;
* on a 2-D mesh the per-worker gradient batch inside each cell additionally
  runs data-parallel across the ``"data"`` axis: the in/out specs stay
  ``P("cells")`` (args and outputs replicated over data), and the injected
  ``repro.mesh.pmean_grad`` slices the sample axis per data shard and psums
  the partial gradients -- taus and every integer leaf stay bitwise-equal
  to the 1-D path, objectives equal under jit (see the psum-axis contract
  in ``repro.mesh``);
* the stacked service-time / client-round tensors -- the only O(B * n * K)
  inputs -- are **donated** (``donate_argnums=0``), so XLA reuses their
  buffers and peak memory stays flat instead of doubling at dispatch;
* B rarely divides the cell-shard count: ``round_robin_pad`` pads the batch
  to the next cells-axis multiple by cycling cell indices (so padding
  replays real cells -- every device gets live work and identical per-cell
  shapes), and the wrappers strip the padded rows before returning;
* executables cache by **mesh topology** (``repro.mesh.mesh_topology``:
  axis names + shape + device kind + process count), never mesh identity,
  so 1-D / reshaped 2-D / multi-host meshes never collide on a program.

``sharded_sweep_*`` convenience wrappers mirror ``sweep_*`` exactly
(including ragged-grid bucketing) and return identical row values; keep the
``make_sharded_*`` builders when amortizing compiles across repeated calls
(see ``benchmarks/mega_grid.py``, which scales a >= 512-cell
policy x seed x topology x n_workers grid across forced host devices).
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core.bcd import BCDResult, sample_blocks
from repro.core.piag import PIAGResult
from repro.core.prox import ProxOp
from repro.federated.events import default_fed_steps
from repro.federated.server import FedResult
from repro.mesh import (DATA_AXIS, cell_axis_size, cell_mesh, data_axis_size,
                        grid_mesh, mesh_topology, pmean_grad)

from repro.telemetry.timing import timed

from repro.faults.spec import normalize_faults

from .cache import IdKey, cached_program, tree_key
from .grid import SweepBucket, SweepGrid
from .runners import (Horizon, _bcd_cell, _cell_seeds, _fed_cell,
                      _fedasync_scan_adapter, _fedbuff_scan_adapter,
                      _piag_cell, _slice_workers, _stack_fed_rounds,
                      _check_fed_diag, resolve_grid_horizon, run_bucketed)

__all__ = ["cell_mesh", "grid_mesh", "mesh_topology", "round_robin_pad",
           "shard_cells",
           "make_sharded_sweep_piag", "sharded_sweep_piag",
           "sharded_sweep_piag_logreg",
           "make_sharded_sweep_bcd", "sharded_sweep_bcd",
           "sharded_sweep_fedasync", "sharded_sweep_fedbuff"]


def round_robin_pad(n_cells: int, n_cell_shards: int) -> np.ndarray:
    """Index map of length ``ceil(B / C) * C`` cycling through the B
    cells, where C is the size of the mesh's **cells axis** -- NOT the
    total device count.  On a 2-D
    ``(cells, data)`` mesh the data axis replicates the batch, so only the
    cells axis constrains padding; a (2, 4) mesh pads exactly like a (2,)
    mesh.

    Gathering the stacked inputs through this map pads the batch to a
    cells-axis multiple with REPLAYED cells (not zeros), so every shard
    keeps identical shapes and live work; callers drop rows ``>= n_cells``
    on the way out.  One cell per shard is fine.
    """
    if n_cells < 1:
        raise ValueError("empty grid")
    per_shard = -(-n_cells // n_cell_shards)
    return np.arange(per_shard * n_cell_shards) % n_cells


def shard_cells(vmapped_fn: Callable, mesh: Mesh, n_args: int,
                donate: bool = True) -> Callable:
    """Wrap a vmapped cell function in ``shard_map`` over ``mesh`` and jit.

    Every argument and output is partitioned on its leading (cell) axis
    over the mesh's "cells" axis; argument 0 -- the big stacked
    service-time / client-rounds tensor -- is donated so its buffer is
    reused in place.  The batch size fed to the returned function must be a
    multiple of the cells-axis size (``round_robin_pad``).

    On a 2-D ``(cells, data)`` mesh the specs are unchanged: arguments and
    outputs are replicated over the data axis, and the data axis only
    carries gradient COMPUTE via an injected ``pmean_grad`` whose psum makes
    every data shard's output identical -- so ``P("cells")`` out_specs stay
    valid and row values match the 1-D mesh bitwise on integer leaves."""
    specs = tuple(PartitionSpec("cells") for _ in range(n_args))
    # check_vma=False: the scan carries start from constants (invariant
    # over the mesh) and are updated from the per-shard inputs (varying
    # over "cells"), which the varying-axes checker rejects as a carry
    # type change.  Every output is sharded over "cells", and on a 2-D
    # mesh made identical over "data" by pmean_grad's psum, so the
    # out_specs hold without the check.
    fn = jax.shard_map(vmapped_fn, mesh=mesh, in_specs=specs,
                       out_specs=PartitionSpec("cells"), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _pad_gather(tree, idx: np.ndarray):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[idx], tree)


def _unpad(tree, n: int):
    return jax.tree_util.tree_map(lambda x: x[:n], tree)


def _run_sharded_bucket(cell_build, mesh: Mesh, args, n_cells: int,
                        n_args: int, cache_key: Optional[tuple] = None):
    """Pad the stacked args to a cells-axis multiple, run the sharded
    program, strip the padding.  ``cell_build()`` makes the per-cell
    function; the wrapped executable is cached under ``cache_key`` (when
    given) so repeated sweeps skip rebuild+retrace, exactly like the
    batched path."""
    idx = round_robin_pad(n_cells, cell_axis_size(mesh))

    def build():
        return shard_cells(jax.vmap(cell_build()), mesh, n_args=n_args)

    fn = build() if cache_key is None else cached_program(cache_key, build)
    # telemetry: dispatch wall time across the mesh (per-device skew shows
    # up as dispatch >> cells/devices * per-cell cost on the warm path)
    with timed("sharded_dispatch", devices=int(mesh.devices.size),
               data_shards=data_axis_size(mesh),
               cells=int(n_cells)):
        out = fn(*(_pad_gather(a, idx) for a in args))
    return _unpad(out, n_cells)


# ---------------------------------------------------------------- PIAG ----

def _dp_grad_for(worker_loss: Callable, mesh: Mesh) -> Optional[Callable]:
    """``pmean_grad`` over the mesh's data axis, or None on a 1-D mesh."""
    D = data_axis_size(mesh)
    return pmean_grad(worker_loss, DATA_AXIS, D) if D > 1 else None


def make_sharded_sweep_piag(worker_loss: Callable, x0, worker_data,
                            prox: ProxOp, objective: Optional[Callable] = None,
                            horizon: int = 4096, use_tau_max: bool = True,
                            masked: bool = False,
                            mesh: Optional[Mesh] = None,
                            record_every: int = 1, telemetry=None,
                            engine: str = "scan", faults=None) -> Callable:
    """Sharded twin of ``make_sweep_piag``: same signature and row values,
    but the batch axis is partitioned across ``mesh``'s cells axis (batch
    size must be a cells-axis multiple; see ``round_robin_pad``).  On a 2-D
    ``(cells, data)`` mesh worker gradients are additionally computed
    data-parallel via ``pmean_grad``.  Arg 0 is donated.  With ``faults``
    the signature grows a trailing ``seeds (B,)`` argument."""
    mesh = cell_mesh() if mesh is None else mesh
    faults = normalize_faults(faults)
    cell = _piag_cell(worker_loss, x0, worker_data, prox, objective, horizon,
                      use_tau_max, masked, record_every, telemetry, engine,
                      faults, grad_fn=_dp_grad_for(worker_loss, mesh))
    n_args = (3 if masked else 2) + (1 if faults is not None else 0)
    return shard_cells(jax.vmap(cell), mesh, n_args=n_args)


def sharded_sweep_piag(worker_loss: Callable, x0, worker_data,
                       grid: SweepGrid, prox: ProxOp,
                       objective: Optional[Callable] = None,
                       horizon: Horizon = 4096, use_tau_max: bool = True,
                       mesh: Optional[Mesh] = None,
                       bucket_widths: Optional[Sequence[int]] = None,
                       record_every: int = 1, telemetry=None,
                       engine: str = "scan", faults=None,
                       checkpoint=None) -> PIAGResult:
    """``sweep_piag`` with the cell axis sharded across the mesh's cells
    axis; a 2-D ``(cells, data)`` mesh adds data-parallel worker gradients
    (``pmean_grad`` psums over "data"; rows stay bitwise on integer
    leaves)."""
    mesh = cell_mesh() if mesh is None else mesh
    horizon = resolve_grid_horizon(horizon, grid)
    faults = normalize_faults(faults)
    grad_fn = _dp_grad_for(worker_loss, mesh)

    def run_bucket(b: SweepBucket):
        key = ("piag/sharded", b.width, not b.uniform, horizon, use_tau_max,
               record_every, telemetry, engine, faults, mesh_topology(mesh),
               IdKey(worker_loss), tree_key(x0), tree_key(worker_data),
               IdKey(prox), IdKey(objective))
        T = jnp.asarray(b.grid.service_times(b.width))
        pp = b.grid.policy_params()
        args = ((T, pp) if b.uniform else
                (T, jnp.asarray(b.grid.active_masks(b.width)), pp))
        if faults is not None:
            args = args + (_cell_seeds(b),)
        return _run_sharded_bucket(
            lambda: _piag_cell(worker_loss, x0,
                               _slice_workers(worker_data, b.width), prox,
                               objective, horizon, use_tau_max,
                               not b.uniform, record_every, telemetry,
                               engine, faults, grad_fn=grad_fn),
            mesh, args, len(b.grid), n_args=len(args), cache_key=key)

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint)


def sharded_sweep_piag_logreg(problem, grid: SweepGrid, prox: ProxOp,
                              horizon: int = 4096,
                              mesh: Optional[Mesh] = None) -> PIAGResult:
    """DEPRECATED shim over ``repro.api`` (sharded twin of
    ``sweep_piag_logreg``); bitwise-equal rows -- the spec routes back to
    ``sharded_sweep_piag`` with the same arguments."""
    from .runners import _warn_legacy
    _warn_legacy("sharded_sweep_piag_logreg")
    from repro.api import run_components
    return run_components("piag", "sharded", problem=problem, grid=grid,
                          prox=prox, horizon=horizon, mesh=mesh).raw


# ----------------------------------------------------------- Async-BCD ----

def _pick_bcd_grad(grad_f: Callable, dp_grad_f: Optional[Callable],
                   mesh: Mesh) -> Callable:
    """On a 2-D mesh, swap in the data-parallel full gradient when given.

    BCD's ``grad_f`` is an opaque x->grad closure, so the runner cannot
    rebuild it data-parallel itself (unlike PIAG's ``worker_loss``); the
    api layer derives ``dp_grad_f`` from ``problem.worker_loss`` via
    ``pmean_grad``.  A 2-D mesh without one still computes correct rows --
    just replicated over the data axis -- so we warn instead of raising."""
    if data_axis_size(mesh) <= 1:
        return grad_f
    if dp_grad_f is None:
        warnings.warn(
            "sharded BCD on a (cells, data) mesh without dp_grad_f: the "
            "gradient runs replicated on every data shard (correct but no "
            "speedup); pass dp_grad_f (e.g. built with repro.mesh."
            "pmean_grad) or use the repro.api spec path",
            RuntimeWarning, stacklevel=3)
        return grad_f
    return dp_grad_f


def make_sharded_sweep_bcd(grad_f: Callable, objective: Callable, x0, m: int,
                           n_workers: int, prox: ProxOp, horizon: int = 4096,
                           masked: bool = False,
                           mesh: Optional[Mesh] = None,
                           record_every: int = 1, telemetry=None,
                           engine: str = "scan", faults=None,
                           dp_grad_f: Optional[Callable] = None) -> Callable:
    """Sharded twin of ``make_sweep_bcd`` (batch must be a cells-axis
    multiple).  ``dp_grad_f`` replaces ``grad_f`` on 2-D meshes (see
    ``_pick_bcd_grad``)."""
    mesh = cell_mesh() if mesh is None else mesh
    faults = normalize_faults(faults)
    gf = _pick_bcd_grad(grad_f, dp_grad_f, mesh)
    cell = _bcd_cell(gf, objective, x0, m, n_workers, prox, horizon,
                     masked, record_every, telemetry, engine, faults)
    n_args = (4 if masked else 3) + (1 if faults is not None else 0)
    return shard_cells(jax.vmap(cell), mesh, n_args=n_args)


def sharded_sweep_bcd(grad_f: Callable, objective: Callable, x0, m: int,
                      grid: SweepGrid, prox: ProxOp, horizon: Horizon = 4096,
                      mesh: Optional[Mesh] = None,
                      bucket_widths: Optional[Sequence[int]] = None,
                      record_every: int = 1, telemetry=None,
                      engine: str = "scan", faults=None,
                      checkpoint=None,
                      dp_grad_f: Optional[Callable] = None) -> BCDResult:
    """``sweep_bcd`` with the cell axis sharded; on a 2-D mesh pass
    ``dp_grad_f`` (a psum-over-"data" full gradient) to actually partition
    the gradient compute (see ``_pick_bcd_grad``)."""
    mesh = cell_mesh() if mesh is None else mesh
    horizon = resolve_grid_horizon(horizon, grid)
    faults = normalize_faults(faults)
    gf = _pick_bcd_grad(grad_f, dp_grad_f, mesh)

    def run_bucket(b: SweepBucket):
        key = ("bcd/sharded", b.width, not b.uniform, horizon, m,
               record_every, telemetry, engine, faults, mesh_topology(mesh),
               IdKey(gf),
               IdKey(objective), tree_key(x0), IdKey(prox))
        T = jnp.asarray(b.grid.service_times(b.width))
        blocks = jnp.asarray(np.stack([
            sample_blocks(m, grid.n_events, seed=c.seed)
            for c in b.grid.cells]))
        pp = b.grid.policy_params()
        args = ((T, blocks, pp) if b.uniform else
                (T, jnp.asarray(b.grid.active_masks(b.width)), blocks, pp))
        if faults is not None:
            args = args + (_cell_seeds(b),)
        return _run_sharded_bucket(
            lambda: _bcd_cell(gf, objective, x0, m, b.width, prox,
                              horizon, not b.uniform, record_every,
                              telemetry, engine, faults),
            mesh, args, len(b.grid), n_args=len(args), cache_key=key)

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint)


# ------------------------------------------------- FedAsync / FedBuff ----

def _sharded_sweep_fed(adapter_for, grid: SweepGrid, client_data,
                       buffer_size: int, n_steps: Optional[int],
                       mesh: Optional[Mesh],
                       bucket_widths: Optional[Sequence[int]] = None,
                       cache_key: Optional[tuple] = None, faults=None,
                       checkpoint=None) -> FedResult:
    mesh = cell_mesh() if mesh is None else mesh
    K = grid.n_events
    S = default_fed_steps(K) if n_steps is None else int(n_steps)

    def run_bucket(b: SweepBucket):
        key = None if cache_key is None else \
            cache_key + (b.width, S, mesh_topology(mesh))
        rounds, cparams, active = _stack_fed_rounds(b.grid, b.width, S)
        args = (rounds, cparams, active, b.grid.policy_params())
        if faults is not None:
            args = args + (_cell_seeds(b),)
        res, n_up, exhausted = _run_sharded_bucket(
            lambda: _fed_cell(adapter_for(_slice_workers(client_data,
                                                         b.width)),
                              K, buffer_size, S, faults),
            mesh, args, len(b.grid), n_args=len(args), cache_key=key)
        _check_fed_diag(n_up, exhausted, K, S)
        return res

    return run_bucketed(grid, run_bucket, bucket_widths,
                        checkpoint=checkpoint)


def sharded_sweep_fedasync(client_update: Callable, x0, client_data,
                           grid: SweepGrid,
                           objective: Optional[Callable] = None,
                           buffer_size: int = 1, horizon: Horizon = 4096,
                           n_steps: Optional[int] = None,
                           mesh: Optional[Mesh] = None,
                           bucket_widths: Optional[Sequence[int]] = None,
                           record_every: int = 1, telemetry=None,
                           engine: str = "scan", faults=None,
                           checkpoint=None) -> FedResult:
    """``sweep_fedasync`` (fused path) with the cell axis sharded.

    On a 2-D mesh pass a data-parallel ``client_update`` (one built with
    ``local_prox_sgd(..., grad_fn=pmean_grad(...))``, as the api path
    does); a plain update runs replicated over "data" -- correct rows, no
    speedup."""
    horizon = resolve_grid_horizon(horizon, grid, fed=True,
                                   buffer_size=buffer_size, n_steps=n_steps)
    faults = normalize_faults(faults)

    def adapter_for(cd):
        return _fedasync_scan_adapter(client_update, x0, cd, objective,
                                      horizon, record_every, telemetry,
                                      engine, faults)

    key = ("fedasync/sharded", grid.n_events, buffer_size, horizon,
           record_every, telemetry, engine, faults, IdKey(client_update),
           tree_key(x0), tree_key(client_data), IdKey(objective))
    return _sharded_sweep_fed(adapter_for, grid, client_data, buffer_size,
                              n_steps, mesh, bucket_widths=bucket_widths,
                              cache_key=key, faults=faults,
                              checkpoint=checkpoint)


def sharded_sweep_fedbuff(client_update: Callable, x0, client_data,
                          grid: SweepGrid, eta: float = 1.0,
                          buffer_size: int = 1,
                          objective: Optional[Callable] = None,
                          horizon: Horizon = 4096,
                          n_steps: Optional[int] = None,
                          mesh: Optional[Mesh] = None,
                          bucket_widths: Optional[Sequence[int]] = None,
                          record_every: int = 1, telemetry=None,
                          engine: str = "scan", faults=None,
                          checkpoint=None) -> FedResult:
    """``sweep_fedbuff`` (fused path) with the cell axis sharded.

    On a 2-D mesh pass a data-parallel ``client_update`` (one built with
    ``local_prox_sgd(..., grad_fn=pmean_grad(...))``, as the api path
    does); a plain update runs replicated over "data" -- correct rows, no
    speedup."""
    horizon = resolve_grid_horizon(horizon, grid, fed=True,
                                   buffer_size=buffer_size, n_steps=n_steps)
    faults = normalize_faults(faults)

    def adapter_for(cd):
        return _fedbuff_scan_adapter(client_update, x0, cd, objective,
                                     horizon, eta, buffer_size, record_every,
                                     telemetry, engine, faults)

    key = ("fedbuff/sharded", grid.n_events, eta, buffer_size, horizon,
           record_every, telemetry, engine, faults, IdKey(client_update),
           tree_key(x0), tree_key(client_data), IdKey(objective))
    return _sharded_sweep_fed(adapter_for, grid, client_data, buffer_size,
                              n_steps, mesh, bucket_widths=bucket_widths,
                              cache_key=key, faults=faults,
                              checkpoint=checkpoint)

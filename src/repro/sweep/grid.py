"""Sweep grids: the cartesian product of policies x seeds x topologies
(x worker counts).

A ``SweepGrid`` is a flat list of cells, each pinning one policy instance,
one RNG seed, and one worker topology (a list of ``WorkerModel``/
``ClientModel``).  The grid knows how to materialize the batched inputs the
runners consume: a stacked service-time tensor (B, width, K+1) for the
jitted trace generator and stacked ``PolicyParams`` for the parametric
policy.

Ragged worker counts
--------------------

Since PR 3 a grid may mix worker counts (``make_grid(..., n_workers=[4, 8])``
grows an ``n_workers`` axis from topology *factories*).  Stacking still needs
rectangular arrays, so ragged grids are **bucketed**: cells are grouped by
a padded width (next power of two by default), each cell's service-time
matrix is padded to the bucket width with ``+inf`` rows, and an
``active_workers`` mask tells the trace/solver scans which rows are real --
padded workers never win the event race and never contribute gradients
(``core.engine.trace_scan`` / ``core.piag.piag_scan``), so a bucketed cell
is the SAME computation as its exact-width run.  Each bucket compiles once;
a homogeneous grid is a single exact-width bucket, i.e. exactly the PR 2
path.

Worker-data semantics for ragged grids: runners slice the shared
``worker_data`` pytree to the bucket width, and a cell with ``w`` active
workers uses rows ``0..w-1``.  A ragged grid therefore sweeps *worker
participation* out of a fixed maximal population -- the FedBuff-style
worker-count axis -- rather than re-partitioning the dataset per cell.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (WorkerModel, heterogeneous_workers,
                               sample_service_times, trace_scan)
from repro.core.stepsize import StepsizePolicy, next_pow2
from repro.telemetry.timing import run_number, timed

from .policies import PolicyParams, stack_params

__all__ = ["SweepCell", "SweepGrid", "SweepBucket", "make_grid",
           "measure_tau_bar", "next_pow2", "standard_topologies",
           "standard_topology_factories"]


def _measure_program():
    # nested: its name, which the XLA module takes, is that of the public
    # measure_tau_bar below
    def measure_tau_bar(T):
        return trace_scan(T).tau_max
    return jax.jit(jax.vmap(measure_tau_bar))


# one jitted trace-delay program for every tau-bar measurement in the repo
# (module-level so repeated resolves/builds reuse the trace instead of
# re-tracing each call; jax re-specializes per shape).  Its XLA module is
# jit_measure_tau_bar, the name profiler readers key on.
_tau_max_jit = _measure_program()


def _worst_delay(groups, n_events: int) -> int:
    """The largest trace delay over ``groups`` of (workers, seed) pairs,
    each group of one worker count: the host draws the service times, one
    vmapped program per group scans them.  One ``api.tau_bar`` span."""
    with timed("api.tau_bar", run=run_number()):
        worst = 0
        for pairs in groups:
            Ts = np.stack([sample_service_times(ws, n_events + 1, seed=int(s))
                           for ws, s in pairs])
            taus = _tau_max_jit(jnp.asarray(Ts))
            worst = max(worst, int(np.max(np.asarray(taus))))
        return worst


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One grid cell: (policy, seed, topology)."""

    policy_name: str
    policy: StepsizePolicy
    seed: int
    topology_name: str
    workers: Tuple = ()

    @property
    def n_workers(self) -> int:
        return len(self.workers)


class SweepBucket(NamedTuple):
    """One rectangular slice of a (possibly ragged) grid.

    width:  the padded worker count every cell in the bucket is stacked to.
    index:  positions of the bucket's cells in the parent grid (used to
            stitch per-bucket results back into parent cell order).
    grid:   the sub-``SweepGrid`` of exactly those cells.
    """

    width: int
    index: np.ndarray
    grid: "SweepGrid"

    @property
    def uniform(self) -> bool:
        """True iff no cell actually needs padding (mask would be all-True);
        runners then use the unmasked builders -- the exact PR 2 program."""
        return all(c.n_workers == self.width for c in self.grid.cells)


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """A flat batch of sweep cells plus the shared event count."""

    cells: Tuple[SweepCell, ...]
    n_events: int

    def __len__(self) -> int:
        return len(self.cells)

    def measure_tau_bar(self) -> int:
        """Worst-case trace delay over the grid's own (topology, seed) cells
        -- the measured bound ``horizon='auto'`` sizes buffers from.

        Policies don't influence traces, so cells are deduplicated by
        (topology, seed) and measured per worker-count group with the shared
        jitted trace program (PIAG/BCD service-time grids only; federated
        staleness is measured by ``runners.measure_fed_tau_bar``)."""
        seen = {}
        for c in self.cells:
            seen.setdefault((c.topology_name, c.seed), c)
        by_width: Dict[int, list] = {}
        for c in seen.values():
            by_width.setdefault(c.n_workers, []).append((c.workers, c.seed))
        return _worst_delay(by_width.values(), self.n_events)

    @property
    def is_ragged(self) -> bool:
        return len({c.n_workers for c in self.cells}) > 1

    @property
    def n_workers(self) -> int:
        ns = {c.n_workers for c in self.cells}
        if len(ns) > 1:
            raise ValueError(
                f"ragged grid (worker counts {sorted(ns)}); use "
                "n_workers_max or iterate buckets()")
        return next(iter(ns))

    @property
    def n_workers_max(self) -> int:
        return max(c.n_workers for c in self.cells)

    def subset(self, index: Sequence[int]) -> "SweepGrid":
        return SweepGrid(cells=tuple(self.cells[int(i)] for i in index),
                         n_events=self.n_events)

    def buckets(self, bucket_widths: Optional[Sequence[int]] = None
                ) -> Tuple[SweepBucket, ...]:
        """Group cells into rectangular buckets by padded worker count.

        ``bucket_widths`` is the sorted menu of allowed widths (each cell
        lands in the smallest width >= its worker count).  Default: a
        homogeneous grid is ONE exact-width bucket (no padding, no mask --
        bitwise the PR 2 path); a ragged grid pads each cell to the next
        power of two capped at the grid's widest cell (padding past the
        widest real topology would only waste FLOPs and outgrow the shared
        worker data), trading a <2x per-cell FLOP overhead for one compile
        per octave instead of one per distinct worker count.
        """
        if bucket_widths is None:
            if not self.is_ragged:
                widths = [self.n_workers_max]
            else:
                widths = sorted({min(next_pow2(c.n_workers),
                                     self.n_workers_max)
                                 for c in self.cells})
        else:
            widths = sorted(int(w) for w in bucket_widths)
        out = []
        for w in widths:
            idx = np.asarray([i for i, c in enumerate(self.cells)
                              if c.n_workers <= w
                              and not any(c.n_workers <= v for v in widths
                                          if v < w)], np.int64)
            if idx.size:
                out.append(SweepBucket(width=w, index=idx,
                                       grid=self.subset(idx)))
        placed = sum(b.index.size for b in out)
        if placed != len(self.cells):
            big = max(c.n_workers for c in self.cells)
            raise ValueError(
                f"bucket_widths {widths} cannot hold all cells "
                f"(max worker count {big})")
        return tuple(out)

    def policy_params(self) -> PolicyParams:
        """Stacked (B,) ``PolicyParams`` for the parametric policy."""
        return stack_params([c.policy for c in self.cells])

    def service_times(self, width: Optional[int] = None) -> np.ndarray:
        """(B, width, n_events + 1) float32 -- one matrix per cell, sampled
        from the cell's seed (per-worker counter substreams).  ``width``
        defaults to the (homogeneous) worker count; padded rows are ``+inf``
        so an unmasked consumer can never mistake them for real tasks (the
        mask from ``active_masks`` is still required for ``tau_max``)."""
        w = self.n_workers if width is None else int(width)
        out = np.full((len(self.cells), w, self.n_events + 1), np.inf,
                      np.float32)
        for i, c in enumerate(self.cells):
            if c.n_workers > w:
                raise ValueError(
                    f"cell {i} has {c.n_workers} workers > width {w}")
            out[i, :c.n_workers] = sample_service_times(
                c.workers, self.n_events + 1, seed=c.seed)
        return out

    def active_masks(self, width: Optional[int] = None) -> np.ndarray:
        """(B, width) bool -- True where a worker row is real, False where
        it is bucket padding."""
        w = self.n_workers if width is None else int(width)
        return np.asarray([
            np.arange(w) < c.n_workers for c in self.cells])

    def labels(self) -> List[str]:
        return [f"{c.policy_name}/s{c.seed}/{c.topology_name}"
                for c in self.cells]


def standard_topologies(n_workers: int, seed: int = 0) -> Dict[str, list]:
    """The four worker regimes the paper's figures probe: homogeneous,
    mildly/strongly heterogeneous speeds (Fig. 3 shows ~2.4x per-worker
    spread), and straggler-dominated (Fig. 2's long-tail delays)."""
    return {name: factory(n_workers)
            for name, factory in standard_topology_factories(seed).items()}


def standard_topology_factories(seed: int = 0) -> Dict[str, Callable]:
    """The same four regimes as ``standard_topologies`` but as width ->
    worker-list factories, the form ``make_grid``'s ``n_workers`` axis
    consumes (each cell instantiates the regime at its own worker count)."""
    return {
        "uniform": lambda n: [WorkerModel() for _ in range(n)],
        "hetero2": lambda n: heterogeneous_workers(n, spread=2.0, seed=seed),
        "hetero4": lambda n: heterogeneous_workers(n, spread=4.0,
                                                   seed=seed + 1),
        "straggler": lambda n: [WorkerModel(mean=1.0, p_straggle=0.1,
                                            straggle_x=12.0)
                                for _ in range(n)],
    }


def measure_tau_bar(topologies: Dict[str, Sequence], seeds: Sequence[int],
                    n_events: int) -> int:
    """The worst-case delay bound tau-bar over every (topology, seed) trace
    of a prospective grid -- what the paper's fixed baselines are tuned from.

    Runs the jitted trace generator over all topology x seed cells in one
    vmapped program (policies don't influence traces, so none are needed).
    Shared by ``benchmarks/sweep_grid.py`` and ``repro.launch.sweep``.
    Ragged topology menus are measured per width (stacking is rectangular).
    """
    by_width: Dict[int, list] = {}
    for ws in topologies.values():
        by_width.setdefault(len(ws), []).extend((ws, s) for s in seeds)
    return _worst_delay(by_width.values(), n_events)


def make_grid(policies: Dict[str, StepsizePolicy],
              seeds: Sequence[int],
              topologies: Dict[str, Sequence],
              n_events: int,
              n_workers: Optional[Sequence[int]] = None) -> SweepGrid:
    """Cartesian product in deterministic (policy, seed, topology[, width])
    order.

    Without ``n_workers``, topology values are concrete worker lists (the
    PR 2 form).  With ``n_workers``, the grid grows a worker-count axis:
    topology values must be factories ``width -> worker list`` (see
    ``standard_topology_factories``) and each (topology, width) pair becomes
    its own topology named ``{name}/w{width}``.  Mixed widths make the grid
    ragged; see ``SweepGrid.buckets``.
    """
    if n_workers is None:
        topo_items = [(tn, tuple(ws)) for tn, ws in topologies.items()]
    else:
        topo_items = []
        for tn, factory in topologies.items():
            if not callable(factory):
                raise TypeError(
                    f"topology {tn!r} must be a width -> workers factory "
                    "when n_workers is given (got a concrete sequence)")
            for w in n_workers:
                topo_items.append((f"{tn}/w{int(w)}",
                                   tuple(factory(int(w)))))
    cells = tuple(
        SweepCell(policy_name=pn, policy=pol, seed=int(s),
                  topology_name=tn, workers=ws)
        for (pn, pol), s, (tn, ws) in itertools.product(
            policies.items(), seeds, topo_items))
    return SweepGrid(cells=cells, n_events=n_events)

"""Vectorized experiment sweeps (`repro.sweep`).

The paper's headline claims are sweep-shaped -- grids over step-size
policies, seeds, worker counts and straggler regimes (Figs. 2-5).  This
package turns a whole grid into ONE compiled XLA program per bucket, and
(since PR 3) spreads the cell axis across every available device:

* ``policies``  -- ``PolicyParams`` / ``ParamPolicy``: step-size policies as
  vmappable data (``lax.switch`` dispatch), arithmetic-identical to the
  ``core.stepsize`` dataclasses.
* ``grid``      -- ``SweepGrid`` / ``make_grid`` / ``standard_topologies``:
  the cartesian product of policies x seeds x topologies (x worker counts;
  ragged grids are bucketed by padded width with ``active_workers`` masks),
  and the stacked tensors that feed the runners.
* ``runners``   -- ``sweep_piag`` / ``sweep_bcd`` / ``sweep_fedasync`` /
  ``sweep_fedbuff`` (and ``make_sweep_*`` builders): ``vmap`` of the jitted
  trace generators (``core.engine.trace_scan``,
  ``federated.events.federated_trace_scan``) composed with the shared solver
  scan cores; one compile per bucket, B cells, bit-identical rows to solo
  runs.  The federated sweeps fuse client round-trip simulation with the
  server scan under the same jit (``reference=True`` falls back to the
  heapq twin).
* ``shard``     -- ``sharded_sweep_*``: the same cell programs with the cell
  axis partitioned across a ``("cells",)`` or 2-D ``("cells", "data")``
  device mesh via ``shard_map`` (donated input buffers, round-robin batch
  padding; 2-D meshes additionally psum per-worker gradients over the data
  axis -- see ``repro.mesh``) -- mega-grids at device-count scaling.

Quick taste::

    from repro.core import Adaptive1, Adaptive2, L1, make_logreg
    from repro.sweep import (make_grid, standard_topology_factories,
                             sweep_piag_logreg)

    prob = make_logreg(800, 100, n_workers=8, seed=0)
    grid = make_grid(
        policies={"a1": Adaptive1(gamma_prime=0.99 / prob.L),
                  "a2": Adaptive2(gamma_prime=0.99 / prob.L)},
        seeds=range(8),
        topologies=standard_topology_factories(),
        n_events=2000,
        n_workers=[4, 8])          # ragged: bucketed + masked automatically
    res = sweep_piag_logreg(prob, grid, L1(lam=prob.lam1))  # (128, 2000)
"""
from .cache import (clear_program_cache, program_cache_stats)
from .grid import (SweepBucket, SweepCell, SweepGrid, make_grid,
                   measure_tau_bar, next_pow2, standard_topologies,
                   standard_topology_factories)
from .policies import POLICY_IDS, ParamPolicy, PolicyParams, policy_params, stack_params
from .runners import (make_sweep_bcd, make_sweep_fedasync,
                      make_sweep_fedasync_fused, make_sweep_fedbuff,
                      make_sweep_piag, measure_fed_tau_bar,
                      pick_grad_layout, resolve_grid_horizon, run_bucketed,
                      sweep_bcd, sweep_bcd_logreg, sweep_fedasync,
                      sweep_fedasync_problem, sweep_fedbuff,
                      sweep_fedbuff_problem, sweep_piag, sweep_piag_logreg)
from .shard import (cell_mesh, grid_mesh, make_sharded_sweep_bcd,
                    make_sharded_sweep_piag, mesh_topology, round_robin_pad,
                    shard_cells, sharded_sweep_bcd, sharded_sweep_fedasync,
                    sharded_sweep_fedbuff, sharded_sweep_piag,
                    sharded_sweep_piag_logreg)

__all__ = [
    "SweepBucket", "SweepCell", "SweepGrid", "make_grid", "measure_tau_bar",
    "next_pow2", "standard_topologies", "standard_topology_factories",
    "clear_program_cache", "program_cache_stats", "measure_fed_tau_bar",
    "resolve_grid_horizon", "pick_grad_layout",
    "POLICY_IDS", "ParamPolicy", "PolicyParams", "policy_params",
    "stack_params", "make_sweep_bcd", "make_sweep_fedasync",
    "make_sweep_fedasync_fused", "make_sweep_fedbuff", "make_sweep_piag",
    "run_bucketed", "sweep_bcd", "sweep_bcd_logreg", "sweep_fedasync",
    "sweep_fedasync_problem", "sweep_fedbuff", "sweep_fedbuff_problem",
    "sweep_piag", "sweep_piag_logreg",
    "cell_mesh", "grid_mesh", "mesh_topology",
    "make_sharded_sweep_bcd", "make_sharded_sweep_piag",
    "round_robin_pad", "shard_cells", "sharded_sweep_bcd",
    "sharded_sweep_fedasync", "sharded_sweep_fedbuff", "sharded_sweep_piag",
    "sharded_sweep_piag_logreg",
]

"""Device-sharded mega-grid benchmark: a >= 512-cell policy x seed x
topology x n_workers PIAG grid, single-device batched vs sharded across
forced host devices.

Three timed paths over the SAME cells (same service-time matrices, same
policies, bucketed by padded worker count exactly as ``repro.sweep`` does):

* ``single``   -- the PR 2 path: one ``jit(vmap(cell))`` program per bucket
                  on one device.
* ``sharded1`` -- the shard_map path over a 1-device mesh (measures the
                  shard_map overhead in isolation).
* ``shardedN`` -- the shard_map path over every device: the cell axis
                  round-robin-padded to a device multiple and partitioned,
                  stacked service-time tensors donated.

Everything runs in this one process: a child process that needs the
device cannot start once this one holds it.

Emits ``BENCH_mega_grid.json``.  Run with forced host devices (done
automatically when this module is imported before jax, e.g. ``python -m
benchmarks.mega_grid``):

    PYTHONPATH=src python -m benchmarks.mega_grid \
        [--events N] [--seeds N] [--widths 4,8] [--out PATH]
"""
from __future__ import annotations

import os
import sys

# must precede ANY jax import in the process: forced host devices are fixed
# at backend init (no-op if the operator already set a device count)
_FLAG = "xla_force_host_platform_device_count"
if "jax" not in sys.modules and _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --{_FLAG}={os.environ.get('MEGA_GRID_DEVICES', '8')}").strip()

import argparse
import json
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import clipped_summary
from repro.core import (Adaptive1, Adaptive2, FixedStepSize, L1,
                        SunDengFixed, make_logreg)
from repro.core.engine import WorkerModel, trace_scan, sample_service_times
from repro.core.piag import piag_scan
from repro.core.stepsize import auto_horizon
from repro.mesh import cell_axis_size, grid_mesh
from repro.sweep import (cell_mesh, make_grid, make_sharded_sweep_piag,
                         make_sweep_piag, measure_tau_bar, round_robin_pad,
                         run_bucketed, sharded_sweep_piag,
                         standard_topology_factories)
from repro.sweep.runners import _slice_workers

from .common import emit


def _host_cores() -> int:
    """Physical parallelism actually granted to this process.  Forced host
    DEVICES are XLA-level threads: on a 1-core container they multiplex a
    single core and no sharded layout can beat a narrower one, so the
    speedup gates (never the bitwise-equivalence gates) key on this."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


def build_mega_grid(widths, n_seeds, n_events, gp):
    seeds = list(range(n_seeds))
    topos = standard_topology_factories()
    tau_bar = measure_tau_bar(
        {f"{tn}/w{w}": f(w) for tn, f in topos.items() for w in widths},
        seeds, n_events)
    policies = {
        "adaptive1": Adaptive1(gamma_prime=gp, alpha=0.9),
        "adaptive2": Adaptive2(gamma_prime=gp),
        "fixed": FixedStepSize(gamma_prime=gp, tau_bound=tau_bar),
        "fixed_sun_deng": SunDengFixed(gamma_prime=gp, tau_bound=tau_bar),
    }
    return make_grid(policies, seeds, topos, n_events,
                     n_workers=list(widths)), tau_bar


class BucketedRunner:
    """Pre-built per-bucket programs + pre-stacked inputs, so repeated calls
    time execution (warm) instead of rebuild+retrace.  ``mesh=None`` is the
    plain single-device path; otherwise shard_map over the mesh (inputs are
    re-uploaded per call because the sharded program donates them).

    ``horizon`` is the step-size window-buffer size: the mega-grid now runs
    on the measured-delay sizing (``auto_horizon(tau_bar)``) -- rows stay
    bitwise-equal to the old 4096 default (no delay exceeds the measured
    bound by construction), with a 4096/H x leaner per-cell scan carry."""

    def __init__(self, problem, grid, prox, mesh=None, horizon=4096):
        Aw, bw = problem.worker_slices()
        x0 = jnp.zeros((problem.dim,), jnp.float32)
        loss = lambda x, A, b: problem.worker_loss(x, A, b)
        self.grid, self.mesh = grid, mesh
        self.plan = {}
        for b in grid.buckets():
            wd = _slice_workers((Aw, bw), b.width)
            masked = not b.uniform
            if mesh is None:
                fn = make_sweep_piag(loss, x0, wd, prox, objective=problem.P,
                                     masked=masked, horizon=horizon)
                idx = None
            else:
                fn = make_sharded_sweep_piag(loss, x0, wd, prox,
                                             objective=problem.P,
                                             masked=masked, mesh=mesh,
                                             horizon=horizon)
                # pad to the CELLS-axis size, not the device count: on a
                # 2-D (cells, data) mesh the data axis replicates the batch
                idx = round_robin_pad(len(b.grid), cell_axis_size(mesh))
            T = b.grid.service_times(b.width)
            act = b.grid.active_masks(b.width)
            pp = b.grid.policy_params()
            self.plan[b.width] = (fn, masked, idx, T, act, pp)

    def __call__(self):
        def run_bucket_cached(b):
            fn, masked, idx, T, act, pp = self.plan[b.width]
            args = (jnp.asarray(T),) + (
                (jnp.asarray(act),) if masked else ()) + (pp,)
            if idx is not None:
                args = tuple(
                    jax.tree_util.tree_map(lambda x: jnp.asarray(x)[idx], a)
                    for a in args)
            out = fn(*args)
            if idx is not None:
                out = jax.tree_util.tree_map(lambda x: x[:len(b.grid)], out)
            return out

        return jax.block_until_ready(
            run_bucketed(self.grid, run_bucket_cached))


def _time(runner):
    t0 = time.perf_counter()
    res = runner()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = runner()
    warm = time.perf_counter() - t0
    return cold, warm, res


def run_2d(n_events: int = 120, n_cells: int = 4, samples_per_worker: int = 2048,
           dim: int = 384, data_shards: int = 2) -> dict:
    """1-D vs 2-D mesh on a transformer-preset per-cell workload.

    Few big cells (``dim`` matches the 25m launch preset's d_model=384;
    thousands of samples per worker) -- the regime where the per-event
    worker gradient dominates and extra devices on a second ``data`` axis
    pay for themselves.  Both paths use the SAME ``n_cells``-wide cells
    axis; the 2-D mesh adds ``data_shards`` devices per cell shard for
    data-parallel gradients (``pmean_grad``).  Rows must stay bitwise on
    taus/gammas; the gate (>= 8 devices) requires the 2-D warm time to
    beat 1-D."""
    n_dev = len(jax.devices())
    if n_dev < n_cells * data_shards:
        return {"skipped": f"needs {n_cells * data_shards} devices, "
                           f"have {n_dev}"}
    n_workers = 8
    prob = make_logreg(n_workers * samples_per_worker, dim,
                       n_workers=n_workers, seed=0)
    gp = 0.99 / prob.L
    prox = L1(lam=prob.lam1)
    grid = make_grid(
        policies={"adaptive1": Adaptive1(gamma_prime=gp, alpha=0.9)},
        seeds=list(range(n_cells)),
        topologies={"uniform": [WorkerModel() for _ in range(n_workers)]},
        n_events=n_events)
    loss = lambda x, A, b: prob.worker_loss(x, A, b)
    obj = prob.P
    x0 = jnp.zeros((prob.dim,), jnp.float32)
    wd = prob.worker_slices()
    mesh_1d = grid_mesh((n_cells,))
    mesh_2d = grid_mesh((n_cells, data_shards))
    emit("mega_grid/2d_config", 0.0,
         f"cells={len(grid)};events={n_events};dim={dim};"
         f"samples_per_worker={samples_per_worker};"
         f"mesh_1d=({n_cells},);mesh_2d=({n_cells},{data_shards})")

    def runner(mesh):
        return lambda: jax.block_until_ready(sharded_sweep_piag(
            loss, x0, wd, grid, prox, objective=obj, mesh=mesh))

    cold_1d, warm_1d, res_1d = _time(runner(mesh_1d))
    emit("mega_grid/2d_mesh1d", cold_1d * 1e6, f"warm_us={warm_1d * 1e6:.1f}")
    cold_2d, warm_2d, res_2d = _time(runner(mesh_2d))
    emit("mega_grid/2d_mesh2d", cold_2d * 1e6, f"warm_us={warm_2d * 1e6:.1f}")
    speedup_warm = warm_1d / warm_2d
    emit("mega_grid/2d_speedup", 0.0,
         f"warm={speedup_warm:.2f}x;cold={cold_1d / cold_2d:.2f}x")

    taus_equal = bool(np.array_equal(np.asarray(res_1d.taus),
                                     np.asarray(res_2d.taus)))
    gammas_equal = bool(np.array_equal(np.asarray(res_1d.gammas),
                                       np.asarray(res_2d.gammas)))
    obj_diff = float(np.max(np.abs(np.asarray(res_1d.objective)
                                   - np.asarray(res_2d.objective))))
    ok = taus_equal and gammas_equal and obj_diff <= 1e-4
    emit("mega_grid/2d_equivalence", 0.0,
         f"taus_bitwise={taus_equal};gammas_bitwise={gammas_equal};"
         f"max_objective_diff={obj_diff:.2e};ok={ok}")
    return {
        "cells": len(grid), "n_events": n_events, "dim": dim,
        "samples_per_worker": samples_per_worker,
        "host_cores": _host_cores(),
        "mesh_1d": [n_cells], "mesh_2d": [n_cells, data_shards],
        "seconds_cold_1d": cold_1d, "seconds_warm_1d": warm_1d,
        "seconds_cold_2d": cold_2d, "seconds_warm_2d": warm_2d,
        "speedup_2d_vs_1d_warm": speedup_warm,
        "equivalence": {"taus_bitwise_equal": taus_equal,
                        "gammas_bitwise_equal": gammas_equal,
                        "max_objective_diff": obj_diff, "ok": ok},
    }


def run(n_events: int = 300, n_seeds: int = 16, widths=(4, 8),
        loop_cells: int = 6, out: str = "BENCH_mega_grid.json") -> dict:
    n_dev = len(jax.devices())
    prob = make_logreg(480, 60, n_workers=max(widths), seed=0)
    gp = 0.99 / prob.L
    prox = L1(lam=prob.lam1)
    grid, tau_bar = build_mega_grid(widths, n_seeds, n_events, gp)
    B = len(grid)
    horizon = auto_horizon(tau_bar)  # measured-delay sizing, bitwise rows
    emit("mega_grid/config", 0.0,
         f"cells={B};events={n_events};widths={list(widths)};"
         f"devices={n_dev};tau_bar={tau_bar};horizon={horizon}")

    single = BucketedRunner(prob, grid, prox, mesh=None, horizon=horizon)
    cold_1, warm_1, res_single = _time(single)
    emit("mega_grid/single_device", cold_1 * 1e6, f"warm_us={warm_1 * 1e6:.1f}")

    sharded1 = BucketedRunner(prob, grid, prox,
                              mesh=cell_mesh(jax.devices()[:1]),
                              horizon=horizon)
    cold_s1, warm_s1, _ = _time(sharded1)
    emit("mega_grid/sharded_1dev", cold_s1 * 1e6,
         f"warm_us={warm_s1 * 1e6:.1f}")

    shardedN = BucketedRunner(prob, grid, prox, mesh=cell_mesh(),
                              horizon=horizon)
    cold_sN, warm_sN, res_shard = _time(shardedN)
    speedup_cold = cold_1 / cold_sN
    speedup_warm = warm_1 / warm_sN
    emit("mega_grid/sharded_all", cold_sN * 1e6,
         f"warm_us={warm_sN * 1e6:.1f};devices={n_dev}")
    emit("mega_grid/speedup_vs_single", 0.0,
         f"cold={speedup_cold:.2f}x;warm={speedup_warm:.2f}x")
    emit("mega_grid/device_scaling", 0.0,
         f"warm_1dev_mesh={warm_s1:.3f}s;warm_{n_dev}dev={warm_sN:.3f}s;"
         f"scaling={warm_s1 / warm_sN:.2f}x")

    # ---- row equivalence: sharded == single-device, spot-check vs solo ----
    max_diff = float(np.max(np.abs(np.asarray(res_single.objective)
                                   - np.asarray(res_shard.objective))))
    taus_equal = bool(np.array_equal(np.asarray(res_single.taus),
                                     np.asarray(res_shard.taus)))
    Aw, bw = prob.worker_slices()
    x0 = jnp.zeros((prob.dim,), jnp.float32)
    solo_diff = 0.0
    for i in np.unique(np.linspace(0, B - 1, loop_cells).round().astype(int)):
        c = grid.cells[i]
        T = sample_service_times(c.workers, n_events + 1, seed=c.seed)
        tr = trace_scan(jnp.asarray(T))
        w = c.n_workers
        solo = jax.jit(lambda ev, _w=w, _p=c.policy: piag_scan(
            lambda x, A, b: prob.worker_loss(x, A, b), x0,
            (Aw[:_w], bw[:_w]), ev, _p, prox,
            objective=prob.P))((tr.worker, tr.tau_max))
        solo_diff = max(solo_diff, float(np.max(np.abs(
            np.asarray(solo.objective)
            - np.asarray(res_shard.objective[i])))))
    rows_ok = taus_equal and max_diff <= 1e-5 and solo_diff <= 1e-4
    emit("mega_grid/equivalence", 0.0,
         f"sharded_vs_single_max_diff={max_diff:.2e};"
         f"solo_rows_max_diff={solo_diff:.2e};ok={rows_ok}")

    # ---- clipped-horizon diagnostic now visible per cell ------------------
    n_clipped = clipped_summary(res_shard.clipped)["cells_clipped"]
    emit("mega_grid/clipped_cells", 0.0, f"cells_with_clipping={n_clipped}")

    # ---- 2-D (cells, data) mesh on a transformer-sized workload ----------
    two_d = run_2d()

    payload = {
        "bench": "mega_grid",
        "devices": n_dev,
        "host_cores": _host_cores(),
        "cells": B,
        "n_events": n_events,
        "widths": list(widths),
        "buckets": [{"width": b.width, "cells": len(b.grid)}
                    for b in grid.buckets()],
        "tau_bar": tau_bar,
        "horizon": horizon,
        "single_device_seconds_cold": cold_1,
        "single_device_seconds_warm": warm_1,
        "sharded_1dev_seconds_cold": cold_s1,
        "sharded_1dev_seconds_warm": warm_s1,
        "sharded_seconds_cold": cold_sN,
        "sharded_seconds_warm": warm_sN,
        "speedup_sharded_vs_single_cold": speedup_cold,
        "speedup_sharded_vs_single_warm": speedup_warm,
        "device_scaling_warm_1_to_N": warm_s1 / warm_sN,
        "cells_with_horizon_clipping": n_clipped,
        "equivalence": {"taus_bitwise_equal": taus_equal,
                        "sharded_vs_single_max_objective_diff": max_diff,
                        "solo_rows_checked": int(loop_cells),
                        "solo_rows_max_objective_diff": solo_diff,
                        "ok": rows_ok},
        "two_d": two_d,
    }
    Path(out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}: {B} cells on {n_dev} devices, sharded speedup "
          f"cold {speedup_cold:.2f}x / warm {speedup_warm:.2f}x, "
          f"equivalence ok={rows_ok}")
    return payload


def _gate_2d(two_d: dict, n_dev: int) -> None:
    """CI gate for the 2-D section: bitwise rows always; measured warm
    speedup over the 1-D mesh when the full 8-device mesh ran AND the host
    has cores beyond the cells axis for the data axis to use."""
    if "skipped" in two_d:
        print(f"2-D mesh section skipped: {two_d['skipped']}")
        return
    if not two_d["equivalence"]["ok"]:
        raise SystemExit("2-D mesh equivalence failed: "
                         f"{two_d['equivalence']}")
    cores = _host_cores()
    if (n_dev >= 8 and cores > two_d["mesh_1d"][0]
            and two_d["speedup_2d_vs_1d_warm"] <= 1.0):
        raise SystemExit(
            f"2-D (cells, data) mesh failed to beat the 1-D mesh on a "
            f"{cores}-core host: warm {two_d['seconds_warm_2d']:.2f}s vs "
            f"{two_d['seconds_warm_1d']:.2f}s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--widths", default="4,8",
                    help="comma-separated worker counts (the ragged axis)")
    ap.add_argument("--loop-cells", type=int, default=6,
                    help="solo spot-check rows")
    ap.add_argument("--only-2d", action="store_true",
                    help="run just the 2-D (cells, data) mesh comparison "
                         "and its gate (CI multi-device lane); writes no "
                         "artifact")
    ap.add_argument("--out", default="BENCH_mega_grid.json")
    a = ap.parse_args()
    if a.only_2d:
        two_d = run_2d()
        _gate_2d(two_d, len(jax.devices()))
        if "skipped" not in two_d:
            print(f"2-D mesh: warm {two_d['seconds_warm_2d']:.2f}s vs 1-D "
                  f"{two_d['seconds_warm_1d']:.2f}s "
                  f"({two_d['speedup_2d_vs_1d_warm']:.2f}x), "
                  f"equivalence ok={two_d['equivalence']['ok']}")
        return
    widths = tuple(int(w) for w in a.widths.split(","))
    payload = run(n_events=a.events, n_seeds=a.seeds, widths=widths,
                  loop_cells=a.loop_cells, out=a.out)
    if not payload["equivalence"]["ok"]:
        raise SystemExit("equivalence spot-check failed")
    if (payload["devices"] > 1 and _host_cores() > 1
            and payload["speedup_sharded_vs_single_warm"] <= 1.0):
        raise SystemExit("sharded path failed to beat single-device")
    _gate_2d(payload["two_d"], payload["devices"])


if __name__ == "__main__":
    main()

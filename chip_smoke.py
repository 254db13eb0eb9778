"""Bring-up check: the sweep engine and the asynchronous trainer on a TPU.

    python chip_smoke.py               # one chip: sweep and trainer phases
    python chip_smoke.py --four-chips  # four chips: sharded vs batched only

One process drives every phase (a chip belongs to one process).  Each
phase prints its findings on lines of its own.  A failed check, or a
phase that raises, is printed as ``FAIL ...`` and the run goes on to the
next check, so one run reports every fault; any failure makes the script
exit non-zero and print no result.  The last line of a passing run is one
JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Seconds printed here are set-up and smoke readings of one run, not
benchmark metrics.

Phases (one chip):

* sweep -- ``repro.api.run`` on the paper's rcv1-like shapes
  (``configs/paper_logreg.py``: n=4000, d=800, 10 workers) for piag, bcd,
  fedasync and fedbuff, ``backend='batched'``, ``horizon='auto'``, once with
  ``engine='scan'`` and once with ``engine='fused'``.  Fused rows must equal
  scan rows bit for bit; the chip's event traces must equal the host heapq
  reference on the same service-time matrices; PIAG's and BCD's adaptive1
  runs must end below their initial gap to the centralized optimum (the
  federated gaps are printed); the compiled fused program
  must hold the Pallas kernel (``tpu_custom_call``).
* trainer -- ``launch.train.run_training`` on mamba2-780m at its published
  widths, 2 workers, batch 4, seq 1024, adaptive1: finite loss that starts
  near ln(vocab) and falls, taus equal to the host trace's, gammas equal
  to the policy's on the host; prints the device's peak memory.

``--four-chips`` runs only the sharded sweep backend on a ``(4,)`` cells
mesh and a ``(2, 2)`` ``(cells, data)`` mesh for piag and fedbuff, against
the batched backend on one device in the same process.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# rcv1-like workload of configs/paper_logreg.py
PAPER = dict(n_samples=4000, dim=800, sparse_like=True, lam1=1e-5, lam2=1e-4,
             seed=0)
N_WORKERS = 10
SOLVERS = ("piag", "bcd", "fedasync", "fedbuff")
# solvers whose adaptive1 run the paper's principle (8) guarantees to
# converge; the federated servers mix stale client models, which it does not
# cover, so their gaps are printed but not checked
CONVERGENT = ("piag", "bcd")
POLICIES = ("adaptive1", "adaptive2", "fixed")
SEEDS = (0, 1, 2, 3)
EVENTS = 1000
# sharded vs batched float leaves: the two backends are different XLA
# programs, and the 2-D mesh psums partial gradients over "data", which
# reorders a float32 sum, so iterates and objectives agree to rounding
OBJECTIVE_RTOL, OBJECTIVE_ATOL = 1e-4, 1e-6
# trainer gammas vs the policy run on the host CPU: the host's compiler may
# contract the running window sum's multiply-add into an FMA
GAMMA_RTOL = 1e-6


FAILURES: list = []


def check(ok, what: str) -> bool:
    """Record a failed check (printed at once) and go on; returns ``ok``."""
    if not ok:
        FAILURES.append(what)
        print(f"FAIL {what}", flush=True)
    return bool(ok)


def guarded(what: str, fn, *args) -> None:
    """Run one independent unit of a phase; an exception is a failure."""
    try:
        fn(*args)
    except Exception:  # noqa: BLE001 -- report it, then run the next unit
        traceback.print_exc()
        check(False, f"{what} raised")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _spec(solver: str, engine: str, backend: str = "batched", mesh=None,
          n_workers=(N_WORKERS,), events: int = 0):
    from repro import api
    fed = solver in ("fedasync", "fedbuff")
    return api.ExperimentSpec(
        problem=api.ProblemSpec(kind="logreg", params=PAPER),
        solver=api.SolverSpec(name=solver, horizon="auto",
                              **({"buffer_size": 4, "eta": 0.5}
                                 if solver == "fedbuff" else {})),
        topology=api.TopologySpec(kind="edge" if fed else "standard",
                                  n_workers=tuple(n_workers)),
        policies=api.PolicyGridSpec(names=POLICIES, seeds=SEEDS),
        execution=api.ExecutionSpec(backend=backend, engine=engine,
                                    mesh=mesh),
        n_events=events or EVENTS)


def _leaves(raw):
    import jax
    import numpy as np
    return [(path, np.asarray(leaf)) for path, leaf in
            jax.tree_util.tree_flatten_with_path(raw)[0]]


def _name(path) -> str:
    import jax
    return jax.tree_util.keystr(path)


def _bitwise_equal(a, b, what: str) -> bool:
    import numpy as np
    la, lb = _leaves(a), _leaves(b)
    ok = check(len(la) == len(lb),
               f"{what}: leaf count {len(la)} != {len(lb)}")
    for (pa, xa), (_, xb) in zip(la, lb):
        if xa.shape != xb.shape:
            ok = check(False, f"{what}: {_name(pa)} shape {xa.shape} != "
                       f"{xb.shape}")
        elif not np.array_equal(xa, xb):
            diff = np.abs(xa.astype(np.float64) - xb.astype(np.float64))
            ok = check(False, f"{what}: {_name(pa)} differs in "
                       f"{int(np.sum(xa != xb))}/{xa.size} elements, max "
                       f"abs diff {float(np.nanmax(diff)):.3e}")
    return ok


# --------------------------------------------------------------- sweep ----

def _check_traces(res, phase: str) -> None:
    """Chip ``trace_scan`` == host heapq reference on the same matrices,
    and the piag rows consumed exactly those delays."""
    import jax
    import numpy as np
    from repro.core.engine import simulate_parameter_server, trace_scan
    grid = res.grid
    T = np.asarray(grid.service_times(N_WORKERS))
    chip = jax.device_get(jax.jit(jax.vmap(trace_scan))(T))
    for b, cell in enumerate(grid.cells):
        ref = simulate_parameter_server(N_WORKERS, grid.n_events,
                                        list(cell.workers),
                                        service_times=T[b])
        ok = all([check(np.array_equal(getattr(chip, f)[b], getattr(ref, f)),
                        f"trace_scan {f} != heapq reference in cell {b}")
                  for f in ("worker", "read_at", "tau", "tau_max")])
        if not ok:
            return
    if check(np.array_equal(np.asarray(res.taus), chip.tau_max),
             "piag taus != the chip trace's tau_max"):
            say(phase, f"trace_scan == heapq reference: {len(grid)} cells x "
            f"{grid.n_events} events (worker, read_at, tau, tau_max); "
            f"max tau {int(chip.tau_max.max())}")


def _fused_program_text(res) -> str:
    """Compiled text of the batched piag program with the fused engine."""
    import jax.numpy as jnp
    from repro import api
    from repro.sweep import make_sweep_piag
    r = api.resolve(res.spec)
    problem = r.problem
    fn = make_sweep_piag(lambda x, A, b: problem.worker_loss(x, A, b),
                         jnp.zeros((problem.dim,), jnp.float32),
                         problem.worker_slices(), r.prox,
                         objective=problem.P, horizon=res.horizon,
                         engine="fused")
    T = jnp.asarray(res.grid.service_times(N_WORKERS))
    return fn.lower(T, res.grid.policy_params()).compile().as_text()


def _sweep_solver(solver: str, p_star: float, gap0: float) -> None:
    import numpy as np
    from repro import api
    phase = "sweep"
    runs = {}
    for engine in ("scan", "fused"):
        res = api.run(_spec(solver, engine))
        rec = res.telemetry
        say(phase, f"{solver}/{engine}: {len(res)} cells x "
            f"{res.n_events} events, horizon={res.horizon} (auto), "
            f"set-up compile {rec.compile_ms / 1e3:.2f}s, smoke warm "
            f"{rec.warm_ms / 1e3:.2f}s (not a metric)")
        runs[engine] = res
    if _bitwise_equal(runs["scan"].raw, runs["fused"].raw,
                       f"{solver} fused vs scan"):
        say(phase, f"{solver}: engine='fused' rows == engine='scan' rows, "
            f"every leaf bitwise")
    res = runs["fused"]
    a1 = [b for b, c in enumerate(res.grid.cells)
          if c.policy_name == "adaptive1"]
    final = np.asarray(res.objective)[a1, -1] - p_star
    check(np.all(np.isfinite(final)), f"{solver}: non-finite objective")
    if solver in CONVERGENT:
        check(np.all(final < gap0),
              f"{solver}: adaptive1 final gap {final.max():.6f} not below "
              f"initial gap {gap0:.6f}")
    say(phase, f"{solver}: adaptive1 final gap mean {final.mean():.6f} "
        f"max {final.max():.6f}, initial {gap0:.6f} ({len(a1)} cells"
        f"{'' if solver in CONVERGENT else '; reported, not checked'})")
    if solver == "piag":
        guarded("trace check", _check_traces, res, phase)
        n_kernels = _fused_program_text(res).count("tpu_custom_call")
        if check(n_kernels > 0, "compiled fused program holds no "
                 "tpu_custom_call: the Pallas kernel did not compile"):
            say(phase, f"compiled fused piag program: {n_kernels} "
                f"tpu_custom_call site(s)")


def sweep_phase() -> None:
    import numpy as np
    from repro import api
    from repro.core.problems import solve_centralized
    r = api.resolve(_spec("piag", "scan"))
    _, trace = solve_centralized(r.problem, r.prox)
    p_star = float(np.asarray(trace)[-1])
    gap0 = float(r.problem.P(np.zeros((r.problem.dim,), np.float32))) - p_star
    say("sweep", f"FISTA P*={p_star:.6f}; initial gap P(0)-P*={gap0:.6f}")
    for solver in SOLVERS:
        guarded(f"sweep {solver}", _sweep_solver, solver, p_star, gap0)


# ------------------------------------------------------------- trainer ----

def trainer_phase() -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.stepsize import make_policy
    from repro.launch.train import event_trace, run_training
    phase = "trainer"
    cfg = get_config("mamba2-780m")
    steps, workers, lr = 8, 2, 3e-3
    t0 = time.perf_counter()
    log = run_training(cfg, steps=steps, batch=4, seq=1024,
                       policy_name="adaptive1", lr=lr, n_workers=workers,
                       log_every=1)
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if check(peak is not None, "device reports no peak_bytes_in_use"):
        say(phase, f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB); set-up and "
            f"smoke wall {wall:.1f}s (not a metric)")
    losses = np.asarray([r["loss"] for r in log])
    check(len(log) == steps, f"{len(log)} logged events, expected {steps}")
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(cfg.vocab)) < 1.0,
          f"first loss {losses[0]:.4f} not within 1 of ln(vocab)="
          f"{math.log(cfg.vocab):.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    say(phase, f"{cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab}: {steps} write events, {workers} workers, "
        f"losses {[round(float(v), 4) for v in losses]} (ln vocab "
        f"{math.log(cfg.vocab):.4f})")
    taus = np.asarray([r["tau"] for r in log])
    host = event_trace(workers, steps, seed=0)
    check(np.array_equal(taus, host.tau),
          f"logged taus {taus.tolist()} != host k - s_read "
          f"{host.tau.tolist()}")
    gammas = np.asarray([r["gamma"] for r in log], np.float32)
    with jax.default_device(jax.devices("cpu")[0]):
        ref = np.asarray(make_policy("adaptive1", lr).run(taus))
    check(np.allclose(gammas, ref, rtol=GAMMA_RTOL, atol=0.0),
          f"gammas {gammas.tolist()} != host policy {ref.tolist()}")
    say(phase, f"taus {taus.tolist()} vs host trace k - s_read "
        f"{host.tau.tolist()}; gammas vs host policy: max abs diff "
        f"{float(np.max(np.abs(gammas - ref))):.3e}")


# ---------------------------------------------------------- four chips ----

def _compare_sharded(batched, sharded, what: str) -> bool:
    """Integer leaves, taus and gammas bitwise; float leaves within the
    stated tolerance, with each float leaf's largest difference printed."""
    import numpy as np
    la, lb = _leaves(batched), _leaves(sharded)
    ok = check(len(la) == len(lb), f"{what}: leaf count differs")
    for (path, xa), (_, xb) in zip(la, lb):
        name = _name(path)
        if not check(xa.shape == xb.shape, f"{what}: {name} shape differs"):
            ok = False
            continue
        if (np.issubdtype(xa.dtype, np.integer) or "gammas" in name
                or "weights" in name or "taus" in name):
            ok &= check(np.array_equal(xa, xb), f"{what}: {name} not bitwise")
            continue
        diff = float(np.max(np.abs(xa - xb)))
        ok &= check(np.allclose(xa, xb, rtol=OBJECTIVE_RTOL,
                                atol=OBJECTIVE_ATOL),
                    f"{what}: {name} max diff {diff:.3e} beyond "
                    f"rtol={OBJECTIVE_RTOL} atol={OBJECTIVE_ATOL}")
        say("four-chips", f"{what}: {name} "
            f"{'bitwise equal' if diff == 0 else 'max diff %.3e' % diff}")
    return ok


def _four_chip_solver(solver: str, widths) -> None:
    import jax
    from repro import api
    from repro.mesh import grid_mesh
    phase, devs = "four-chips", jax.devices()
    batched = api.run(_spec(solver, "scan", n_workers=widths,
                            events=EVENTS // 2))
    say(phase, f"{solver}/batched on {devs[0]}: {len(batched)} cells, "
        f"set-up compile {batched.telemetry.compile_ms / 1e3:.2f}s")
    for shape in ((4,), (2, 2)):
        res = api.run(_spec(solver, "scan", backend="sharded",
                            mesh=grid_mesh(shape, devs[:4]),
                            n_workers=widths, events=EVENTS // 2))
        say(phase, f"{solver}/sharded mesh {shape}: set-up compile "
            f"{res.telemetry.compile_ms / 1e3:.2f}s, smoke warm "
            f"{res.telemetry.warm_ms / 1e3:.2f}s (not a metric)")
        if _compare_sharded(batched.raw, res.raw, f"{solver} {shape}"):
            say(phase, f"{solver} {shape}: taus, gammas and integer leaves "
                f"bitwise equal to batched; floats within rtol="
                f"{OBJECTIVE_RTOL} atol={OBJECTIVE_ATOL}")


def _one_cell_per_shard() -> None:
    """Four cells on the four-wide cells axis (and two per shard on the
    two-wide one of the 2-D mesh)."""
    import jax
    from repro import api
    from repro.mesh import grid_mesh
    small = _spec("piag", "scan", events=EVENTS // 2).replace(
        policies=api.PolicyGridSpec(names=("adaptive1",), seeds=SEEDS),
        topology=api.TopologySpec(kind="standard", names=("hetero2",),
                                  n_workers=(N_WORKERS,)))
    batched = api.run(small)
    for shape in ((4,), (2, 2)):
        res = api.run(small.replace(execution=api.ExecutionSpec(
            backend="sharded", mesh=grid_mesh(shape, jax.devices()[:4]))))
        if _compare_sharded(batched.raw, res.raw,
                            f"piag {len(batched)} cells {shape}"):
            say("four-chips", f"piag {len(batched)} cells on mesh {shape} "
                f"({-(-len(batched) // shape[0])} per cells shard): "
                f"matches batched")


def four_chip_phase() -> None:
    import jax
    n = len(jax.devices())
    if not check(n >= 4, f"--four-chips needs 4 devices, found {n}"):
        return
    # ragged widths make two buckets, so the sharded results are stitched
    guarded("four-chips piag", _four_chip_solver, "piag", (5, N_WORKERS))
    guarded("four-chips fedbuff", _four_chip_solver, "fedbuff", (N_WORKERS,))
    guarded("four-chips one cell per shard", _one_cell_per_shard)


# ---------------------------------------------------------------- main ----

def _count_cache_hits() -> dict:
    import jax
    counts = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-batched sweep phase on "
                         "four chips")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", flush=True)
    if not check(device["platform"] == "tpu",
                 f"no TPU: JAX found platform {device['platform']!r}"):
        sys.exit(1)

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.dispatch import default_interpret
    if not check(not default_interpret(),
                 "Pallas kernels would run in interpret mode "
                 f"(REPRO_PALLAS_INTERPRET="
                 f"{os.environ.get('REPRO_PALLAS_INTERPRET')!r})"):
        sys.exit(1)
    cache_dir = enable_compile_cache()
    cache = _count_cache_hits()
    print(f"compile cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        guarded("four-chips phase", four_chip_phase)
    else:
        guarded("sweep phase", sweep_phase)
        guarded("trainer phase", trainer_phase)
    print(f"compile cache: {cache['hits']} hit(s), {cache['misses']} "
          f"miss(es); smoke wall {time.perf_counter() - t0:.1f}s "
          f"(not a metric)", flush=True)
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed:", *FAILURES, sep="\n  ",
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

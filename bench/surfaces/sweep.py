"""The sweep surface: policy x seed x regime grids of PIAG through
``repro.api.run``, the program's own entry point.

Configuration keys: ``problem`` (``logreg``), ``n_samples``, ``dim``,
``n_workers``, ``density``, ``lam1``, ``lam2`` and ``data_seed``; the data
set is generated on the device from ``data_seed`` and is the same in every
run, as a published data set is.  Traffic keys: ``solver``, ``policies``,
``seeds`` (grid seeds per run: ``seeds * n .. seeds * n + seeds - 1`` for
``--seed n``), ``regimes``, ``events``, ``check_per_policy`` (cells of each
policy that the reference replays) and ``limits`` (one per compared
number).  Engine, horizon and recording stride are the program's defaults.

The window runs whole grids until ``--seconds`` have passed; each grid
ends in ``block_until_ready`` inside ``api.run``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import work as work_mod
from bench.reference import piag as ref


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@partial(jax.jit, static_argnames=("n", "d", "n_workers", "density",
                                   "lam2"))
def make_data(key, *, n: int, d: int, n_workers: int, density: float,
              lam2: float):
    """MNIST-shaped logistic-regression data in one call on the device:
    dense non-negative features (a quarter non-zero, scaled to at most 1),
    labels from a planted model with noise; and the smoothness constants
    the program's step-size rule needs: the RMS over the worker shards of
    lambda_max(A_i^T A_i) / (4 N_i) + lam2, and the coordinate-wise one."""
    k_model, k_val, k_mask, k_noise, k_power = jax.random.split(key, 5)
    hi = jax.lax.Precision.HIGHEST
    x_star = jax.random.normal(k_model, (d,)) / np.sqrt(d)
    A = jnp.abs(jax.random.normal(k_val, (n, d))) * (
        jax.random.uniform(k_mask, (n, d)) < density)
    A = A / jnp.max(A)
    logits = jnp.dot(A, x_star, precision=hi) + 0.3 * jax.random.normal(
        k_noise, (n,))
    b = jnp.where(logits >= 0, 1.0, -1.0)
    rows = n // n_workers
    shards = A[:rows * n_workers].reshape(n_workers, rows, d)

    def lam_max(Ai, v):
        def it(v, _):
            w = jnp.dot(Ai.T, jnp.dot(Ai, v, precision=hi), precision=hi)
            return w / jnp.linalg.norm(w), jnp.linalg.norm(w)
        _, lams = jax.lax.scan(it, v / jnp.linalg.norm(v), None, length=200)
        return lams[-1]

    v0 = jax.random.normal(k_power, (d,))
    Ls = jax.vmap(lam_max, in_axes=(0, None))(shards, v0) / (4.0 * rows) + lam2
    L = jnp.sqrt(jnp.mean(Ls * Ls))
    Lhat = jnp.max(jnp.sum(A * A, axis=0)) / (4.0 * n) + lam2
    return A, b, L, Lhat


def make_problem(config: dict):
    """The configuration's problem as the program's own problem object."""
    from repro.core.problems import LogRegProblem
    if config["problem"] != "logreg":
        raise ValueError(f"no sweep problem {config['problem']!r}")
    A, b, L, Lhat = make_data(
        seed_key(int(config["data_seed"])), n=int(config["n_samples"]),
        d=int(config["dim"]), n_workers=int(config["n_workers"]),
        density=float(config["density"]), lam2=float(config["lam2"]))
    return LogRegProblem(A=A, b=b, lam1=float(config["lam1"]),
                         lam2=float(config["lam2"]), L=float(L),
                         Lhat=float(Lhat), n_workers=int(config["n_workers"]))


def grid_seeds(traffic: dict, seed: int):
    k = int(traffic["seeds"])
    return tuple(k * seed + i for i in range(k))


def make_spec(problem, config: dict, traffic: dict, seed: int):
    from repro import api
    return api.ExperimentSpec(
        problem=api.ProblemSpec(problem=problem),
        solver=api.SolverSpec(name=traffic["solver"]),
        topology=api.TopologySpec(kind="standard",
                                  names=tuple(traffic["regimes"]),
                                  n_workers=(int(config["n_workers"]),)),
        policies=api.PolicyGridSpec(names=tuple(traffic["policies"]),
                                    seeds=grid_seeds(traffic, seed)),
        n_events=int(traffic["events"]))


class State:
    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.problem = make_problem(config)
        self.spec = make_spec(self.problem, config, traffic, seed)
        self.results = []
        self._traces = None

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self.spec = make_spec(self.problem, self.config, self.traffic, seed)
        self.results, self._traces = [], None

    def traces(self):
        """The reference's event order of every cell of the grid, from task
        times it draws itself by each cell's regime, width and seed."""
        if self._traces is None:
            tasks = int(self.traffic["events"]) + 1
            topology_seed = self.spec.topology.seed
            self._traces = [ref.simulate(ref.service_times(
                ref.regime_workers(c.topology_name.split("/")[0],
                                   c.n_workers, topology_seed),
                tasks, c.seed)) for c in self.results[-1].grid.cells]
        return self._traces


def run_grid(state: State):
    from repro import api
    return api.run(state.spec)


def setup(config: dict, traffic: dict, seed: int) -> State:
    """Build the data and the grid, and run one whole grid, which compiles
    every program the window uses."""
    state = State(config, traffic, seed)
    run_grid(state)
    return state


def window(state: State, seconds: float, marks) -> dict:
    marks.start()
    while True:
        state.results.append(run_grid(state))
        if time.perf_counter() - marks.t_start >= seconds:
            break
    marks.stop()
    elapsed = marks.t_stop - marks.t_start
    res = state.results[-1]
    return {"seconds": elapsed, "grids": len(state.results),
            "cells": len(res), "events": res.n_events,
            "attempted": len(state.results) * len(res), "failed": 0}


def end_to_end(win: dict) -> dict:
    return {"cell_events_per_s":
            win["grids"] * win["cells"] * win["events"] / win["seconds"]}


def work(state: State, win: dict) -> dict:
    """Least bytes and operations of all grids of the window."""
    c = state.config
    one = work_mod.piag_grid_work(
        np.stack([w for w, _ in state.traces()]),
        n_samples=int(c["n_samples"]), dim=int(c["dim"]),
        n_workers=int(c["n_workers"]))
    return {"piag": {k: v * win["grids"] for k, v in one.items()}}


def program_outputs(state: State, cells) -> dict:
    """The checked grid's rows of ``cells``, on the host."""
    res = state.results[-1]
    idx = np.asarray(cells)
    return {"taus": np.asarray(res.taus)[idx],
            "gammas": np.asarray(res.gammas)[idx],
            "objective": np.asarray(res.objective)[idx],
            "x": np.asarray(res.x)[idx], "tau_bar": int(res.tau_bar)}


def reference_outputs(state: State, cells, *, dtype=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST) -> dict:
    """The plain reference on ``cells`` of the checked grid, in ``dtype``:
    float32 at the highest matmul precision is the reference; a lower one
    is the control."""
    res, traces = state.results[-1], state.traces()
    p, cells = state.problem, list(cells)
    tau_bar = max(int(t.max()) for _, t in traces)
    gamma_prime = 0.99 / p.L
    host = np.dtype(dtype).type
    steps = np.stack([ref.gammas(res.grid.cells[i].policy_name, gamma_prime,
                                 traces[i][1], tau_bar=tau_bar, dtype=host)
                      for i in cells])
    workers = np.stack([traces[i][0] for i in cells])
    obj, x = ref.replay(p.A, p.b, jnp.asarray(workers),
                        jnp.asarray(steps.astype(np.float32)),
                        n_workers=int(p.n_workers), lam1=float(p.lam1),
                        lam2=float(p.lam2), dtype=dtype, precision=precision)
    return {"taus": np.stack([traces[i][1] for i in cells]),
            "gammas": steps.astype(np.float32),
            "objective": np.asarray(obj, np.float32),
            "x": np.asarray(x, np.float32), "tau_bar": tau_bar,
            "gamma_prime": gamma_prime}


def numbers(prog: dict, refo: dict) -> dict:
    """Every number the check can compare, by name."""
    return {
        "tau_bar_gap": float(abs(prog["tau_bar"] - refo["tau_bar"])),
        "tau_mismatch": float(np.sum(prog["taus"] != refo["taus"])),
        "gamma_err": float(np.max(np.abs(prog["gammas"] - refo["gammas"]))
                           / refo["gamma_prime"]),
        "objective_rel": float(np.max(np.abs(prog["objective"]
                                             - refo["objective"])
                                      / np.abs(refo["objective"]))),
        "x_rel": float(np.max(np.linalg.norm(prog["x"] - refo["x"], axis=1)
                              / np.linalg.norm(refo["x"], axis=1))),
    }


def compare(prog: dict, refo: dict, limits: dict) -> list:
    """The numbers that the traffic's ``limits`` name, each with its
    limit."""
    got = numbers(prog, refo)
    return [(n, got[n], float(lim)) for n, lim in limits.items()]


def sample_cells(state: State, seed: int) -> list:
    """Cells the reference replays, drawn from the seed:
    ``check_per_policy`` of each policy."""
    rng = np.random.default_rng(seed)
    cells = state.results[-1].grid.cells
    out = []
    for name in state.traffic["policies"]:
        idx = [i for i, c in enumerate(cells) if c.policy_name == name]
        k = min(int(state.traffic["check_per_policy"]), len(idx))
        out.extend(sorted(rng.choice(idx, size=k, replace=False).tolist()))
    return out


def grids_differ(state: State) -> int:
    """Grids of the window whose rows are not bitwise those of the checked
    grid (every grid of a run is the same computation)."""
    last = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        state.results[-1].raw)]
    n = 0
    for res in state.results[:-1]:
        leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(res.raw)]
        n += not all(np.array_equal(a, b) for a, b in zip(leaves, last))
    return n


# operands of the control: the precision below the configuration's float32
CONTROL = jnp.bfloat16


def readings(state: State, seed: int, control=None) -> dict:
    """Every number of one grid of ``seed``, without a window: of the
    program, or of the reference computed in ``control`` (the control) in
    the program's place."""
    state.reseed(seed)
    state.results.append(run_grid(state))
    cells = sample_cells(state, seed)
    if control is None:
        got = program_outputs(state, cells)
    else:
        got = dict(reference_outputs(state, cells, dtype=control,
                                     precision=jax.lax.Precision.DEFAULT),
                   tau_bar=int(state.results[-1].tau_bar))
    return numbers(got, reference_outputs(state, cells))


def check(state: State, seed: int) -> list:
    cells = sample_cells(state, seed)
    checks = compare(program_outputs(state, cells),
                     reference_outputs(state, cells),
                     state.traffic["limits"])
    checks.append(("grids_differ", float(grids_differ(state)), 0.0))
    return checks

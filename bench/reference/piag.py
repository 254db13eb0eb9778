"""A plain PIAG replay: the paper's Algorithm 1 on one cell of a grid.

Four parts, each written out without the program:

* ``regime_workers`` and ``service_times``: the task durations of one
  cell, drawn from its seed by the recipe of the program's four standard
  worker regimes: lognormal times (sigma 0.25) around each worker's mean,
  some tasks slowed by a straggler factor, one ``numpy`` stream per
  worker keyed by ``(seed, worker)``, rounded to float32.
* ``simulate``: the parameter server's event order from a service-time
  matrix, by a heap of in-flight tasks.  Worker ``i``'s ``j``-th task lasts
  ``T[i, j]``; completion times add up in float32; ties go to the task
  pushed first.  Gives, per write event, the returning worker and the
  staleness of the whole gradient table (``tau_max``).
* ``gammas``: the step-size policies of Eqs. (13) and (14) and the fixed
  baseline, from the delays alone, in a stated precision.
* ``replay``: the iterates: the returning worker's gradient at the iterate
  it read, the mean of the gradient table, a gradient step and the l1
  prox, and the objective after each event.
"""
from __future__ import annotations

import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def regime_workers(regime: str, n: int, topology_seed: int = 0) -> list:
    """``(mean, sigma, straggle chance, straggle factor)`` of each of ``n``
    workers in a standard regime: ``uniform``; ``hetero2``/``hetero4``
    (means log-spaced over [1, 2] or [1, 4], shuffled by the topology
    seed, 2% of tasks 8x slower); ``straggler`` (10% of tasks 12x
    slower)."""
    if regime == "uniform":
        return [(1.0, 0.25, 0.0, 1.0)] * n
    if regime in ("hetero2", "hetero4"):
        spread, shuffle = ((2.0, topology_seed) if regime == "hetero2"
                           else (4.0, topology_seed + 1))
        means = np.geomspace(1.0, spread, n)
        np.random.default_rng(shuffle).shuffle(means)
        return [(float(m), 0.25, 0.02, 8.0) for m in means]
    if regime == "straggler":
        return [(1.0, 0.25, 0.1, 12.0)] * n
    raise ValueError(f"no reference for worker regime {regime!r}")


def service_times(workers: list, n_tasks: int, seed: int) -> np.ndarray:
    """``T[i, j]``: the duration of worker ``i``'s ``j``-th task."""
    T = np.empty((len(workers), n_tasks), np.float32)
    for i, (mean, sigma, p, x) in enumerate(workers):
        rng = np.random.default_rng([seed, i])
        t = rng.lognormal(np.log(mean) - 0.5 * sigma ** 2, sigma, n_tasks)
        if p > 0:
            t = np.where(rng.random(n_tasks) < p, t * x, t)
        T[i] = t
    return T


def simulate(T: np.ndarray):
    """Event order of one cell: ``(worker, tau_max)``, each (events,)."""
    T = np.asarray(T, np.float32)
    n, tasks = T.shape
    events = tasks - 1
    heap = [(T[i, 0], i, i, 0) for i in range(n)]  # (time, push, worker, read)
    heapq.heapify(heap)
    nxt = np.ones(n, np.int64)
    table = np.zeros(n, np.int64)  # version each table row was computed on
    worker = np.zeros(events, np.int32)
    tau_max = np.zeros(events, np.int32)
    for k in range(events):
        t, _, i, v = heapq.heappop(heap)
        table[i] = v
        worker[k], tau_max[k] = i, k - table.min()
        heapq.heappush(heap, (np.float32(t + T[i, nxt[i]]), n + k, i, k + 1))
        nxt[i] += 1
    return worker, tau_max


def gammas(policy: str, gamma_prime: float, taus, *, tau_bar: int,
           alpha: float = 0.9, dtype=np.float32) -> np.ndarray:
    """Step-sizes of one cell.  ``adaptive1``: alpha * max(gamma' - W, 0);
    ``adaptive2``: gamma' / (tau + 1) where it fits gamma' - W, else 0;
    ``fixed``: gamma' / (tau_bar + 1); W is the sum of the last ``tau``
    step-sizes."""
    gp = dtype(gamma_prime)
    out = np.zeros(len(taus), dtype)
    prefix = np.zeros(len(taus) + 1, dtype)  # prefix[k] = sum of gamma_t, t < k
    for k, tau in enumerate(np.asarray(taus)):
        window = dtype(prefix[k] - prefix[k - min(int(tau), k)])
        if policy == "adaptive1":
            g = dtype(alpha) * max(dtype(gp - window), dtype(0))
        elif policy == "adaptive2":
            cand = dtype(gp / dtype(int(tau) + 1))
            g = cand if cand <= dtype(gp - window) else dtype(0)
        elif policy == "fixed":
            g = dtype(gp / dtype(tau_bar + 1))
        else:
            raise ValueError(f"no reference for policy {policy!r}")
        out[k] = g
        prefix[k + 1] = dtype(prefix[k] + g)
    return out


@partial(jax.jit, static_argnames=("n_workers", "lam1", "lam2", "dtype",
                                   "precision"))
def replay(A, b, workers, steps, *, n_workers: int, lam1: float,
           lam2: float, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST):
    """Algorithm 1 on the l1-regularized logistic loss, for a batch of
    cells: ``workers`` and ``steps`` are (cells, events).  Returns the
    objective after every event (cells, events) and the final iterates
    (cells, d)."""
    A, b = A.astype(dtype), b.astype(dtype)
    N, d = A.shape
    rows = N // n_workers
    dot = partial(jnp.dot, precision=precision)

    def grad(x, i):
        Ai = jax.lax.dynamic_slice_in_dim(A, i * rows, rows)
        bi = jax.lax.dynamic_slice_in_dim(b, i * rows, rows)
        s = -bi * jax.nn.sigmoid(-bi * dot(Ai, x))
        return dot(s, Ai) / rows + lam2 * x

    def objective(x):
        z = b * dot(A, x)
        return (jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * lam2 * jnp.sum(x * x)
                + lam1 * jnp.sum(jnp.abs(x)))

    def cell(ws, gs):
        x0 = jnp.zeros((d,), dtype)
        table = jax.vmap(lambda i: grad(x0, i))(jnp.arange(n_workers))
        read = jnp.zeros((n_workers, d), dtype)

        def event(carry, e):
            x, table, read = carry
            w, g = e
            table = table.at[w].set(grad(read[w], w))
            v = x - g * jnp.mean(table, axis=0)
            x = jnp.sign(v) * jnp.maximum(jnp.abs(v) - g * lam1, 0.0)
            return (x, table, read.at[w].set(x)), objective(x)

        (x, _, _), obj = jax.lax.scan(event, (x0, table, read),
                                      (ws, gs.astype(dtype)))
        return obj, x

    return jax.vmap(cell)(workers, steps)

"""PIAG (Proximal Incremental Aggregated Gradient) with delay tracking.

Implements the paper's Algorithm 1 / Eqs. (3)-(4):

    g_k     = (1/n) sum_i grad f_i(x_{k - tau_k^(i)})
    x_{k+1} = prox_{gamma_k R}(x_k - gamma_k g_k)

as a fully-jitted ``lax.scan`` over a write-event trace (core.engine).  The
master state carries the aggregated gradient table g^(i), the iterate
snapshot each worker is computing on, and the delay-adaptive step-size state;
delays are the trace's write-event staleness, exactly Algorithm 1's
``tau_k^(i) = k - s^(i)`` bookkeeping.

The solver is generic over pytree iterates and any per-worker loss
``worker_loss(x, worker_data...)``; ``run_piag_logreg`` specializes it to the
paper's §4 workload.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from typing import Any

from .engine import EventTrace, strided_scan
from .prox import ProxOp
from .stepsize import (StepsizePolicy, StepsizeState, auto_horizon, clip_delta,
                       clipped_count as _clipped_of)
from ..telemetry.accumulators import (TelemetryConfig, init_telemetry,
                                      observe, emit_window, finalize)
from ..faults.spec import CODE_CORRUPT, FaultSpec, normalize_faults
from ..faults.inject import corrupt_value, update_fault_codes
from ..faults.guards import (guard_event, guarded_gamma, init_faults,
                             payload_finite)

__all__ = ["PIAGResult", "piag_scan", "run_piag", "run_piag_logreg"]


class PIAGResult(NamedTuple):
    x: jnp.ndarray            # final iterate (pytree)
    objective: jnp.ndarray    # (K,) P(x_{k+1}) after each write event
    gammas: jnp.ndarray       # (K,) emitted step-sizes
    taus: jnp.ndarray         # (K,) tau_k = max_i tau_k^(i) fed to the policy
    opt_residual: jnp.ndarray  # (K,) ||x_{k+1} - x_k|| / gamma_k (prox-grad map)
    clipped: jnp.ndarray = 0  # plain-int default: no jax init at import time
    # ^ final StepsizeState.clipped: number of events whose delay exceeded the
    #   policy horizon (H - 1 cap) -- nonzero means the horizon was undersized
    #   and window sums were silently truncated; see ROADMAP.
    telemetry: Any = None     # DelayTelemetry when telemetry= was passed
    # ^ trailing optional field: existing positional construction and the
    #   bitwise row-equivalence pins over the other leaves are unaffected.
    faults: Any = None        # FaultState counters when faults= was passed


def piag_scan(
    worker_loss: Callable,      # (x, *worker_data_slice) -> scalar, f_i
    x0,                         # pytree initial iterate
    worker_data,                # pytree, each leaf (n_workers, ...)
    events,                     # (worker (K,) i32, tau (K,) i32) jnp arrays
    policy: StepsizePolicy,
    prox: ProxOp,
    objective: Callable | None = None,  # P(x); defaults to mean worker loss + R
    horizon: int = 4096,
    active: jnp.ndarray | None = None,  # (n,) bool; ragged-bucket worker mask
    record_every: int = 1,
    telemetry: TelemetryConfig | None = None,
    engine: str = "scan",
    faults: FaultSpec | None = None,
    fault_codes: jnp.ndarray | None = None,
    grad_fn: Callable | None = None,  # (x, *worker_data_slice) -> grad pytree
) -> PIAGResult:
    """The traceable PIAG core: Algorithm 1 as a pure ``lax.scan``.

    Everything is a function of jnp values, so the SAME step code serves the
    solo path (``run_piag`` jits it directly) and the batched path
    (``repro.sweep.sweep_piag`` vmaps it over stacked events and policy
    parameters) -- which is what makes per-row equivalence between the two
    exact rather than approximate.

    ``active`` supports ragged worker-count sweeps: a bucketed cell pads its
    gradient table to the bucket width, and the mask turns the aggregation
    into a mean over ACTIVE rows only, so padded workers never contribute
    gradients (their table rows are multiplied by an exact 0.0; padded
    ``worker_data`` rows therefore only need to be finite).  The trace must
    be masked consistently (``engine.trace_scan(T, active=...)``) so padded
    workers never appear in ``events`` either.

    ``record_every=s`` decimates the recorded trajectory: only every s-th
    event's (objective, gamma, tau, residual) row is materialized -- and the
    objective/residual are only COMPUTED on those events -- so big sweeps
    stop paying an O(K) objective evaluation and an O(B, K) output for
    trajectories they will subsample anyway.  The iterate path is unchanged
    (recorded rows are bitwise rows ``s-1, 2s-1, ...`` of a stride-1 run);
    K must be a multiple of s.

    ``telemetry=TelemetryConfig(...)`` threads an in-scan accumulator
    (delay histogram, tau/gamma moments, per-window clip counts) through the
    carry and returns it finalized on ``result.telemetry``.  The accumulator
    observes EVERY event -- decimated steps included -- so its aggregates
    are exact under any ``record_every``, and it is bitwise-neutral: no
    solver leaf depends on it.

    ``engine='fused'`` launches line 16 + line 17 (window-sum gather, policy
    select, cumulative-sum push, prox step) as ONE Pallas kernel per event
    (``repro.kernels.fused_step``) instead of chained XLA ops -- bitwise-
    equal to ``engine='scan'`` and telemetry-neutral (the accumulator rides
    the same carry either way).  Requires a single-1-D-leaf iterate and a
    ``PolicyParams``-expressible policy; both are checked loudly.

    ``faults=FaultSpec(...)`` (with a ``fault_codes`` event column from
    ``repro.faults.update_fault_codes``) switches in the guarded step:
    drop/dup/corrupt codes are applied to the returning worker's gradient,
    non-finite or over-stale payloads are rejected (skip-and-count; the
    gradient table keeps its previous row so one corrupt worker never
    poisons the aggregate), horizon overflow degrades to the
    worst-case-bound ``gamma'/(tau+1)``, and a ``FaultState`` counter tuple
    rides the carry onto ``result.faults``.  ``faults=None`` is bitwise the
    pre-fault jaxpr -- the guarded body is a SEPARATE code path, not a
    predicated version of the old one.
    """
    if engine not in ("scan", "fused"):
        raise ValueError(f"engine must be 'scan' or 'fused', got {engine!r}")
    faults = normalize_faults(faults)
    if faults is not None:
        if engine == "fused":
            raise TypeError("engine='fused' does not support fault "
                            "injection; use engine='scan'")
        if fault_codes is None:
            raise ValueError("faults is set but fault_codes is None; build "
                             "the event codes with "
                             "repro.faults.update_fault_codes")
    if engine == "fused":
        from ..kernels.fused_step import (as_policy_params, fused_leaf,
                                          fused_policy_prox_step)
        fparams = as_policy_params(policy)
        _, x_treedef = fused_leaf(x0, "PIAG iterate")
    n = jax.tree_util.tree_leaves(worker_data)[0].shape[0]
    # grad_fn is the data-parallel seam: the 2-D sharded backend injects
    # repro.mesh.pmean_grad(worker_loss, "data", D) so each mesh data shard
    # differentiates its slice of the samples and psums back the full
    # gradient.  grad_fn=None is bitwise the old jaxpr (off-is-absent).
    grad_i = jax.grad(worker_loss) if grad_fn is None else grad_fn

    if active is None:
        def aggregate(buf):
            return jnp.mean(buf, axis=0)
    else:
        amask = jnp.asarray(active, jnp.float32)
        n_active = jnp.sum(amask)

        def aggregate(buf):
            w = amask.reshape((n,) + (1,) * (buf.ndim - 1))
            return jnp.sum(buf * w, axis=0) / n_active

    def data_at(w):
        return jax.tree_util.tree_map(lambda leaf: leaf[w], worker_data)

    if objective is None:
        def objective(x):
            losses = jax.vmap(lambda i: worker_loss(x, *jax.tree_util.tree_leaves(data_at(i))))
            # note: assumes worker_data leaves order == worker_loss arg order
            idx = jnp.arange(n)
            return aggregate(losses(idx)) + prox.value(x)

    # Algorithm 1 line 3: g^(i) <- grad f_i(x_0)
    def init_grad(w):
        return grad_i(x0, *jax.tree_util.tree_leaves(data_at(w)))

    g_table = jax.vmap(init_grad)(jnp.arange(n))
    x_read0 = jax.tree_util.tree_map(lambda leaf: jnp.broadcast_to(leaf, (n,) + leaf.shape), x0)

    def make_step(emit):
        if faults is not None:
            return _make_fault_step(emit)

        def step(carry, event):
            x, gtab, x_read, ss = carry[:4]
            w, tau = event
            # worker w returns grad f_w(x_read[w])  (Algorithm 1 line 12)
            xw = jax.tree_util.tree_map(lambda leaf: leaf[w], x_read)
            gw = grad_i(xw, *jax.tree_util.tree_leaves(data_at(w)))
            gtab = jax.tree_util.tree_map(lambda buf, gnew: buf.at[w].set(gnew), gtab, gw)
            # line 14: aggregate; line 16: delay-adaptive gamma; line 17: prox step
            # g is materialized before the update under either engine: the
            # fused kernel needs it whole, and XLA would otherwise fuse the
            # scan engine's reduction into the update in another order
            g = jax.lax.optimization_barrier(
                jax.tree_util.tree_map(aggregate, gtab))
            ss_old = ss
            if engine == "fused":
                gamma, ss, x_leaf = fused_policy_prox_step(
                    fparams, prox, ss, tau,
                    jax.tree_util.tree_leaves(x)[0],
                    jax.tree_util.tree_leaves(g)[0])
                x_new = jax.tree_util.tree_unflatten(x_treedef, [x_leaf])
            else:
                gamma, ss = policy.step(ss, tau)
                x_new = prox.prox(
                    jax.tree_util.tree_map(
                        lambda xv, gv: xv - gamma * gv, x, g), gamma)
            # line 20: hand x_{k+1} to the returning worker
            x_read = jax.tree_util.tree_map(
                lambda buf, xv: buf.at[w].set(xv), x_read, x_new)
            if telemetry is None:
                if not emit:  # decimated step: carry advances, nothing recorded
                    return (x_new, gtab, x_read, ss), None
            else:
                tel = observe(carry[4], tau, gamma, clip_delta(ss_old, ss))
                if not emit:
                    return (x_new, gtab, x_read, ss, tel), None
                tel, wclip = emit_window(tel)
            dx = jnp.sqrt(sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
                jax.tree_util.tree_leaves(x_new), jax.tree_util.tree_leaves(x))))
            res = jnp.where(gamma > 0, dx / jnp.maximum(gamma, 1e-30), 0.0)
            out = (objective(x_new), gamma, tau, res)
            if telemetry is None:
                return (x_new, gtab, x_read, ss), out
            return (x_new, gtab, x_read, ss, tel), out + (wclip,)
        return step

    # Index of the FaultState in the carry (after the optional telemetry).
    fi = 5 if telemetry is not None else 4

    def _make_fault_step(emit):
        poison = corrupt_value(faults)

        def step(carry, event):
            x, gtab, x_read, ss = carry[:4]
            fs = carry[fi]
            w, tau, code = event
            xw = jax.tree_util.tree_map(lambda leaf: leaf[w], x_read)
            gw = grad_i(xw, *jax.tree_util.tree_leaves(data_at(w)))
            # update-level corruption: poison the payload BEFORE the guard
            gw = jax.tree_util.tree_map(
                lambda a: (a + jnp.where(code == CODE_CORRUPT, poison,
                                         jnp.float32(0.0))).astype(a.dtype),
                gw)
            finite = payload_finite(gw) if faults.guard_nonfinite \
                else jnp.ones((), jnp.bool_)
            accept, mult, fs = guard_event(faults, code, tau, finite, fs)
            # rejected updates keep the worker's PREVIOUS table row: one
            # corrupt gradient must never poison the aggregate
            gtab = jax.tree_util.tree_map(
                lambda buf, gnew: buf.at[w].set(
                    jnp.where(accept, gnew, buf[w])), gtab, gw)
            g = jax.tree_util.tree_map(aggregate, gtab)
            ss_old = ss
            gamma, ss, fs = guarded_gamma(policy, ss, tau, mult, faults, fs)
            x_cand = prox.prox(
                jax.tree_util.tree_map(
                    lambda xv, gv: xv - gamma * gv, x, g), gamma)
            x_new = jax.tree_util.tree_map(
                lambda cnd, old: jnp.where(accept, cnd, old), x_cand, x)
            # the worker refetches the latest iterate either way (a rejected
            # worker rejoins on fresh state, shrinking its next staleness)
            x_read = jax.tree_util.tree_map(
                lambda buf, xv: buf.at[w].set(xv), x_read, x_new)
            tel = None
            if telemetry is not None:
                tel = observe(carry[4], tau, gamma, clip_delta(ss_old, ss))
            extras = ((tel,) if telemetry is not None else ()) + (fs,)
            if not emit:
                return (x_new, gtab, x_read, ss) + extras, None
            wtail = ()
            if telemetry is not None:
                tel, wclip = emit_window(tel)
                extras = (tel, fs)
                wtail = (wclip,)
            dx = jnp.sqrt(sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
                jax.tree_util.tree_leaves(x_new),
                jax.tree_util.tree_leaves(x))))
            res = jnp.where(gamma > 0, dx / jnp.maximum(gamma, 1e-30), 0.0)
            out = (objective(x_new), gamma, tau, res) + wtail
            return (x_new, gtab, x_read, ss) + extras, out
        return step

    if faults is not None:
        events = tuple(events) + (jnp.asarray(fault_codes, jnp.int32),)
    carry0 = (x0, g_table, x_read0, policy.init(horizon))
    if telemetry is not None:
        carry0 = carry0 + (init_telemetry(telemetry),)
    if faults is not None:
        carry0 = carry0 + (init_faults(),)
    carry_fin, outs = strided_scan(make_step, carry0, events, record_every)
    x_fin, ss_fin = carry_fin[0], carry_fin[3]
    obj, gam, taus, res = outs[:4]
    tel_out = None
    if telemetry is not None:
        tel_out = finalize(carry_fin[4], outs[4])
    faults_out = carry_fin[fi] if faults is not None else None
    return PIAGResult(x=x_fin, objective=obj, gammas=gam, taus=taus,
                      opt_residual=res, clipped=_clipped_of(ss_fin),
                      telemetry=tel_out, faults=faults_out)


def run_piag(
    worker_loss: Callable,
    x0,
    worker_data,
    trace: EventTrace,
    policy: StepsizePolicy,
    prox: ProxOp,
    objective: Callable | None = None,
    horizon: int | str = 4096,
    use_tau_max: bool = True,
    record_every: int = 1,
    telemetry: TelemetryConfig | None = None,
    engine: str = "scan",
    faults: FaultSpec | None = None,
    fault_seed: int = 0,
) -> PIAGResult:
    """Run PIAG over a write-event trace; everything under one jit.

    ``horizon='auto'`` sizes the step-size window buffer from the trace's
    own measured delays (``auto_horizon``) instead of the 4096 worst-case
    default -- bitwise-identical output, a fraction of the scan carry.
    ``engine='fused'`` routes the per-event policy + prox update through
    the fused Pallas kernel (see ``piag_scan``).  ``faults`` enables the
    guarded step (``piag_scan``); the per-event drop/dup/corrupt codes are
    drawn inside the jit from ``fault_seed`` (the cell seed), so solo runs
    match the batched sweep bitwise under faults."""
    taus = trace.tau_max if use_tau_max else trace.tau
    if horizon == "auto":
        horizon = auto_horizon(int(np.max(taus, initial=0)))
    events = (
        jnp.asarray(trace.worker, jnp.int32),
        jnp.asarray(taus, jnp.int32),
    )
    faults = normalize_faults(faults)

    if faults is None:
        @jax.jit
        def run(events):
            return piag_scan(worker_loss, x0, worker_data, events, policy,
                             prox, objective=objective, horizon=horizon,
                             record_every=record_every, telemetry=telemetry,
                             engine=engine)

        return run(events)

    n_events = int(events[0].shape[0])

    @jax.jit
    def run_faulted(events, fseed):
        codes = update_fault_codes(faults, n_events, fseed)
        return piag_scan(worker_loss, x0, worker_data, events, policy, prox,
                         objective=objective, horizon=horizon,
                         record_every=record_every, telemetry=telemetry,
                         engine=engine, faults=faults, fault_codes=codes)

    return run_faulted(events, jnp.int32(fault_seed))


def run_piag_lipschitz(problem, trace, prox, h: float = 0.9,
                       alpha: float = 0.9, gamma0: float = 1.0,
                       horizon: int = 4096) -> PIAGResult:
    """BEYOND-PAPER: PIAG needing neither the delay bound nor L.

    Uses core.stepsize.AdaptiveLipschitz: per write event, the returning
    worker's (old grad, new grad, old iterate, new iterate) quadruple yields
    a secant curvature sample ||dg||/||dx||; the running max estimates L and
    sets the Eq.-(8) budget gamma' = h / L_est on-line (the paper's §5
    future work, made concrete)."""
    from .stepsize import AdaptiveLipschitz

    Aw, bw = problem.worker_slices()
    n = Aw.shape[0]
    grad_i = jax.grad(lambda x, A, b: problem.worker_loss(x, A, b))
    pol = AdaptiveLipschitz(gamma_prime=gamma0, h=h, alpha=alpha)
    x0 = jnp.zeros((problem.dim,), jnp.float32)

    g_table = jax.vmap(lambda i: grad_i(x0, Aw[i], bw[i]))(jnp.arange(n))
    x_read0 = jnp.broadcast_to(x0, (n,) + x0.shape)
    events = (jnp.asarray(trace.worker, jnp.int32),
              jnp.asarray(trace.tau_max, jnp.int32))

    def step(carry, event):
        x, gtab, x_read, x_prev, lip = carry
        w, tau = event
        xw = x_read[w]
        gw = grad_i(xw, Aw[w], bw[w])
        # secant curvature sample from worker w's consecutive gradients
        dg = jnp.linalg.norm(gw - gtab[w])
        dx = jnp.linalg.norm(xw - x_prev[w])
        lip = pol.observe_curvature(lip, dg, dx)
        gtab = gtab.at[w].set(gw)
        x_prev = x_prev.at[w].set(xw)
        g = jnp.mean(gtab, axis=0)
        gamma, lip = pol.step(lip, tau)
        x_new = prox.prox(x - gamma * g, gamma)
        x_read = x_read.at[w].set(x_new)
        return (x_new, gtab, x_read, x_prev, lip), (
            problem.P(x_new), gamma, tau, lip.L_est)

    @jax.jit
    def run(carry0, events):
        return jax.lax.scan(step, carry0, events)

    carry0 = (x0, g_table, x_read0, x_read0, pol.init(horizon))
    (x_fin, _, _, _, lip_fin), (obj, gam, taus, L_est) = run(carry0, events)
    return PIAGResult(x=x_fin, objective=obj, gammas=gam, taus=taus,
                      opt_residual=L_est, clipped=_clipped_of(lip_fin))


def run_piag_logreg(problem, trace, policy, prox, horizon: int = 4096) -> PIAGResult:
    """PIAG on the paper's l1-regularized logistic regression (§4.1)."""
    Aw, bw = problem.worker_slices()

    def worker_loss(x, A, b):
        return problem.worker_loss(x, A, b)

    def objective(x):
        return problem.P(x)

    x0 = jnp.zeros((problem.dim,), jnp.float32)
    return run_piag(worker_loss, x0, (Aw, bw), trace, policy, prox,
                    objective=objective, horizon=horizon)

"""Fused per-event Pallas kernels for the sweep inner loop.

One solver event in the scan cores is four separate XLA ops chained
through the carry: the ``window_sum`` gather from the circular
cumulative-sum buffer, the policy ``lax.switch`` dispatch, the ``_push``
scatter back into the buffer, and the prox/mix/merge update of the
iterate.  Batched over cells that becomes a per-step
``take_along_axis`` / ``put_along_axis`` round trip on the (cells, H)
carry block.  The kernels here fuse all four into ONE ``pallas_call``
per event, so the carry block is read and written exactly once.

Bitwise contract (the repo's standing rule, pinned in
``tests/test_fused_engine.py`` and checked on the chip by
``chip_smoke.py``): each kernel repeats ``core.stepsize.window_sum`` /
``_push`` operation for operation, and the prox / server-merge
arithmetic is written with the identical expression the scan cores use.
Two changes of form keep Mosaic able to lower the body, and both are
exact: the read of ``S_j`` at a traced slot is a one-hot masked lane sum
(one slot kept, ``+0.0`` elsewhere) instead of a gather, and the push is
a one-slot ``where`` instead of a scatter.  Policy dispatch is
:func:`select_gamma`, a branch-free ``where`` chain over the six
``ParamPolicy`` branches, because ``lax.switch`` does not lower inside a
compiled Pallas body; every branch is the exact expression from
``repro.sweep.policies.ParamPolicy.step``, so selecting a value computed
by identical ops keeps the result bitwise-equal to the switch.

Carry layout contract (durable -- see ROADMAP): the policy parameters,
the delay and the step-size state ``(k, total, cumbuf, clipped)`` cross
the kernel boundary as refs, followed by the iterate rows.  Compiled,
every operand is 2-D -- scalars ``(1, 1)``, rows ``(1, n)``, ``cumbuf``
``(1, H)`` -- so each block equals its array's last two dims, as Mosaic's
(8, 128) rule requires; ``vmap`` over cells then adds a squeezed leading
grid axis (one cell per grid step), which is what lets the batched and
sharded runners reuse these kernels unchanged.  Interpreted (CPU),
operands keep the scan engine's own shapes (see :func:`_call`).

Interpret-vs-compile dispatch follows ``kernels.dispatch``: compiled on
tpu/gpu, interpreted on cpu (where the kernel body runs as plain jax
ops -- still one fused dataflow block, and still bitwise-equal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.stepsize import StepsizeState

from .dispatch import resolve_interpret

__all__ = ["select_gamma", "as_policy_params", "fused_leaf",
           "fused_policy_prox_step", "fused_policy_mix_step",
           "fused_policy_buff_step", "boundary_bytes"]


def boundary_bytes(horizon: int, n: int) -> int:
    """Per-event HBM traffic contract of ``fused_policy_prox_step`` on a
    COMPILED backend: the bytes crossing the kernel boundary (operands +
    results).  Refs stream through on-chip memory inside the kernel, so
    nothing between the policy update and the prox write touches HBM --
    this is the quantity the roofline tooling compares against the scan
    engine's per-event HLO bytes.  Interpret mode (CPU) does not honor the
    contract: ref reads materialize whole arrays as ordinary XLA ops.
    All elements are 4-byte (f32/i32)."""
    state = 4 * (1 + 1 + horizon + 1)      # k, total, cumbuf (H,), clipped
    inputs = 4 * 4 + 4 + state + 2 * 4 * n  # params, tau, state, x, g
    outputs = 4 + state + 4 * n             # gamma, new state, x_new
    return inputs + outputs


def select_gamma(policy_id, gamma_prime, c0, c1, ws, tau):
    """Branch-free twin of ``ParamPolicy.step``'s ``lax.switch``.

    Arguments are the four ``PolicyParams`` scalars plus the window sum
    and the (int) delay; each candidate below is the verbatim branch
    expression from ``repro.sweep.policies`` (ids: 0 fixed_like, 1 naive,
    2 adaptive1, 3 adaptive2, 4 hinge, 5 poly).
    """
    t = jnp.asarray(tau, jnp.float32)
    g_fixed = jnp.broadcast_to(c0, ws.shape)
    g_naive = gamma_prime / (t + c0)
    g_ad1 = c0 * jnp.maximum(gamma_prime - ws, 0.0)
    g_ad2 = jnp.where(gamma_prime / (t + 1.0) <= gamma_prime - ws,
                      gamma_prime / (t + 1.0), 0.0)
    g_hinge = gamma_prime * jnp.where(
        t <= c1, 1.0, 1.0 / (c0 * jnp.maximum(t - c1, 0.0) + 1.0))
    g_poly = gamma_prime * jnp.power(t + 1.0, -c0)
    gamma = jnp.where(
        policy_id == 0, g_fixed, jnp.where(
            policy_id == 1, g_naive, jnp.where(
                policy_id == 2, g_ad1, jnp.where(
                    policy_id == 3, g_ad2, jnp.where(
                        policy_id == 4, g_hinge, g_poly)))))
    return jnp.asarray(gamma, jnp.float32)


def as_policy_params(policy):
    """``PolicyParams`` for any policy the fused engine can run.

    ``ParamPolicy`` adapters hand over their traced params; concrete
    ``StepsizePolicy`` dataclasses flatten through ``policy_params``,
    which raises a loud ``TypeError`` for stateful policies
    (``AdaptiveLipschitz``) that the fused kernel cannot express --
    callers fall back to ``engine='scan'`` for those.
    """
    # imported lazily: core modules import this module, and sweep.policies
    # imports core.stepsize -- a module-level import here would cycle
    from repro.sweep.policies import ParamPolicy, policy_params
    if isinstance(policy, ParamPolicy):
        return policy.params
    return policy_params(policy)


def fused_leaf(tree, what: str):
    """The single 1-D leaf the fused kernels operate on, plus its treedef.

    The fused engine moves the iterate through the kernel as one
    whole-array ref, so multi-leaf or multi-dimensional pytrees are
    rejected loudly rather than silently flattened.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(leaves) != 1 or leaves[0].ndim != 1:
        raise ValueError(
            f"engine='fused' requires the {what} to be a single 1-D array "
            f"leaf; got {len(leaves)} leaves with shapes "
            f"{[l.shape for l in leaves]} -- use engine='scan'")
    return leaves[0], treedef


def _window_sum(k, total, cumbuf, tau):
    """``core.stepsize.window_sum`` on the kernel's operands.

    The same clip and the same ``total - S_j`` subtraction; the read of
    ``S_j`` from slot ``(j - 1) % H`` is a masked lane sum instead of a
    dynamic gather, which Mosaic does not lower.  The mask keeps exactly
    one slot and ``where`` puts ``+0.0`` everywhere else, so the sum is
    that slot's value exactly, whatever the other slots hold.
    """
    H = cumbuf.shape[-1]
    cap = jnp.minimum(k, H - 1)
    tau_c = jnp.clip(tau, 0, cap)
    was_clipped = (tau > cap).astype(jnp.int32)
    j = k - tau_c  # we need S_j
    slot = lax.broadcasted_iota(jnp.int32, cumbuf.shape, cumbuf.ndim - 1)
    s_read = jnp.sum(jnp.where(slot == (j - 1) % H, cumbuf, 0.0), axis=-1,
                     keepdims=cumbuf.ndim > 1)
    s_j = jnp.where(j <= 0, 0.0, s_read)
    return total - s_j, was_clipped


def _push_slot(k, total, cumbuf, gamma):
    """``core.stepsize._push``'s buffer write as a one-slot ``where``."""
    new_total = total + gamma
    slot = lax.broadcasted_iota(jnp.int32, cumbuf.shape, cumbuf.ndim - 1)
    return new_total, jnp.where(slot == k % cumbuf.shape[-1], new_total,
                                cumbuf)


def _policy_update(pid_ref, gp_ref, c0_ref, c1_ref, tau_ref,
                   k_ref, total_ref, cumbuf_ref, clip_ref,
                   k_out, total_out, cumbuf_out, clip_out, gamma_out):
    """Shared kernel-body prologue: window sum, policy select, push; writes
    the new step-size state and returns gamma for the iterate update."""
    k, total, cumbuf, tau = k_ref[...], total_ref[...], cumbuf_ref[...], \
        tau_ref[...]
    ws, clip = _window_sum(k, total, cumbuf, tau)
    gamma = select_gamma(pid_ref[...], gp_ref[...], c0_ref[...], c1_ref[...],
                         ws, tau)
    new_total, new_cumbuf = _push_slot(k, total, cumbuf, gamma)
    k_out[...] = k + 1
    total_out[...] = new_total
    cumbuf_out[...] = new_cumbuf
    clip_out[...] = clip_ref[...] + clip
    gamma_out[...] = gamma
    return gamma


def _call(kernel, params, tau, state, operands, n_out, interpret):
    """Run ``kernel`` on one cell: the nine policy/state operands, then
    ``operands`` (per-cell scalars and 1-D rows); returns ``(gamma,
    new_state, *rows)`` for the kernel's ``n_out`` row outputs.

    Compiled, every operand is lifted to 2-D -- a scalar to ``(1, 1)``, a
    row to ``(1, n)`` -- so each block equals its array's last two dims,
    which Mosaic's (8, 128) block rule accepts; ``vmap`` then adds a
    squeezed leading cells axis and the blocks stay legal.  Interpreted,
    operands keep the scan engine's own shapes: XLA's CPU compiler then
    fuses the kernel body as it fuses the scan step, so it contracts the
    same multiply-adds into FMAs and the two engines stay bitwise equal.
    """
    interp = resolve_interpret(interpret)
    lift = (lambda v: v) if interp else (lambda v: v.reshape(1, -1))
    ins = [jnp.asarray(params.policy_id, jnp.int32),
           jnp.asarray(params.gamma_prime, jnp.float32),
           jnp.asarray(params.c0, jnp.float32),
           jnp.asarray(params.c1, jnp.float32),
           jnp.asarray(tau, jnp.int32), jnp.asarray(state.k, jnp.int32),
           jnp.asarray(state.total, jnp.float32), state.cumbuf,
           jnp.asarray(state.clipped, jnp.int32), *operands]
    # new k, total, cumbuf, clipped; gamma (an f32 scalar like total); rows
    outs = ins[5:9] + [ins[6]] + [operands[-1]] * n_out
    res = pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct(lift(o).shape, o.dtype)
                           for o in outs], interpret=interp,
    )(*map(lift, ins))
    k, total, cumbuf, clipped, gamma, *rows = [
        r.reshape(o.shape) for r, o in zip(res, outs)]
    return (gamma, StepsizeState(k=k, total=total, cumbuf=cumbuf,
                                 clipped=clipped)) + tuple(rows)


# ---------------------------------------------------------------------------
# PIAG / BCD: gamma select + prox(x - gamma * g)
# ---------------------------------------------------------------------------

def _prox_kernel(*refs, prox):
    state_refs, (x_ref, g_ref), outs = refs[:9], refs[9:11], refs[11:]
    gamma = _policy_update(*state_refs, *outs[:5])
    # identical expression to the scan cores: prox(x - gamma * g, gamma)
    outs[5][...] = prox.prox(x_ref[...] - gamma * g_ref[...], gamma)


def fused_policy_prox_step(params, prox, state, tau, x, g, *,
                           interpret=None):
    """One fused PIAG/BCD event: ``policy.step`` + ``prox(x - gamma*g)``.

    Returns ``(gamma, new_state, x_new)`` -- bitwise-equal to
    ``gamma, ss = policy.step(state, tau); prox.prox(x - gamma * g, gamma)``.
    The prox operator is static (baked into the kernel body); the policy
    is a runtime ``PolicyParams`` value.
    """
    return _call(functools.partial(_prox_kernel, prox=prox), params, tau,
                 state, (x, g), 1, interpret)


# ---------------------------------------------------------------------------
# FedAsync: gamma select + server mix x + gamma * (xc - x)
# ---------------------------------------------------------------------------

def _mix_kernel(*refs):
    state_refs, (x_ref, xc_ref), outs = refs[:9], refs[9:11], refs[11:]
    gamma = _policy_update(*state_refs, *outs[:5])
    a = x_ref[...]
    outs[5][...] = a + gamma * (xc_ref[...] - a)


def fused_policy_mix_step(params, state, tau, x, xc, *, interpret=None):
    """One fused FedAsync server event: ``policy.step`` + convex mix.

    Returns ``(gamma, new_state, x_new)`` with
    ``x_new = x + gamma * (xc - x)``.
    """
    return _call(_mix_kernel, params, tau, state, (x, xc), 1,
                 interpret)


# ---------------------------------------------------------------------------
# FedBuff: gamma select + delta accumulate + buffered apply/decay
# ---------------------------------------------------------------------------

def _buff_kernel(*refs, scale):
    state_refs, (agg_ref, x_ref, xc_ref, xw_ref, delta_ref), outs = \
        refs[:9], refs[9:14], refs[14:]
    gamma = _policy_update(*state_refs, *outs[:5])
    agg = agg_ref[...]
    # identical expressions to fedbuff_scan: accumulate against the
    # client's READ snapshot xw, apply scaled by eta/buffer_size on
    # aggregation events, then decay
    delta = delta_ref[...] + gamma * (xc_ref[...] - xw_ref[...])
    outs[5][...] = x_ref[...] + agg * scale * delta
    outs[6][...] = (1.0 - agg) * delta


def fused_policy_buff_step(params, state, tau, x, xc, xw, delta, agg,
                           scale: float, *, interpret=None):
    """One fused FedBuff server event.

    ``scale = eta / buffer_size`` is static; ``agg`` is the traced 0/1
    aggregation flag.  Returns ``(gamma, new_state, x_new, delta_new)``.
    """
    return _call(functools.partial(_buff_kernel, scale=scale), params, tau,
                 state, (jnp.asarray(agg, jnp.float32), x, xc, xw, delta), 2,
                 interpret)

"""The trainer surface: asynchronous training through
``repro.launch.train.run_training``, the program's own entry point.

Configuration keys: ``arch_id`` (a model of ``repro.configs``) and
``overrides`` (``ModelConfig`` fields, applied with ``dataclasses.replace``
and listed under ``reduced``).  Traffic keys: ``workers``, ``batch``,
``seq``, ``policy``, ``lr``, ``straggler``, ``log_every`` (the trainer's
logging stride: each record syncs on a held-out loss), ``warm_events``,
``trace_seconds`` and ``limits``.  ``--seed`` is the trainer's seed: it
draws the initial weights, the event trace and the token stream.

Set-up calls ``run_training`` once with ``warm_events`` events, which
compiles every program, and reads the warm time per event from its log.
The measured call then builds the trainer, drives it through its first
``log_every`` events (its first three are copied out for the check) and
goes on through the window: from the log record at ``log_every`` to the
last record, sized to fill ``--seconds`` at the warm rate.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench import work as work_mod
from bench.reference import trainer as ref

CHECKED = 3  # write events the reference follows
# operands of the control: the precision below the configuration's bfloat16
CONTROL = jnp.float8_e4m3fn


def model_config(config: dict):
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["arch_id"]),
                              **config.get("overrides", {}))
    cfg.validate()
    return cfg


def reference_config(cfg) -> dict:
    return {"d_model": cfg.d_model, "vocab": cfg.vocab,
            "n_layers": cfg.n_layers, "d_inner": cfg.d_inner,
            "ssm_state": cfg.ssm_state, "heads": cfg.ssm_heads,
            "head_dim": cfg.ssm_head_dim, "conv": cfg.ssm_conv,
            "chunk": cfg.ssm_chunk}


def _train(cfg, traffic, seed, steps, log_every):
    from repro.launch.train import run_training
    return run_training(cfg, steps=steps, batch=traffic["batch"],
                        seq=traffic["seq"], policy_name=traffic["policy"],
                        lr=traffic["lr"], n_workers=traffic["workers"],
                        seed=seed, log_every=log_every,
                        straggler=traffic["straggler"])


class State:
    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfg = model_config(config)
        self.log, self.captured = None, {}


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda l: jnp.linalg.norm(jnp.ravel(l).astype(jnp.float32)), tree)


def _change_norms(host_before, after):
    """Per-leaf norms of ``after - host_before``, where ``host_before`` is
    a host copy: one leaf at a time goes back to the chip."""
    return {jax.tree_util.keystr(p): float(ref.diff_norm(y, jnp.asarray(x)))
            for (p, x), (_, y) in zip(
                jax.tree_util.tree_leaves_with_path(host_before),
                jax.tree_util.tree_leaves_with_path(after))}


def _capture(state: State, marks, start_at: int):
    """A replacement for ``repro.launch.train.make_apply``: the trainer's
    own jitted update, around which the first events' inputs and outputs
    are copied out, and which starts the window at event ``start_at``."""
    from repro.launch import train as train_mod
    real_make_apply = train_mod.make_apply
    cap = state.captured = {}

    def make_apply(trainer):
        apply = real_make_apply(trainer)
        b1 = trainer.optimizer.base.b1
        calls = [0]

        def wrapped(params, grads, opt, tau):
            k = calls[0]
            calls[0] += 1
            if marks is not None and k == start_at:
                marks.start()
            if k == 0:
                cap["p0"] = jax.device_get(params)
            out = apply(params, grads, opt, tau)
            if k == 0:
                mu = out[1].inner.mu
                cap["grad_norms"] = {
                    jax.tree_util.keystr(p): float(n) / (1.0 - b1)
                    for p, n in jax.tree_util.tree_leaves_with_path(
                        _leaf_norms(mu))}
                cap["grad0"] = {
                    jax.tree_util.keystr(p): np.asarray(m) / (1.0 - b1)
                    for p, m in jax.tree_util.tree_leaves_with_path(
                        jax.device_get(mu))}
            if k < CHECKED:
                cap.setdefault("taus", []).append(int(tau))
                cap.setdefault("gammas", []).append(float(out[2]))
            if k == CHECKED - 1:
                cap["change_norms"] = _change_norms(cap.pop("p0"), out[0])
            return out

        return wrapped

    return train_mod, real_make_apply, make_apply


def setup(config: dict, traffic: dict, seed: int) -> State:
    """One short call of the trainer, which compiles its programs, and the
    warm time per write event from its first and last log records."""
    state = State(config, traffic, seed)
    n = int(traffic["warm_events"])
    log = _train(state.cfg, traffic, seed, n, n - 1)
    state.event_s = (log[-1]["wall_s"] - log[0]["wall_s"]) / (
        log[-1]["step"] - log[0]["step"])
    return state


def window(state: State, seconds: float, marks) -> dict:
    t = state.traffic
    every = int(t["log_every"])
    steps = every + max(every, math.ceil(seconds / state.event_s)) + 1
    train_mod, real, wrapped = _capture(state, marks, every + 1)
    train_mod.make_apply = wrapped
    try:
        state.log = _train(state.cfg, t, state.seed, steps, every)
    finally:
        train_mod.make_apply = real
    marks.stop()
    first = next(r for r in state.log if r["step"] >= every)
    last = state.log[-1]
    events = last["step"] - first["step"]
    return {"seconds": last["wall_s"] - first["wall_s"], "events": events,
            "tokens": events * t["batch"] * t["seq"], "attempted": events,
            "failed": 0}


def end_to_end(win: dict) -> dict:
    return {"tokens_per_s": win["tokens"] / win["seconds"]}


def work(state: State, win: dict) -> dict:
    cfg = state.cfg
    return {"train": {
        "flops_per_token": work_mod.mamba2_flops_per_token(cfg),
        "update_bytes": work_mod.UPDATE_BYTES_PER_PARAM
        * work_mod.mamba2_params(cfg) * win["events"]}}


def program_outputs(state: State) -> dict:
    cap = state.captured
    return {"taus": cap["taus"], "gammas": cap["gammas"],
            "loss1": state.log[0]["loss"], "grad_norms": cap["grad_norms"],
            "grad0": cap.pop("grad0"), "change_norms": cap["change_norms"]}


def reference_outputs(state: State, *, dtype=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST) -> dict:
    return ref.replay(reference_config(state.cfg), state.traffic, state.seed,
                      steps=CHECKED, dtype=dtype, precision=precision)


def leaf_gap(prog: dict, refn: dict, leaves) -> float:
    """The worst leaf's gap between the two norms, against the larger of
    that leaf's reference norm and the median leaf's."""
    med = statistics.median(refn[k] for k in leaves)
    return max(abs(prog[k] - refn[k]) / max(refn[k], med) for k in leaves)


def diff_median(prog: dict, refn: dict, norms: dict) -> float:
    """Per leaf, the norm of the program's leaf less the reference's,
    against the larger of that leaf's reference norm and the median
    leaf's; the median leaf's."""
    med = statistics.median(norms.values())
    return statistics.median(
        float(np.linalg.norm(prog[k] - refn[k])) / max(norms[k], med)
        for k in norms)


def numbers(prog: dict, refo: dict, lr: float) -> dict:
    """Every number the check can compare, by name."""
    grads = refo["grad_norms"]
    med = statistics.median(grads.values())
    moved = [k for k, v in grads.items() if v >= 1e-3 * med]
    return {
        "tau_mismatch": float(np.sum(np.asarray(prog["taus"])
                                     != np.asarray(refo["taus"]))),
        "gamma_err": float(np.max(np.abs(np.asarray(prog["gammas"])
                                         - np.asarray(refo["gammas"]))))
        / lr,
        "loss1_rel": abs(prog["loss1"] - refo["loss1"]) / refo["loss1"],
        "grad_leaf_gap": leaf_gap(prog["grad_norms"], grads, list(grads)),
        "grad_diff_median": diff_median(prog["grad0"], refo["grad0"], grads),
        "change_leaf_gap": leaf_gap(prog["change_norms"],
                                    refo["change_norms"], moved),
    }


def compare(prog: dict, refo: dict, limits: dict, lr: float) -> list:
    """The numbers that the traffic's ``limits`` name, each with its
    limit."""
    got = numbers(prog, refo, lr)
    return [(n, got[n], float(lim)) for n, lim in limits.items()]


def half_batch():
    """A fault planted in the trainer's gradient: each worker's gradient is
    the mean over the first half of its batch's rows (of its tokens, where
    the batch is one row).  The held-out loss the trainer logs still sees
    the whole batch.  Returns the undo."""
    import types
    import repro.launch.train as train_mod

    def half(b):
        rows = b["tokens"].shape[0]
        if rows > 1:
            return {k: v[:rows // 2] for k, v in b.items()}
        return {k: v[:, :v.shape[1] // 2] for k, v in b.items()}

    def grad(f, *args, **kwargs):
        return jax.grad(lambda p, b: f(p, half(b)), *args, **kwargs)

    real = train_mod.jax
    train_mod.jax = types.SimpleNamespace(**dict(vars(jax), grad=grad))
    return lambda: setattr(train_mod, "jax", real)


def altered_step():
    """A fault planted in the trainer: the delay-adaptive policy's answer,
    the step-size, is altered by a thousandth where it is produced.
    Returns the undo."""
    from repro.core.stepsize import Adaptive1
    real = Adaptive1._gamma

    def altered(self, state, tau):
        gamma, clipped = real(self, state, tau)
        return gamma * 1.001, clipped
    Adaptive1._gamma = altered
    return lambda: setattr(Adaptive1, "_gamma", real)


# faults a training cell can have, read on the chip against the limits
FAULTS = {"half_batch": half_batch, "altered_step": altered_step}


def readings(state: State, seed: int, control=None) -> dict:
    """Every number of ``seed``'s first events, compared or not, without a
    window: of the program, or of the reference computed with ``control``
    operands (the control) in the program's place."""
    state.seed = seed
    if control is None:
        train_mod, real, wrapped = _capture(state, None, -1)
        train_mod.make_apply = wrapped
        try:
            state.log = _train(state.cfg, state.traffic, seed, CHECKED, 1)
        finally:
            train_mod.make_apply = real
        got = program_outputs(state)
        state.log = None
    else:
        got = reference_outputs(state, dtype=control,
                                precision=jax.lax.Precision.DEFAULT)
    return numbers(got, reference_outputs(state), float(state.traffic["lr"]))


def check(state: State, seed: int) -> list:
    prog = program_outputs(state)
    state.log = None
    return compare(prog, reference_outputs(state), state.traffic["limits"],
                   float(state.traffic["lr"]))

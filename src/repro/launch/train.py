"""Asynchronous delay-adaptive training driver.

Implements the paper's parameter-server semantics with REAL stale gradients
on one host: each simulated worker holds the gradient it computed on the
iterate version it last read; at each write event the arriving worker's
(stale) gradient is applied with the delay-adaptive step-size, and the worker
picks up the new iterate.  Memory = n_workers x grad size, so this runs a
~100M-parameter model with genuine gradient staleness.

    PYTHONPATH=src python -m repro.launch.train --preset 100m --steps 300
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b --reduced \
        --steps 50 --policy adaptive2
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.engine import heterogeneous_workers, simulate_parameter_server
from repro.core.stepsize import make_policy
from repro.data import EmbedStream, TokenStream
from repro.launch.steps import make_trainer
from repro.models import init_params, loss_fn
from repro.models.config import ModelConfig
from repro import telemetry
from repro.checkpoint import save_checkpoint
from repro.compile_cache import enable_compile_cache

PRESETS = {
    # ~103M params: the end-to-end driver scale
    "100m": ModelConfig(name="lm-100m", n_layers=12, d_model=768, n_heads=12,
                        n_kv_heads=4, head_dim=64, d_ff=2048, vocab=8192,
                        q_chunk=256),
    "25m": ModelConfig(name="lm-25m", n_layers=8, d_model=384, n_heads=8,
                       n_kv_heads=4, head_dim=48, d_ff=1024, vocab=4096,
                       q_chunk=256),
    "moe-tiny": ModelConfig(name="moe-tiny", family="moe", n_layers=6,
                            d_model=384, n_heads=8, n_kv_heads=8, head_dim=48,
                            d_ff=512, n_experts=8, top_k=2, moe_ff=512,
                            shared_ff=512, vocab=4096, q_chunk=256),
}


def make_stream(cfg: ModelConfig, batch: int, seq: int, seed: int = 0):
    if cfg.embed_inputs:
        return EmbedStream(d_model=cfg.d_model, vocab=cfg.vocab, batch=batch,
                           seq=seq, seed=seed, mrope=cfg.rope == "mrope")
    return TokenStream(vocab=cfg.vocab, batch=batch, seq=seq, seed=seed)


def event_trace(n_workers: int, steps: int, *, seed: int = 0,
                straggler: float = 0.05):
    """The host event trace ``run_training`` replays: which worker writes
    at each event and the iterate version it read (``read_at``), from
    heterogeneous workers with occasional 8x stragglers."""
    workers = heterogeneous_workers(n_workers, spread=2.0, seed=seed,
                                    p_straggle=straggler, straggle_x=8.0)
    return simulate_parameter_server(n_workers, steps, workers, seed=seed)


def make_apply(trainer):
    """The jitted write-event update ``(params, grads, opt, tau) ->
    (params, opt, gamma)``.  Params and optimizer state are updated in
    place: without donation a full-width model holds both generations at
    once (mamba2-780m: 17.2 GB of arguments + outputs against 16 GB on one
    v5e)."""
    return jax.jit(trainer.optimizer.step_fn, donate_argnums=(0, 2))


def _memory_counters() -> dict:
    """``bytes_in_use`` and ``peak_bytes_in_use`` of the default device,
    where its platform reports them (not on CPU).  Read at a log record,
    after its fetches have synced the host with the device."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def run_training(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
                 policy_name: str = "adaptive1", lr: float = 3e-3,
                 n_workers: int = 4, seed: int = 0, log_every: int = 10,
                 straggler: float = 0.05, out_dir: Optional[str] = None,
                 tau_bound_for_fixed: int = 8,
                 resume_from: Optional[str] = None,
                 save_every: int = 0):
    """Returns the metrics log (list of dicts)."""
    from repro.checkpoint import load_checkpoint
    key = jax.random.PRNGKey(seed)
    kwargs = {}
    if policy_name in ("fixed", "sun_deng"):
        kwargs["tau_bound"] = tau_bound_for_fixed
    policy = make_policy(policy_name, lr, **kwargs)
    trainer = make_trainer(cfg, policy=policy, n_workers=n_workers)
    state = trainer.init(key)
    start_step = 0
    if resume_from:
        (state,), meta = load_checkpoint(resume_from, (state,))
        start_step = int(meta.get("steps", 0))
        print(f"resumed from {resume_from} at step {start_step}")
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(state.params))
    print(f"model={cfg.name} params={n_params/1e6:.1f}M "
          f"policy={policy_name} gamma'={lr} workers={n_workers}")

    trace = event_trace(n_workers, steps, seed=seed, straggler=straggler)
    stream = make_stream(cfg, batch, seq, seed)

    # named programs: the XLA modules are jit_train_grad, jit_heldout_loss
    # and (make_apply) jit_step_fn, which profiler readers key on
    def train_grad(p, b):
        return loss_fn(p, cfg, b)[0]

    def heldout_loss(p, b):
        return loss_fn(p, cfg, b)[0]

    grad_fn = jax.jit(jax.grad(train_grad))
    loss_jit = jax.jit(heldout_loss)
    apply_jit = make_apply(trainer)

    # Algorithm-1 init: every worker computes a gradient at x_0
    pending = {}
    with telemetry.span("train.init_grads", workers=n_workers):
        for w in range(n_workers):
            pending[w] = (grad_fn(state.params, stream.batch_at(w)), 0)

    params, opt = state.params, state.opt
    log = []
    t0 = time.perf_counter()
    for k in range(steps):
        w = int(trace.worker[k])
        g, s_read = pending[w]
        with telemetry.step_span("train.event", start_step + k, worker=w,
                                 tau=k - s_read):
            tau = jnp.int32(k - s_read)
            with telemetry.span("train.apply"):
                params, opt, gamma = apply_jit(params, g, opt, tau)
            # worker w picks up x_{k+1} and computes its next gradient
            with telemetry.span("train.batch"):
                batch = stream.batch_at(n_workers + k)
            with telemetry.span("train.grad"):
                pending[w] = (grad_fn(params, batch), k + 1)
            if k % log_every == 0 or k == steps - 1:
                with telemetry.span("train.log"):
                    lv = float(loss_jit(params, stream.batch_at(10_000)))
                    rec = {"step": start_step + k, "loss": lv,
                           "gamma": float(gamma), "tau": int(tau),
                           "wall_s": time.perf_counter() - t0,
                           **_memory_counters()}
                    log.append(rec)
                    print(f"step {start_step + k:5d} loss {lv:.4f} "
                          f"gamma {float(gamma):.2e} tau {int(tau)} "
                          f"({rec['wall_s']:.1f}s)")
        if out_dir and save_every and (k + 1) % save_every == 0:
            os.makedirs(out_dir, exist_ok=True)
            from repro.launch.steps import TrainState
            save_checkpoint(os.path.join(out_dir, f"step_{start_step + k + 1}.npz"),
                            (TrainState(params=params, opt=opt),),
                            {"steps": start_step + k + 1,
                             "policy": policy_name})
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        from repro.launch.steps import TrainState
        save_checkpoint(os.path.join(out_dir, "final.npz"),
                        (TrainState(params=params, opt=opt),),
                        {"steps": start_step + steps, "policy": policy_name,
                         "final_loss": log[-1]["loss"]})
        with open(os.path.join(out_dir, "log.json"), "w") as f:
            json.dump(log, f, indent=1)
    return log


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--preset", choices=list(PRESETS))
    g.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke variant of --arch")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--policy", default="adaptive1",
                    choices=["adaptive1", "adaptive2", "fixed", "sun_deng",
                             "naive"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--resume-from", default=None,
                    help="checkpoint .npz to resume params+optimizer from")
    ap.add_argument("--save-every", type=int, default=0)
    args = ap.parse_args()

    if args.preset:
        cfg = PRESETS[args.preset]
    else:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    enable_compile_cache()
    run_training(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                 policy_name=args.policy, lr=args.lr, n_workers=args.workers,
                 seed=args.seed, out_dir=args.out,
                 resume_from=args.resume_from, save_every=args.save_every)


if __name__ == "__main__":
    main()

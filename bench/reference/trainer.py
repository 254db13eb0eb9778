"""A plain replay of the asynchronous trainer's first write events.

The trainer (``run_training``) makes its own inputs from its seed; this
module makes the same ones without it, from the recipes the trainer
states: initial weights (normal draws from ``PRNGKey(seed)`` split per
embedding and per layer, stored in bfloat16), token batches (a sparse
bigram process from ``numpy`` generators keyed by the seed and the batch
index), and the parameter server's event order (heterogeneous lognormal
workers with stragglers).  Then it replays the first events: each
returning worker's gradient at the iterate it read, clipped to global
norm 1, AdamW, and the delay-adaptive step-size of Eq. (13), with the
float32 model of ``bench.reference.mamba2``.
"""
from __future__ import annotations

import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mamba2
from bench.reference.piag import gammas as policy_gammas

B1, B2, ADAM_EPS, CLIP = 0.9, 0.95, 1e-8, 1.0
# leaves the trainer keeps in float32; every other leaf is stored in bfloat16
F32_LEAVES = ("A_log", "D", "dt_bias")


def init(seed: int, cfg: dict):
    """Initial weights as float32 copies of their bfloat16 values."""
    D, V, L = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    din, N, H = cfg["d_inner"], cfg["ssm_state"], cfg["heads"]
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def layer(key):
        k1, _ = jax.random.split(key)
        ks = jax.random.split(k1, 5)
        conv = din + 2 * N
        return {"ln": {"scale": jnp.ones((D,))}, "mixer": {
            "in_proj": bf(jax.random.normal(ks[0], (D, 2 * din + 2 * N + H))
                          * D ** -0.5),
            "conv_w": bf(jax.random.normal(ks[1], (cfg["conv"], conv)) * 0.1),
            "conv_b": jnp.zeros((conv,)),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)),
            "D": jnp.ones((H,)),
            "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, H))),
            "norm": jnp.ones((din,)),
            "out_proj": bf(jax.random.normal(ks[2], (din, D)) * din ** -0.5),
        }}

    @jax.jit
    def make(key):
        k_emb, k_layers, _, _ = jax.random.split(key, 4)
        k_tok, _ = jax.random.split(k_emb)
        return {"embed": {"tok": bf(jax.random.normal(k_tok, (V, D)) * 0.02)},
                "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
                "final_norm": {"scale": jnp.ones((D,))}}

    return make(jax.random.PRNGKey(seed))


class Tokens:
    """Token batches: each of 64 states prefers 4 next tokens; 85% of
    steps follow the preference, the rest draw uniformly."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed
        rng = np.random.default_rng(seed)
        K = min(64, vocab)
        self.next = rng.integers(0, vocab, size=(K, 4))
        self.state_of = rng.integers(0, K, size=(vocab,))

    def at(self, index: int):
        B, S = self.batch, self.seq
        rng = np.random.default_rng((self.seed, index))
        toks = np.zeros((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=(B,))
        noise, pick = rng.random((B, S)), rng.integers(0, 4, size=(B, S))
        rand = rng.integers(0, self.vocab, size=(B, S))
        for t in range(S):
            nxt = self.next[self.state_of[toks[:, t]], pick[:, t]]
            toks[:, t + 1] = np.where(noise[:, t] < 0.85, nxt, rand[:, t])
        return toks[:, :-1], toks[:, 1:]


def events(n_workers: int, steps: int, seed: int, straggler: float):
    """(worker, read_at) of the first ``steps`` write events: worker mean
    times log-spaced over [1, 2] and shuffled, lognormal (sigma 0.25) task
    times, a ``straggler`` chance of an 8x slower task; ties go to the
    task pushed first."""
    rng = np.random.default_rng(seed)
    means = np.geomspace(1.0, 2.0, n_workers)
    rng.shuffle(means)
    sigma, rng = 0.25, np.random.default_rng(seed + 1)

    def task(i):
        t = float(rng.lognormal(np.log(means[i]) - 0.5 * sigma ** 2, sigma))
        if straggler > 0 and rng.random() < straggler:
            t *= 8.0
        return t

    heap = [(task(i), i, i, 0) for i in range(n_workers)]
    heapq.heapify(heap)
    worker, read_at = [], []
    for k in range(steps):
        t, _, i, v = heapq.heappop(heap)
        worker.append(i)
        read_at.append(v)
        heapq.heappush(heap, (t + task(i), n_workers + k, i, k + 1))
    return worker, read_at


def _leaves(tree):
    return {jax.tree_util.keystr(p): l
            for p, l in jax.tree_util.tree_leaves_with_path(tree)}


def _keeps_f32(name: str) -> bool:
    return any(name.endswith(f"['{k}']") for k in F32_LEAVES)


@jax.jit
def _sum_squares(tree):
    return sum(jnp.sum(l * l) for l in jax.tree_util.tree_leaves(tree))


@partial(jax.jit, static_argnames=("keep_f32",), donate_argnums=(0, 1))
def _adamw(mu, nu, p, g, scale, gamma, c1, c2, *, keep_f32: bool):
    """One leaf of the trainer's update: the gradient clipped by ``scale``,
    AdamW's moments and bias correction, the step ``gamma`` and the
    parameter stored in the trainer's precision.  Also returns the norm of
    the clipped gradient."""
    g = g * scale
    mu = B1 * mu + (1 - B1) * g
    nu = B2 * nu + (1 - B2) * g * g
    p = p.astype(jnp.float32) - gamma * (mu / c1) / (jnp.sqrt(nu / c2)
                                                     + ADAM_EPS)
    return mu, nu, p if keep_f32 else p.astype(jnp.bfloat16), \
        jnp.linalg.norm(jnp.ravel(g))


@jax.jit
def diff_norm(a, b):
    """The norm of a - b in float32, fused so that no float32 copy of
    either is made."""
    return jnp.linalg.norm(jnp.ravel(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))


def replay(cfg: dict, traffic: dict, seed: int, *, steps: int = 3,
           dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST) -> dict:
    """The first ``steps`` write events of the trainer: taus, step-sizes,
    the held-out loss after the first event, the first gradient as the
    optimizer gets it (clipped; ``grad0``, per leaf on the host) with its
    per-leaf norms, and the norms of the parameters' change over the
    ``steps`` events.

    Between gradients the iterates, AdamW's moments and the versions the
    pending workers read wait on the host; the chip holds one float32
    gradient with its activations, and updates one leaf at a time."""
    n, batch, seq = traffic["workers"], traffic["batch"], traffic["seq"]
    data = Tokens(cfg["vocab"], batch, seq, seed)
    worker, read_at = events(n, steps, seed, traffic["straggler"])
    taus = [k - v for k, (w, v) in enumerate(zip(worker, read_at))]
    gam = policy_gammas(traffic["policy"], traffic["lr"], taus, tau_bar=0)

    lossf = jax.jit(lambda p, t, y: mamba2.loss(p, t, y, cfg, dtype,
                                                 precision))
    gradf = jax.jit(jax.grad(lambda p, t, y: mamba2.loss(p, t, y, cfg, dtype,
                                                         precision)))
    p0 = init(seed, cfg)
    treedef = jax.tree_util.tree_structure(p0)
    names = list(_leaves(p0))
    stored = lambda k, v: v if _keeps_f32(k) else v.astype(jnp.bfloat16)
    versions = {0: {k: np.asarray(stored(k, v))
                    for k, v in _leaves(p0).items()}}
    del p0
    chip = lambda leaves: jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(leaves[k]).astype(jnp.float32) for k in names])
    pending = {w: (0, w) for w in range(n)}  # worker -> (version, batch)
    mu = {k: np.zeros(v.shape, np.float32) for k, v in versions[0].items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in versions[0].items()}
    out = {}
    for k in range(steps):
        w = worker[k]
        version, index = pending[w]
        g = _leaves(gradf(chip(versions[version]),
                          *map(jnp.asarray, data.at(index))))
        scale = jnp.minimum(1.0, CLIP / jnp.maximum(
            jnp.sqrt(_sum_squares(g)), 1e-12))
        c1, c2 = 1 - B1 ** (k + 1), 1 - B2 ** (k + 1)
        new, norms = {}, {}
        for key in names:
            m, v, p, gn = _adamw(jnp.asarray(mu[key]), jnp.asarray(nu[key]),
                                 jnp.asarray(versions[k][key]), g.pop(key),
                                 scale, np.float32(gam[k]), c1, c2,
                                 keep_f32=_keeps_f32(key))
            mu[key], nu[key], new[key] = map(np.asarray, (m, v, p))
            norms[key] = float(gn)
        if k == 0:
            out["grad_norms"] = norms
            out["grad0"] = {key: m / (1 - B1) for key, m in mu.items()}
        versions[k + 1] = new
        pending[w] = (k + 1, n + k)
        keep = {v for v, _ in pending.values()} | {0, k + 1}
        versions = {v: p for v, p in versions.items() if v in keep}
        if k == 0:
            out["loss1"] = float(lossf(chip(new), *map(
                jnp.asarray, data.at(10_000))))
    out["change_norms"] = {key: float(diff_norm(
        jnp.asarray(versions[steps][key]), jnp.asarray(versions[0][key])))
        for key in names}
    out.update(taus=taus, gammas=gam.tolist())
    return out

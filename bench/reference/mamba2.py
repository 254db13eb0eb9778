"""A plain Mamba2 language model (arXiv:2405.21060) in float32.

Written from the paper, in the layout of the program's parameter tree so
that leaves can be compared by name:

    x = embed[tokens]
    per layer:  x = x + out_proj(gated_norm(ssd(conv(in_proj(rmsnorm(x))))))
    logits = rmsnorm(x) @ embed^T          (tied embeddings)

The state-space part is the paper's minimal chunked SSD ("ssd_minimal"):
the quadratic form inside chunks of ``chunk`` steps, and the recurrence
between chunks.  Every matrix product runs at ``precision`` in ``dtype``
operands with float32 accumulation; the reference is float32 at the
highest precision, a lower ``dtype`` is the control.  Layers run one at a
time under ``jax.checkpoint`` inside a scan, so the backward pass holds
one layer's activations.

Departures from the published model, which the program shares: norms use
eps 1e-6 (the paper's code: 1e-5); one group of B and C.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6


def _dot(spec, a, b, dtype, precision):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=precision, preferred_element_type=F32)


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * scale


def segsum(a):
    """(..., T) -> (..., T, T): sum of a over (s, t] below the diagonal,
    a large negative number above it."""
    c = jnp.cumsum(a, axis=-1)
    seg = c[..., :, None] - c[..., None, :]
    T = a.shape[-1]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -1e30)


def ssd(x, a, B, C, chunk, dtype, precision):
    """y_t = sum_{s<=t} C_t.B_s exp(a_{s+1} + ... + a_t) x_s, with x
    already scaled by dt and a = dt * A.  x (b,l,h,p), a (b,l,h),
    B and C (b,l,h,n)."""
    b, l, h, p = x.shape
    c = l // chunk
    r = lambda t: t.reshape((b, c, chunk) + t.shape[2:])
    x, B, C = r(x), r(B), r(C)
    a = jnp.moveaxis(r(a), -1, 1)                          # b h c l
    acum = jnp.cumsum(a, axis=-1)
    L = jnp.exp(segsum(a))                                  # b h c l l
    dot = partial(_dot, dtype=dtype, precision=precision)
    y_diag = dot("bclhn,bcshn->bhcls", C, B) * L
    y_diag = dot("bhcls,bcshp->bclhp", y_diag, x)
    decay = jnp.exp(acum[..., -1:] - acum)                  # b h c l
    states = dot("bclhn,bclhp->bchpn", B * jnp.moveaxis(decay, 1, -1)[
        ..., None], x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                        precision=precision)[:, :-1]
    out_decay = jnp.moveaxis(jnp.exp(acum), 1, -1)          # b c l h
    y_off = dot("bclhn,bchpn->bclhp", C * out_decay[..., None], states)
    return (y_diag + y_off).reshape(b, l, h, p)


def mixer(p, x, cfg, dtype, precision):
    """One Mamba2 mixer on x (b, l, d)."""
    b, l, _ = x.shape
    din, N, H, P = cfg["d_inner"], cfg["ssm_state"], cfg["heads"], \
        cfg["head_dim"]
    dot = partial(_dot, dtype=dtype, precision=precision)
    zxbcdt = dot("bld,dp->blp", x, p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :din], zxbcdt[..., din:2 * din + 2 * N],
                  zxbcdt[..., 2 * din + 2 * N:])
    K = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + l] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    xs = xbc[..., :din].reshape(b, l, H, P)
    Bm = jnp.broadcast_to(xbc[..., din:din + N][:, :, None], (b, l, H, N))
    Cm = jnp.broadcast_to(xbc[..., din + N:][:, :, None], (b, l, H, N))
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # b l h
    A = -jnp.exp(p["A_log"])
    y = ssd(xs * dt[..., None], dt * A, Bm, Cm, cfg["chunk"], dtype,
            precision)
    y = (y + xs * p["D"][:, None]).reshape(b, l, din)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"])
    return dot("blp,pd->bld", y, p["out_proj"])


def loss(params, tokens, targets, cfg, dtype=F32,
         precision=jax.lax.Precision.HIGHEST):
    """Mean next-token cross-entropy of float32 ``params``."""
    x = params["embed"]["tok"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        return x + mixer(lp["mixer"], rmsnorm(x, lp["ln"]["scale"]), cfg,
                         dtype, precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"])
    logits = _dot("bld,vd->blv", x, params["embed"]["tok"], dtype, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

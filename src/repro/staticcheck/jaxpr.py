"""Canonical text form of jaxprs, for structural comparison.

``jax.make_jaxpr`` output is almost-but-not-quite comparable: variable
names depend on trace order and counter state, equation ``source_info``
carries file/line noise, param dicts print in insertion order, and object
reprs leak memory addresses.  This module renders a ``ClosedJaxpr`` to a
deterministic list of lines such that two traces of semantically identical
programs produce identical text:

* variables are alpha-renamed ``v0, v1, ...`` in first-appearance order
  (constvars, then invars, then eqn outputs in program order);
* equations keep program order, with params sorted by name;
* ``source_info`` is simply never rendered;
* nested jaxprs (``pjit``/``scan``/``while``/``cond`` branches,
  ``pallas_call`` kernels, ``shard_map`` bodies) recurse with a fresh
  naming scope;
* array-valued consts and params are summarized as
  ``dtype[shape]#<sha1 prefix>`` so captured data participates in
  identity without dumping buffers;
* any residual repr is scrubbed of ``0x...`` addresses.

The canonical lines feed :func:`fingerprint` (sha1) for cheap equality and
:func:`diff` (unified diff) for readable contract-violation reports.
"""
from __future__ import annotations

import hashlib
import re
from difflib import unified_diff
from typing import Any, Dict, List, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

__all__ = ["canonical_lines", "canonical_text", "fingerprint", "diff",
           "assert_identical", "io_avals"]

_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def _hash_bytes(data: bytes) -> str:
    return hashlib.sha1(data).hexdigest()[:12]


def _array_token(arr: Any) -> str:
    a = np.asarray(arr)
    flat = np.ascontiguousarray(a).reshape(-1)
    if flat.size > 65536:
        flat = np.ascontiguousarray(flat[:: flat.size // 65536 + 1])
    return f"{a.dtype}[{','.join(map(str, a.shape))}]#{_hash_bytes(flat.tobytes())}"


def _render_value(val: Any, depth: int) -> str:
    """Deterministic rendering of an eqn param / const value."""
    if isinstance(val, (ClosedJaxpr, Jaxpr)):
        inner = canonical_lines(val)
        pad = "  " * (depth + 1)
        return "{\n" + "\n".join(pad + ln for ln in inner) + "\n" + "  " * depth + "}"
    if isinstance(val, (tuple, list)):
        body = ", ".join(_render_value(v, depth) for v in val)
        return ("(" + body + ")") if isinstance(val, tuple) else ("[" + body + "]")
    if isinstance(val, dict):
        body = ", ".join(f"{k}={_render_value(v, depth)}"
                         for k, v in sorted(val.items(), key=lambda kv: str(kv[0])))
        return "{" + body + "}"
    if isinstance(val, (np.ndarray, jax.Array)):
        return _array_token(val)
    if isinstance(val, (bool, int, float, complex, str, bytes)) or val is None:
        return repr(val)
    if callable(val):
        name = getattr(val, "__name__", type(val).__name__)
        return f"<fn {name}>"
    return _ADDR.sub("0x~", repr(val))


class _Namer:
    """Alpha-renaming scope: Var -> ``v<n>`` in first-appearance order."""

    def __init__(self):
        self.names: Dict[Var, str] = {}

    def __call__(self, v: Any) -> str:
        if isinstance(v, Literal):
            val = v.val
            if isinstance(val, (np.ndarray, jax.Array)) and np.ndim(val) > 0:
                return _array_token(val)
            return f"lit:{_render_value(np.asarray(val).item() if isinstance(val, (np.ndarray, jax.Array)) else val, 0)}"
        name = self.names.get(v)
        if name is None:
            name = f"v{len(self.names)}"
            self.names[v] = name
        return f"{name}:{v.aval.str_short()}"


def canonical_lines(closed: Any) -> List[str]:
    """Render a ``ClosedJaxpr`` (or bare ``Jaxpr``) to canonical lines."""
    if isinstance(closed, ClosedJaxpr):
        jaxpr, consts = closed.jaxpr, closed.consts
    else:
        jaxpr, consts = closed, ()
    name = _Namer()
    lines: List[str] = []
    for i, cv in enumerate(jaxpr.constvars):
        const = consts[i] if i < len(consts) else "<abstract>"
        tok = (_array_token(const)
               if isinstance(const, (np.ndarray, jax.Array))
               else _render_value(const, 0))
        lines.append(f"const {name(cv)} = {tok}")
    lines.append("in  (" + ", ".join(name(v) for v in jaxpr.invars) + ")")
    for eqn in jaxpr.eqns:
        ins = ", ".join(name(v) for v in eqn.invars)
        outs = ", ".join(name(v) for v in eqn.outvars)
        params = " ".join(
            f"{k}={_render_value(v, 1)}"
            for k, v in sorted(eqn.params.items(), key=lambda kv: kv[0]))
        line = f"{outs} = {eqn.primitive.name}[{params}]({ins})"
        lines.append(_ADDR.sub("0x~", line))
    lines.append("out (" + ", ".join(name(v) for v in jaxpr.outvars) + ")")
    return lines


def canonical_text(closed: Any) -> str:
    return "\n".join(canonical_lines(closed))


def fingerprint(closed: Any) -> str:
    """sha1 of the canonical text -- equal iff structurally identical."""
    return hashlib.sha1(canonical_text(closed).encode()).hexdigest()


def diff(a: Any, b: Any, label_a: str = "a", label_b: str = "b") -> str:
    """Unified diff of two canonical jaxprs ('' when identical)."""
    la, lb = canonical_lines(a), canonical_lines(b)
    return "\n".join(unified_diff(la, lb, fromfile=label_a, tofile=label_b,
                                  lineterm=""))


def assert_identical(a: Any, b: Any, label: str = "jaxpr contract") -> None:
    d = diff(a, b)
    if d:
        head = "\n".join(d.splitlines()[:60])
        raise AssertionError(f"{label}: canonical jaxprs differ\n{head}")


def io_avals(closed: ClosedJaxpr) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(input avals, output avals) as short strings -- the interface
    signature two engines must agree on even when their bodies differ."""
    return (tuple(a.str_short() for a in closed.in_avals),
            tuple(a.str_short() for a in closed.out_avals))

"""Bounded cache of built sweep executables (`repro.sweep.cache`).

Every ``sweep_*`` / ``sharded_sweep_*`` call used to rebuild its per-bucket
cell closure and re-``jit`` it -- so a ragged grid re-traced one program per
bucket on EVERY call, and repeated ``api.run`` invocations of the same spec
paid the full compile again.  ``jax.jit`` caches traces per *function
object*; the missing piece is keeping the function objects alive and keyed.

``cached_program(key, build)`` is that piece: an LRU keyed on the program's
static configuration -- ``(solver tag, bucket width, masked?, horizon,
record_every, ... , captured objects)``.  Captured objects (loss closures,
data pytrees, prox ops) are keyed by IDENTITY via ``IdKey``; meshes ride
keys as ``repro.mesh.mesh_topology`` tuples -- TOPOLOGY, not identity, so a
reshaped or rebuilt mesh with the same axes/shape/device-kind/process-count
reuses the executable while a 1-D vs 2-D reshape keys fresh.  The
cache holds a strong reference through the key, so an id can never be
recycled while its entry lives.  Two calls that pass the *same* objects and
static knobs therefore reuse the same jitted callable -- and jax's own
shape-keyed trace cache underneath it -- while different objects (or a
mutated knob) build fresh.

The cache is deliberately small and clearable: programs pin their captured
constants (worker data!) in memory, so eviction is as important as reuse.

CONTRACT: identity keying means captured arrays are treated as FROZEN --
mutating a numpy ``worker_data`` buffer in place between sweeps would keep
serving the executable compiled against the old contents (the same is true
of any jit-captured constant, but before this cache each call re-traced and
re-read).  Treat sweep inputs as immutable, or build new arrays; after an
in-place mutation, call ``clear_program_cache()``.

``REPRO_CACHE_CHECK=1`` turns that contract into a runtime assertion:
array-valued captures are fingerprinted (shape/dtype + content hash) when
their entry is built and re-verified on every cache hit, so an in-place
mutation raises instead of silently serving the stale executable.

``set_capture_hook`` lets ``repro.staticcheck`` intercept ``cached_program``
dispatches -- the hook sees ``(key, build)`` and substitutes its own
callable, bypassing the cache entirely -- to record cache keys and traced
jaxprs without compiling or executing anything.
"""
from __future__ import annotations

import hashlib
import os
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np

from repro.telemetry.timing import timed

__all__ = ["IdKey", "LRU", "tree_key", "cached_program",
           "clear_program_cache", "mesh_fingerprint", "program_cache_stats",
           "set_capture_hook", "PROGRAM_CACHE_MAXSIZE"]

PROGRAM_CACHE_MAXSIZE = 128


class IdKey:
    """Identity-keyed cache handle: hashes/compares by ``id(obj)`` while
    holding a strong reference, so the id stays valid for the entry's life."""

    __slots__ = ("obj",)

    def __init__(self, obj: Any):
        self.obj = obj

    def __hash__(self) -> int:
        return id(self.obj)

    def __eq__(self, other) -> bool:
        return isinstance(other, IdKey) and self.obj is other.obj

    def __repr__(self) -> str:
        return f"IdKey({type(self.obj).__name__}@{id(self.obj):#x})"


def tree_key(tree: Any) -> Tuple:
    """Identity key of a pytree: one ``IdKey`` per leaf (None for a leafless
    tree).  Array leaves are unhashable by design; identity is the right
    equivalence for captured constants -- same arrays, same program."""
    return tuple(IdKey(leaf) for leaf in jax.tree_util.tree_leaves(tree))


class LRU:
    """Tiny LRU keyed on hashable tuples; also reused by ``repro.api`` to
    memoize resolve-time artifacts (problems, prox ops, runner pieces)."""

    def __init__(self, maxsize: int,
                 on_evict: Optional[Callable[[Any], None]] = None):
        self.maxsize = maxsize
        self.data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.on_evict = on_evict

    def get(self, key, build: Callable[[], Any]):
        try:
            val = self.data[key]
        except KeyError:
            self.misses += 1
            val = build()
            self.data[key] = val
            while len(self.data) > self.maxsize:
                evicted, _ = self.data.popitem(last=False)
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(evicted)
            return val
        self.hits += 1
        self.data.move_to_end(key)
        return val


_PROGRAMS = LRU(PROGRAM_CACHE_MAXSIZE,
                on_evict=lambda key: _FINGERPRINTS.pop(key, None))

# bumped by clear_program_cache(); snapshot consumers (api.run's per-call
# cache deltas) compare generations to detect that the absolute counters
# were reset between their snapshots
_GENERATION = 0

# REPRO_CACHE_CHECK fingerprints, keyed like _PROGRAMS (pruned on eviction)
_FINGERPRINTS: dict = {}

# staticcheck's dispatch interceptor; None in normal operation
_CAPTURE_HOOK: Optional[Callable[[Tuple, Callable[[], Any]], Any]] = None


def set_capture_hook(hook):
    """Install ``hook(key, build)`` to intercept every ``cached_program``
    dispatch (pass ``None`` to uninstall); returns the previous hook.  While
    installed, the cache is bypassed entirely: the hook's return value is
    handed back to the runner in place of the cached executable.  This is
    the seam ``repro.staticcheck.cachekey`` uses to observe cache keys and
    capture traced jaxprs without compiling."""
    global _CAPTURE_HOOK
    prev = _CAPTURE_HOOK
    _CAPTURE_HOOK = hook
    return prev


def _cache_check_enabled() -> bool:
    return (os.environ.get("REPRO_CACHE_CHECK", "").strip().lower()
            in ("1", "true", "yes", "on"))


def _captured_arrays(key: Any, path: str = "key"):
    """Yield ``(path, IdKey)`` for every identity-keyed array inside a
    (possibly nested) key tuple -- numpy buffers and jax Arrays both; other
    captures (closures, prox ops) have no mutable numeric payload worth
    hashing.  Meshes are fingerprinted separately (``_captured_meshes``)."""
    if isinstance(key, tuple):
        for i, el in enumerate(key):
            yield from _captured_arrays(el, f"{path}[{i}]")
    elif isinstance(key, IdKey) and isinstance(key.obj, (np.ndarray, jax.Array)):
        yield path, key


def _captured_meshes(key: Any, path: str = "key"):
    """Yield ``(path, Mesh)`` for every ``jax.sharding.Mesh`` inside a key,
    raw or ``IdKey``-wrapped.  The sharded runners key by
    ``repro.mesh.mesh_topology`` tuples (plain hashables, nothing to
    fingerprint), but external/legacy keys may still carry Mesh objects --
    those fingerprint by TOPOLOGY (axis names, shape, device kind, process
    count), not value identity, matching the runner contract that
    same-topology meshes share executables."""
    if isinstance(key, tuple):
        for i, el in enumerate(key):
            yield from _captured_meshes(el, f"{path}[{i}]")
    elif isinstance(key, jax.sharding.Mesh):
        yield path, key
    elif isinstance(key, IdKey) and isinstance(key.obj, jax.sharding.Mesh):
        yield path, key.obj


def mesh_fingerprint(mesh) -> str:
    """Topology fingerprint of a mesh: stringified
    ``repro.mesh.mesh_topology`` (axis names + shape + device kind +
    process count)."""
    from repro.mesh import mesh_topology
    return str(mesh_topology(mesh))


def _array_fingerprint(obj: Any) -> str:
    try:
        arr = np.asarray(obj)
    except Exception as exc:  # deleted buffer (e.g. donated jax Array)
        return f"<unreadable:{type(exc).__name__}>"
    h = hashlib.sha1()
    h.update(str(arr.shape).encode())
    h.update(str(arr.dtype).encode())
    flat = np.ascontiguousarray(arr).reshape(-1)
    if flat.size > 65536:  # cheap strided sample for big buffers
        flat = np.ascontiguousarray(flat[:: flat.size // 65536 + 1])
    h.update(flat.tobytes())
    return h.hexdigest()


def _key_fingerprints(key: Tuple) -> Tuple:
    return (tuple((path, _array_fingerprint(ik.obj))
                  for path, ik in _captured_arrays(key)) +
            tuple((path, mesh_fingerprint(m))
                  for path, m in _captured_meshes(key)))


def _verify_fingerprints(key: Tuple) -> None:
    fresh = _key_fingerprints(key)
    prior = _FINGERPRINTS.get(key)
    if prior is None:
        _FINGERPRINTS[key] = fresh
        return
    if prior == fresh:
        return
    changed = [p for (p, a), (_, b) in zip(prior, fresh) if a != b]
    raise RuntimeError(
        "REPRO_CACHE_CHECK: captured array(s) mutated in place after "
        f"capture by cached_program (key tag {key[0]!r}, changed: "
        f"{', '.join(changed)}).  Identity-keyed captures are FROZEN by "
        "contract -- the cache would have kept serving the executable "
        "compiled against the old contents.  Build new arrays instead of "
        "mutating, or call clear_program_cache() after an intentional "
        "mutation.")


class _TimedFirstCall:
    """Callable proxy recording the first dispatch of a freshly built
    program as a ``program_first_call`` timing event -- on CPU, jax compiles
    synchronously inside that call, so its wall time is the per-key compile
    cost the run ledger attributes.  Subsequent calls go straight through."""

    __slots__ = ("fn", "tag", "pending")

    def __init__(self, fn: Callable, tag: str):
        self.fn = fn
        self.tag = tag
        self.pending = True

    def __call__(self, *args, **kwargs):
        if not self.pending:
            return self.fn(*args, **kwargs)
        self.pending = False
        with timed("program_first_call", key=self.tag):
            return self.fn(*args, **kwargs)


def cached_program(key: Tuple, build: Callable[[], Any]):
    """Return the cached executable for ``key``, building (and caching) it on
    first use.  ``key`` must be a tuple of hashables; wrap captured objects
    in ``IdKey`` / ``tree_key``.

    Misses are instrumented: ``build()`` wall time lands in the telemetry
    timing buffer as ``program_build``, and callable programs come back
    wrapped so their first dispatch records ``program_first_call``."""
    if _CAPTURE_HOOK is not None:
        return _CAPTURE_HOOK(key, build)
    if _cache_check_enabled():
        _verify_fingerprints(key)

    def timed_build():
        tag = str(key[0]) if key else "?"
        with timed("program_build", key=tag):
            val = build()
        return _TimedFirstCall(val, tag) if callable(val) else val

    return _PROGRAMS.get(key, timed_build)


def clear_program_cache() -> None:
    """Drop every cached executable (tests; memory pressure).  Bumps the
    stats generation so per-call deltas can reset-scope correctly."""
    global _GENERATION
    _PROGRAMS.data.clear()
    _PROGRAMS.hits = _PROGRAMS.misses = _PROGRAMS.evictions = 0
    _FINGERPRINTS.clear()
    _GENERATION += 1


def program_cache_stats() -> dict:
    return {"size": len(_PROGRAMS.data), "hits": _PROGRAMS.hits,
            "misses": _PROGRAMS.misses, "evictions": _PROGRAMS.evictions,
            "maxsize": _PROGRAMS.maxsize, "generation": _GENERATION}

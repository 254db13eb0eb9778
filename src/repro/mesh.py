"""Mesh construction, topology keys, and data-parallel gradients.

This is the ONE mesh module: it owns

* the sweep meshes -- :func:`cell_mesh` (1-D ``("cells",)``) and
  :func:`grid_mesh` (2-D ``("cells", "data")``) used by the sharded sweep
  backend in :mod:`repro.sweep.shard`;
* :func:`mesh_topology`, the hashable token that stands in for a ``Mesh``
  inside program-cache keys (axis names + shape + device kind + process
  count -- NOT object identity, so 1-D/2-D/multi-host variants never share
  an executable while same-topology meshes deliberately do);
* :func:`pmean_grad`, the psum-backed gradient transform that makes a
  per-worker gradient data-parallel across the ``"data"`` mesh axis;
* :func:`maybe_init_distributed`, the ``jax.distributed`` bootstrap behind
  ``ExecutionSpec``'s multi-host knobs;
* the production mesh builders and the parameter/batch/cache sharding
  planner (absorbed from the seed-state ``launch/mesh.py`` and
  ``launch/sharding.py``, which now re-export from here).

Everything is a FUNCTION so importing this module never touches jax device
state (device count is locked at first jax init; dry-runs set XLA_FLAGS
before importing anything else).

psum-axis contract
------------------
``pmean_grad(loss, axis, size)`` is exact (up to one float rounding of the
final ``/ size``) for losses of the form

    mean over a leading sample axis of per-sample terms  +  x-only terms,

which covers both built-in problem classes (``LogRegProblem.worker_loss``
and ``LassoProblem.worker_loss``).  Each shard takes the mean over its
``S / size`` local samples; ``psum / size`` reconstructs the global mean,
and the x-only regulariser -- identical on every shard -- is returned
unchanged (bitwise for power-of-two ``size``).  The sample count ``S`` must
divide by ``size``; anything else raises loudly at trace time rather than
silently dropping samples.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CELL_AXIS = "cells"
DATA_AXIS = "data"


# ---------------------------------------------------------------------------
# sweep meshes
# ---------------------------------------------------------------------------

def cell_mesh(devices: Optional[Sequence[Any]] = None) -> Mesh:
    """1-D mesh over ``devices`` (default: all local) with axis "cells"."""
    devs = np.array(devices if devices is not None else jax.devices())
    return Mesh(devs, (CELL_AXIS,))


def grid_mesh(mesh_shape: Tuple[int, ...],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Mesh with axes ``("cells",)`` or ``("cells", "data")``.

    ``mesh_shape`` is ``(cells,)`` or ``(cells, data)``.  Uses the first
    ``prod(mesh_shape)`` of ``devices`` (default ``jax.devices()``, which
    spans all processes in a multi-host run); raises if fewer are
    available -- a silent fallback would quietly serialize the data axis.
    """
    shape = tuple(int(s) for s in mesh_shape)
    if not 1 <= len(shape) <= 2 or any(s < 1 for s in shape):
        raise ValueError(
            f"mesh_shape must be (cells,) or (cells, data) with positive "
            f"entries, got {mesh_shape!r}")
    need = int(np.prod(shape))
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < need:
        raise ValueError(
            f"mesh_shape {shape} needs {need} devices, "
            f"only {len(devs)} available")
    axes = (CELL_AXIS,) if len(shape) == 1 else (CELL_AXIS, DATA_AXIS)
    return Mesh(np.array(devs[:need]).reshape(shape), axes)


def cell_axis_size(mesh: Mesh) -> int:
    """Size of the "cells" axis (the grid-partition axis)."""
    if CELL_AXIS not in mesh.axis_names:
        raise ValueError(
            f"sweep meshes need a {CELL_AXIS!r} axis; got axes "
            f"{tuple(mesh.axis_names)} -- build one with cell_mesh() or "
            f"grid_mesh()")
    return int(mesh.shape[CELL_AXIS])


def data_axis_size(mesh: Mesh) -> int:
    """Size of the "data" axis; 1 when the mesh has no data axis."""
    return int(mesh.shape.get(DATA_AXIS, 1)) if DATA_AXIS in mesh.axis_names \
        else 1


def mesh_topology(mesh: Mesh) -> Tuple[Any, ...]:
    """Hashable cache-key token for a mesh: its topology, not its identity.

    ``(tag, axis names, shape, device kind, process count)``.  Two meshes
    over the same device kind with the same axes/shape share executables
    (cells are placement-agnostic); reshaping the same devices from (8,) to
    (4, 2) keys fresh because shape and axis names differ.
    """
    dev = mesh.devices.ravel()[0]
    kind = str(getattr(dev, "device_kind", None) or
               getattr(dev, "platform", "unknown"))
    return ("mesh", tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            kind, int(jax.process_count()))


# ---------------------------------------------------------------------------
# data-parallel gradients
# ---------------------------------------------------------------------------

def pmean_grad(loss_fn: Callable, axis: str = DATA_AXIS,
               size: int = 1) -> Callable:
    """Data-parallel ``jax.grad(loss_fn)`` for use inside shard_map.

    Returns ``grad_fn(x, *data)`` that slices each data leaf's leading
    sample axis by this shard's ``axis_index``, differentiates the loss on
    the local slice, and ``psum / size``s the result back to the full
    gradient.  Data stays replicated (captured) -- only gradient COMPUTE is
    partitioned, so outputs remain identical on every data shard and the
    cell-axis out_specs need no change.

    See the module docstring for the exactness contract (sample-mean +
    x-only losses; ``S % size == 0`` enforced at trace time).
    """
    grad = jax.grad(loss_fn)
    if size <= 1:
        return grad

    def grad_fn(x, *data):
        i = jax.lax.axis_index(axis)

        def shard(leaf):
            s = int(leaf.shape[0])
            if s % size:
                raise ValueError(
                    f"pmean_grad: leading sample axis ({s}) must divide by "
                    f"the {axis!r} mesh axis size ({size}); pad the worker "
                    f"slices or pick a mesh_shape whose data axis divides "
                    f"the per-worker sample count")
            loc = s // size
            return jax.lax.dynamic_slice_in_dim(leaf, i * loc, loc, axis=0)

        g = grad(x, *[shard(leaf) for leaf in data])
        return jax.tree_util.tree_map(
            lambda leaf: jax.lax.psum(leaf, axis) / size, g)

    return grad_fn


# ---------------------------------------------------------------------------
# multi-host bootstrap
# ---------------------------------------------------------------------------

_DISTRIBUTED_INITIALIZED = False


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int) -> None:
    """``jax.distributed.initialize`` wrapper (idempotent per process)."""
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=int(process_id))
    _DISTRIBUTED_INITIALIZED = True


def maybe_init_distributed(execution) -> bool:
    """Bootstrap jax.distributed from an ``ExecutionSpec``-like object.

    No-op (returns False) unless ``execution.coordinator`` is set.  The
    knobs never reach a traced program -- their only cache-key footprint is
    the process count inside :func:`mesh_topology`.
    """
    coordinator = getattr(execution, "coordinator", None)
    if not coordinator:
        return False
    init_distributed(coordinator,
                     getattr(execution, "num_processes", 1),
                     getattr(execution, "process_id", 0))
    return True


# ---------------------------------------------------------------------------
# production meshes (absorbed from launch/mesh.py)
# ---------------------------------------------------------------------------

def make_production_mesh(*, multi_pod: bool = False):
    """Single pod 16x16 ("data","model"); multi-pod 2x16x16 adds "pod"."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh (used by reduced-size tests, e.g. (2, 4)).

    Axes are ``Auto``: the planner places params and batches with
    ``param_shardings`` / ``batch_shardings`` and leaves the rest to the
    SPMD partitioner.  (``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which the embedding gather of a batch-sharded token array from a
    data-sharded table has no single output sharding.)"""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


# ---------------------------------------------------------------------------
# sharding planner (absorbed from launch/sharding.py)
#
# Rules (divisibility-checked -- any dim not divisible by its axis size is
# left replicated rather than unevenly sharded):
#
# * parameters: the largest divisible feature dim goes to "model" (ties
#   break toward the *later* dim, i.e. column-parallel for up-projections
#   and row-parallel for down-projections); a second divisible dim goes to
#   the data axes (FSDP/ZeRO-3) so the 236B config fits 16 GB/chip.  The
#   leading stacked-layers axis is never sharded (it is scanned over).
# * MoE expert tensors: the expert dim goes to "model" when divisible
#   (expert parallelism, e.g. deepseek's 160 experts on 16-way model axis);
#   otherwise falls back to the feature rule (qwen2-moe's 60 experts).
# * batches: the global-batch dim is sharded over ("pod","data");
#   everything else replicated.  long_500k (batch=1) shards the cache
#   sequence dim over the data axes instead (context parallelism).
# * optimizer state: same rule as its parameter (identical shapes).
# ---------------------------------------------------------------------------

def _key_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(f"#{k.idx}")
    return tuple(names)


def _param_spec(names: Tuple[str, ...], shape: Tuple[int, ...], mesh,
                fsdp: bool = True, small_out_threshold: int = 0) -> P:
    md = model_size(mesh)
    dps = dp_axes(mesh)
    dsz = dp_size(mesh)
    ndim = len(shape)
    spec: list = [None] * ndim

    # leading stacked-layers axis (params under "layers"/"shared" groups are
    # stacked (L, ...) or (G, ...)): never sharded
    start = 1 if ("layers" in names and ndim >= 2) else 0
    cand = list(range(start, ndim))

    # expert parallelism: 4-D (L, E, D, F) expert tensors
    model_dim: Optional[int] = None
    if any("w" in n for n in names) and "moe" in names and ndim >= 4:
        e_dim = start
        if shape[e_dim] % md == 0:
            model_dim = e_dim
    if model_dim is None:
        best = -1
        for i in cand:
            if md > 1 and shape[i] % md == 0 and shape[i] >= md:
                if shape[i] >= best:
                    best = shape[i]
                    model_dim = i
    # §Perf H2: row-parallel sharding of a projection with a SMALL output
    # (e.g. MLA's w_dkv: 5120 -> 576) forces a per-token all-reduce of the
    # partial sums that dwarfs the weight itself -- replicate over "model"
    # (FSDP still shards it over data) instead.
    if (small_out_threshold and model_dim is not None and ndim >= 2 and
            model_dim == ndim - 2 and shape[-1] <= small_out_threshold):
        model_dim = None
    if model_dim is not None and md > 1:
        spec[model_dim] = "model"

    if fsdp and dps:
        best = -1
        fsdp_dim = None
        for i in cand:
            if i == model_dim:
                continue
            if shape[i] % dsz == 0 and shape[i] >= dsz:
                if shape[i] > best:
                    best = shape[i]
                    fsdp_dim = i
        if fsdp_dim is not None:
            spec[fsdp_dim] = dps if len(dps) > 1 else dps[0]
    return P(*spec)


def param_shardings(tree: Any, mesh, fsdp: bool = True,
                    small_out_threshold: int = 0):
    """NamedShardings for a parameter-shaped pytree (params or opt state)."""
    def one(path, leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _param_spec(
            _key_names(path), shape, mesh, fsdp=fsdp,
            small_out_threshold=small_out_threshold))
    return jax.tree_util.tree_map_with_path(one, tree)


def batch_shardings(tree: Any, mesh, global_batch: int):
    """Shard the global-batch dim over ("pod","data")."""
    dps = dp_axes(mesh)
    dsz = dp_size(mesh)
    dp = dps if len(dps) > 1 else (dps[0] if dps else None)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        spec: list = [None] * len(shape)
        if global_batch % max(dsz, 1) == 0 and dsz > 1:
            for i, s in enumerate(shape):
                if s == global_batch:
                    spec[i] = dp
                    break
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, tree)


def cache_shardings(tree: Any, mesh, global_batch: int, seq_len: int,
                    context_parallel: bool = False):
    """Decode-cache sharding.

    Baseline: batch dim -> data axes; a KV/feature dim -> "model" when
    divisible; batch=1 -> cache sequence dim -> data axes.

    ``context_parallel=True`` (§Perf H3): the cache *sequence* dim is
    sharded over "model" instead of the feature dim, so the per-token
    attention gathers only O(B*H*S) f32 score statistics instead of the
    whole O(B*S*r) latent / O(B*S*KV*hd) KV cache every step."""
    dps = dp_axes(mesh)
    dsz = dp_size(mesh)
    md = model_size(mesh)
    dp = dps if len(dps) > 1 else (dps[0] if dps else None)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        ndim = len(shape)
        spec: list = [None] * ndim
        if ndim <= 1:
            return NamedSharding(mesh, P(*spec))
        dp_dim = None
        if dsz > 1 and global_batch % dsz == 0 and global_batch > 1:
            for i in range(1, ndim):
                if shape[i] == global_batch:
                    dp_dim = i
                    spec[i] = dp
                    break
        elif dsz > 1:
            # batch too small: context-parallel the sequence dim over data
            for i in range(1, ndim):
                if shape[i] == seq_len and seq_len % dsz == 0:
                    dp_dim = i
                    spec[i] = dp
                    break
        if md > 1:
            mdim = None
            if context_parallel:
                for i in range(1, ndim):
                    if i != dp_dim and shape[i] == seq_len and \
                            seq_len % md == 0:
                        mdim = i
                        break
            if mdim is None and not context_parallel:
                best = -1
                for i in range(1, ndim):
                    if i == dp_dim or shape[i] == seq_len:
                        continue
                    if shape[i] % md == 0 and shape[i] >= md and shape[i] > best:
                        best = shape[i]
                        mdim = i
            if mdim is not None:
                spec[mdim] = "model"
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, tree)


def replicated(tree: Any, mesh):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


def describe_shardings(tree, shardings, max_rows: int = 0):
    """Human-readable (path, shape, spec) table for DESIGN/EXPERIMENTS."""
    rows = []
    flat_t = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    for (path, leaf), sh in zip(flat_t, flat_s):
        rows.append(("/".join(_key_names(path)), tuple(leaf.shape),
                     str(sh.spec)))
    if max_rows:
        rows = rows[:max_rows]
    return rows

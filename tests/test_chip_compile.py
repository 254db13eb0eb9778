"""Compile the main path's kernels and the trainer's update for a TPU v5e
that is described, not attached.

The TPU compiler refuses what interpret mode accepts: traced-index slices
inside a kernel, blocks that break the (8, 128) tiling rule, programs that
do not fit the chip's memory.  These cases compile the fused sweep kernels
(single cell and vmapped over cells), ``prox_step`` and the donated
mamba2-780m update at real widths, so such faults surface here at no chip
time.  The topology is described inside a fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.prox import make_prox
from repro.core.stepsize import StepsizeState
from repro.kernels.fused_step import (fused_policy_buff_step,
                                      fused_policy_mix_step,
                                      fused_policy_prox_step)
from repro.kernels.prox_step import prox_step
from repro.sweep.policies import PolicyParams

B, H, D = 64, 256, 800          # cells, window horizon, rcv1-like dim
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cell_args(sharding, batch, n_rows):
    """PolicyParams, tau, StepsizeState, then ``n_rows`` (D,) rows."""
    pre = () if batch is None else (batch,)
    i32 = lambda: _sds(sharding, pre, jnp.int32)
    f32 = lambda shape=(): _sds(sharding, pre + shape, jnp.float32)
    params = PolicyParams(i32(), f32(), f32(), f32())
    state = StepsizeState(i32(), f32(), f32((H,)), i32())
    return (params, i32(), state) + tuple(f32((D,)) for _ in range(n_rows))


_L1 = make_prox("l1", lam=1e-5)
KERNELS = {
    "prox": (lambda p, t, s, x, g: fused_policy_prox_step(
        p, _L1, s, t, x, g, interpret=False), 2),
    "mix": (lambda p, t, s, x, xc: fused_policy_mix_step(
        p, s, t, x, xc, interpret=False), 2),
    "buff": (lambda p, t, s, x, xc, xw, d: fused_policy_buff_step(
        p, s, t, x, xc, xw, d, jnp.float32(1.0), 0.125, interpret=False), 4),
}


@pytest.mark.parametrize("batched", [False, True], ids=["cell", "vmapped"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_fused_kernel_compiles(one_chip, kernel, batched):
    fn, n_rows = KERNELS[kernel]
    if batched:
        fn = jax.vmap(fn)
    args = _cell_args(one_chip, B if batched else None, n_rows)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_prox_step_compiles(one_chip):
    x = _sds(one_chip, (4096, 1024), jnp.float32)
    gamma = _sds(one_chip, (), jnp.float32)
    text = jax.jit(lambda x, g, c: prox_step(x, g, c, interpret=False)) \
        .lower(x, x, gamma).compile().as_text()
    assert "tpu_custom_call" in text


def test_mamba2_780m_update_fits_one_chip(one_chip):
    """The trainer's donated update at published widths: arguments plus
    outputs, less what donation aliases, fit one v5e's HBM."""
    from repro.configs import get_config
    from repro.launch.steps import make_trainer
    from repro.launch.train import make_apply
    trainer = make_trainer(get_config("mamba2-780m"), n_workers=2)
    specs = trainer.state_specs()
    place = lambda tree: jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params, opt = place(specs.params), place(specs.opt)
    tau = _sds(one_chip, (), jnp.int32)
    mem = make_apply(trainer).lower(params, params, opt, tau).compile() \
        .memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0
    assert held < V5E_HBM_BYTES, (held, mem)

"""sweep.scan_roofline: the least time the chip could take for the grids
of the window (``bench.work.piag_grid_work``) over the device time of the
sweep programs (%)."""
from bench.trace import module_seconds
from bench.work import least_seconds

# the batched PIAG program: jax.jit(jax.vmap(cell)) in repro.sweep.runners
MODULES = ("jit_cell",)


def read(run):
    device_s = module_seconds(run.trace, MODULES)
    work = run.work.get("piag")
    if device_s <= 0 or work is None or run.peak is None:
        return None
    return 100.0 * least_seconds(work, run.peak) / device_s

"""train.update_roofline: the least time of the window's optimizer updates
(22 bytes per parameter, ``bench.work.UPDATE_BYTES_PER_PARAM``, at the
chip's HBM bandwidth) over the device time of the update program (%)."""
from bench.trace import module_seconds

# the trainer's update: jax.jit(DelayAdaptiveOptimizer.step_fn)
MODULES = ("jit_step_fn",)


def read(run):
    device_s = module_seconds(run.trace, MODULES)
    work = run.work.get("train")
    if device_s <= 0 or work is None or run.peak is None:
        return None
    return 100.0 * work["update_bytes"] / run.peak["hbm_bytes_per_s"] / device_s

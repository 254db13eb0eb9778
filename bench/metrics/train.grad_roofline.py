"""train.grad_roofline: the least time of the window's gradients (the
model operations of every training token of the window,
``bench.work.mamba2_flops_per_token``, at the chip's peak bf16 rate) over
the device time of the gradient program (%)."""
from bench.trace import module_seconds

# the trainer's gradient: jax.jit(jax.grad(train_grad)) in run_training
MODULES = ("jit_train_grad",)


def read(run):
    device_s = module_seconds(run.trace, MODULES)
    work = run.work.get("train")
    if device_s <= 0 or work is None or run.peak is None:
        return None
    return (100.0 * work["flops_per_token"] * run.window["tokens"]
            / run.peak["flops_bf16"] / device_s)

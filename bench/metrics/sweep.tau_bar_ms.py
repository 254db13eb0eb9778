"""sweep.tau_bar_ms: device milliseconds per grid of the tau-bar measure
(``jit_measure_tau_bar``) that ``api.run`` makes on every resolve of the
spec, over the grids of the window."""
from bench.trace import module_seconds

# repro.sweep.grid's jitted trace-delay program
MODULES = ("jit_measure_tau_bar",)


def read(run):
    device_s = module_seconds(run.trace, MODULES)
    if device_s <= 0 or run.window.get("grids", 0) <= 0:
        return None
    return 1e3 * device_s / run.window["grids"]

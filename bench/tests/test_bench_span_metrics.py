"""The per-layer metrics that read the program's named programs
(``jit_train_grad``, ``jit_measure_tau_bar``), on hand-built summaries."""
from pathlib import Path

import pytest

import conftest

from bench import harness

ROOT = conftest.ROOT
PEAK = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def summary(**module_s):
    return {"window_s": 10.0, "busy_s": 9.0, "module_s": module_s,
            "breakdown": {"device_ops": [], "idle_gaps": []}}


def read(name, window, work, trace, peak=PEAK):
    info = harness.RunInfo(window, work, trace, peak, {})
    return harness.read_metric(ROOT, name, info)


def test_grad_roofline_over_the_gradient_program():
    window = {"tokens": 50, "seconds": 5.0, "events": 5}
    work = {"train": {"flops_per_token": 4.0, "update_bytes": 1.0}}
    trace = summary(jit_train_grad=4.0, jit_step_fn=1.0, jit_heldout_loss=2.0)
    # 4 operations x 50 tokens at 100/s: 2 s least, over 4 s of the program
    assert read("train.grad_roofline", window, work, trace) == \
        pytest.approx(50.0)


def test_tau_bar_ms_per_grid():
    window = {"grids": 4, "seconds": 2.0}
    trace = summary(jit_measure_tau_bar=0.08, jit_cell=1.5)
    assert read("sweep.tau_bar_ms", window, {}, trace) == pytest.approx(20.0)


@pytest.mark.parametrize("name,window,work", [
    ("train.grad_roofline", {"tokens": 50, "seconds": 5.0},
     {"train": {"flops_per_token": 4.0}}),
    ("sweep.tau_bar_ms", {"grids": 4, "seconds": 2.0}, {}),
])
def test_none_without_the_program(name, window, work):
    """A program that lacks the name -- the parent of the change that
    named it -- reads nothing."""
    old = summary(jit__lambda=3.0, jit_step_fn=1.0, jit_cell=1.0)
    assert read(name, window, work, old) is None
    assert read(name, window, work, None) is None


def test_each_new_metric_has_its_reader():
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    layer = {m["name"]: m for m in bench["per_layer"]}
    for name, moves in (("train.grad_roofline", "tokens_per_s"),
                        ("sweep.tau_bar_ms", "cell_events_per_s")):
        assert (Path(ROOT) / "bench" / "metrics" / f"{name}.py").exists()
        assert layer[name]["moves"] == moves

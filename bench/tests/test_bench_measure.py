"""Work counts, the peaks table and the reduction from trace to metrics,
on small shapes and a small recorded trace."""
import json
from pathlib import Path

import numpy as np
import pytest

import conftest  # noqa: F401

from bench import trace as tm
from bench import work
from bench.peaks import PEAKS, peaks

FIXTURES = Path(__file__).parent / "fixtures"


def test_peaks_known_and_unknown():
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in PEAKS.values())
    with pytest.raises(KeyError):
        peaks("cpu")


def test_piag_work_counts_distinct_shards():
    # 3 cells, 2 events; 4 workers over 8 rows of width 2
    workers = np.array([[0, 1], [0, 2], [3, 2]])
    w = work.piag_grid_work(workers, n_samples=8, dim=2, n_workers=4)
    shard = 2 * (2 * 2 + 4)    # rows * (dim bfloat16 features + a label)
    data = 8 * (2 * 2 + 4)
    state = 3 * 2 * work.PIAG_STATE_WORDS * 2 * 4
    assert w["bytes"] == (2 + 2) * shard + 2 * data + state
    assert w["flops"] == 3 * 2 * (4 * 2 * 2 + 2 * 8 * 2)


def test_least_seconds_takes_the_larger_bound():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"flops": 50.0, "bytes": 10.0}, peak) == 1.0
    assert work.least_seconds({"flops": 500.0, "bytes": 10.0}, peak) == 5.0


def _synthetic():
    # window 0..100 ns; chip ops 10-30, 30-40, 60-70; modules
    host = [(0.0, 100.0, tm.WINDOW), (45.0, 10.0, "api.run"),
            (0.0, 100.0, "outer")]
    ops = [[(10.0, 20.0, "fusion.1"), (30.0, 10.0, "fusion.2"),
            (60.0, 10.0, "fusion.1"), (150.0, 5.0, "late")]]
    mods = [[(10.0, 30.0, "jit_cell(3)"), (60.0, 10.0, "jit_cell(4)")]]
    return tm.Trace(ops, mods, host)


def test_summarize_synthetic():
    s = tm.summarize(_synthetic())
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["module_s"] == {"jit_cell": pytest.approx(40e-9)}
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(30e-9)
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["outer", pytest.approx(30e-9)]  # 70..100
    assert ["api.run", pytest.approx(20e-9)] in gaps   # 40..60
    assert sum(g for _, g in gaps) == pytest.approx(60e-9)


def test_trace_json_round_trip():
    t = _synthetic()
    assert tm.Trace.from_json(json.loads(json.dumps(t.to_json()))) == t


def test_module_name_drops_run_number():
    assert tm.module_name("jit_step_fn(12)") == "jit_step_fn"
    assert tm.module_name("jit_cell") == "jit_cell"


def test_self_times_of_nested_ops():
    ops = [(0.0, 100.0, "while.1"), (10.0, 20.0, "fusion.1"),
           (40.0, 30.0, "fusion.2"), (45.0, 5.0, "copy.1"),
           (120.0, 10.0, "fusion.1")]
    got = {}
    for n, d in tm.self_times(ops):
        got[n] = got.get(n, 0.0) + d
    assert got == {"while.1": 50.0, "fusion.1": 30.0, "fusion.2": 25.0,
                   "copy.1": 5.0}


def test_op_name():
    assert tm.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert tm.op_name("jit_cell(3)") == "jit_cell(3)"


def test_recorded_chip_trace():
    """1.6 ms of a narrow grid's window recorded on one TPU v5e: the host
    preparing the grid, then the start of the solver program."""
    t = tm.Trace.from_json(json.loads(
        (FIXTURES / "trace_v5e_narrow.json").read_text()))
    s = tm.summarize(t)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["window_s"] == pytest.approx(1.6e-3)
    assert set(s["module_s"]) == {"jit_cell"}
    # the program's span covers its operations and the gaps between them
    assert s["busy_s"] <= s["module_s"]["jit_cell"] <= s["window_s"]
    assert 0 < len(s["breakdown"]["device_ops"]) <= 10
    name, gap = s["breakdown"]["idle_gaps"][0]
    assert name != "host idle" and gap > 0.5e-3
    total_self = sum(v for _, v in s["breakdown"]["device_ops"])
    assert total_self <= s["busy_s"] * (1 + 1e-9)


def test_readers_on_a_summary():
    """The per-layer readers of BENCHMARK.json on one traced window."""
    from conftest import ROOT
    from bench import harness
    summary = {"busy_s": 9.0, "window_s": 10.0,
               "module_s": {"jit_cell": 8.0, "jit_step_fn": 0.5}}
    peak = peaks("TPU v5 lite")
    work_counts = {"piag": {"bytes": 819e9, "flops": 1.0},
                   "train": {"update_bytes": 0.25 * 819e9,
                             "flops_per_token": 1.97e9}}
    run = harness.RunInfo({"seconds": 2.0, "tokens": 2e4}, work_counts,
                          summary, peak, {})
    read = lambda name: harness.read_metric(ROOT, name, run)
    assert read("sweep.idle_share") == pytest.approx(10.0)
    assert read("train.idle_share") == pytest.approx(10.0)
    assert read("sweep.scan_roofline") == pytest.approx(100.0 / 8.0)
    assert read("train.update_roofline") == pytest.approx(50.0)
    assert read("train.mfu") == pytest.approx(10.0)
    quiet = harness.RunInfo({"seconds": 2.0, "tokens": 2e4}, work_counts,
                            None, None, {})
    assert harness.read_metric(ROOT, "sweep.scan_roofline", quiet) is None
    assert harness.read_metric(ROOT, "train.idle_share", quiet) is None

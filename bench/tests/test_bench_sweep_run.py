"""A whole run of a sweep cell on the CPU, through the harness with its
look for a chip skipped: the result line, and a cell added as files only."""
import time

from conftest import CPU, write_root

from bench import harness


def test_tiny_cell_runs_correct(tiny_root):
    cell = harness.Cell("tiny.grid", root=tiny_root)
    out = harness.run_cell(cell, 2**31 + 7, 0.5, False, time.perf_counter(),
                           device=dict(CPU))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "cell_events_per_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["metrics"]["cell_events_per_s"]["unit"] == "cell-events/s"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0


def test_traced_run_reads_new_metric_file(tmp_path, monkeypatch):
    """A per-layer metric added as a new reader file is found by name;
    device metrics find nothing to read on the CPU and are left out."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    root = write_root(tmp_path, extra_metric=True)
    cell = harness.Cell("tiny.grid", root=root)
    out = harness.run_cell(cell, 3, 0.3, True, time.perf_counter(),
                           device=dict(CPU))
    assert out["correct"] is True
    assert out["metrics"]["tiny.grids"]["value"] >= 1
    assert "sweep.scan_roofline" not in out["metrics"]
    assert {"window_s", "busy_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs(tiny_root):
    from bench.surfaces import sweep
    cell = harness.Cell("tiny.grid", root=tiny_root)
    a = sweep.make_spec(None, cell.config, cell.traffic, 5)
    b = sweep.make_spec(None, cell.config, cell.traffic, 5)
    assert a.policies.seeds == b.policies.seeds == (10, 11)

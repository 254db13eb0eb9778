"""Fixtures of the benchmark's CPU tests: a tiny cell that is not in
``BENCHMARK.json``, defined by files of its own in a temporary root."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def write_root(root: Path, *, extra_metric: bool = False) -> Path:
    """A benchmark root with one tiny sweep cell ``tiny.grid``: its own
    configuration, traffic mix and (optionally) a per-layer metric of its
    own, all new files beside a copy of the real metric readers."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "paper-mnist-l1logreg.json").read_text())
    cfg.update(name="tiny", n_samples=600, dim=16, n_workers=3)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "piag-wide.json").read_text())
    traffic.update(seeds=2, regimes=["uniform", "straggler"], events=40,
                   check_per_policy=1)
    (root / "bench" / "traffic" / "tiny-grid.json").write_text(
        json.dumps(traffic))
    bench["configs"] = [{"name": "tiny", "source": "fixture",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "a CPU-sized sweep"}]
    bench["workloads"] = [{"name": "tiny.grid", "config": "tiny",
                           "traffic": "tiny-grid", "chips": 1, "why": "x"}]
    for group in ("end_to_end", "per_layer"):
        kept = [m for m in bench[group] if "workloads" not in m
                or "sweep.mnist.piag-wide" in m["workloads"]]
        for m in kept:
            if "workloads" in m:
                m["workloads"] = ["tiny.grid"]
        bench[group] = kept
    if extra_metric:
        bench["per_layer"].append({
            "name": "tiny.grids", "unit": "grids", "better": "higher",
            "source": "host_clock", "layer": "api host path",
            "moves": "cell_events_per_s", "workloads": ["tiny.grid"]})
        (root / "bench" / "metrics" / "tiny.grids.py").write_text(
            "def read(run):\n    return run.window['grids']\n")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """The tiny cell's root; the run keeps JAX's compile cache off, so
    nothing leaks into the other tests of this process."""
    from bench import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return write_root(tmp_path)


def write_train_root(root: Path) -> Path:
    """A benchmark root with one tiny trainer cell ``tiny.train``: a
    configuration of its own (mamba2-780m's code at toy widths through
    ``overrides``) and a traffic mix of its own, as new files only."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    overrides = {"n_layers": 2, "d_model": 64, "vocab": 256, "ssm_state": 16,
                 "ssm_head_dim": 16, "ssm_chunk": 16}
    cfg = {"name": "tiny-mamba2", "surface": "train",
           "arch_id": "mamba2-780m", "overrides": overrides,
           "reduced": sorted(overrides)}
    (root / "bench" / "configs" / "tiny-mamba2.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "train-b2s2048.json").read_text())
    traffic.update(seq=64, warm_events=3, log_every=4, trace_seconds=1)
    # the toy cell's own limits, from its CPU readings over eight seeds:
    # sound runs read loss1_rel <= 2.1e-4, change_leaf_gap <= 0.023 and
    # grad_diff_median <= 0.0134; each gradient taken over half of its
    # batch reads grad_diff_median >= 0.63 (four seeds)
    traffic["limits"].update(loss1_rel=5e-4, change_leaf_gap=0.035,
                             grad_diff_median=0.1)
    (root / "bench" / "traffic" / "tiny-train.json").write_text(
        json.dumps(traffic))
    bench["configs"] = [{"name": "tiny-mamba2", "source": "fixture",
                         "file": "bench/configs/tiny-mamba2.json",
                         "reduced": cfg["reduced"], "why": "CPU-sized"}]
    bench["workloads"] = [{"name": "tiny.train", "config": "tiny-mamba2",
                           "traffic": "tiny-train", "chips": 1, "why": "x"}]
    for group in ("end_to_end", "per_layer"):
        kept = [m for m in bench[group] if "workloads" not in m
                or "train.mamba2-780m.b2s2048" in m["workloads"]]
        for m in kept:
            if "workloads" in m:
                m["workloads"] = ["tiny.train"]
        bench[group] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def train_root(tmp_path, monkeypatch):
    from bench import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return write_train_root(tmp_path)

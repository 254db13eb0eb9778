"""A whole run of a trainer cell on the CPU, through the harness with its
look for a chip skipped.  The cell's configuration is a new file only: a
model of ``repro.configs`` with ``overrides``."""
import time

from conftest import CPU

from bench import harness
from bench import work
from bench.surfaces import train


def test_tiny_trainer_runs_correct(train_root):
    cell = harness.Cell("tiny.train", root=train_root)
    out = harness.run_cell(cell, 2**31 + 9, 1.0, False, time.perf_counter(),
                           device=dict(CPU))
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["tau_mismatch"]["value"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0


def test_overrides_apply_to_the_model(train_root):
    cell = harness.Cell("tiny.train", root=train_root)
    cfg = train.model_config(cell.config)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (2, 64, 256)
    assert cfg.ssm_heads == 128 // 16


def test_mamba2_counts_at_published_widths():
    from repro.configs import get_config
    from repro.models import param_specs
    import numpy as np
    import jax
    cfg = get_config("mamba2-780m")
    n = sum(int(np.prod(s.shape)) for s in
            jax.tree_util.tree_leaves(param_specs(cfg)))
    assert work.mamba2_params(cfg) == n
    # 6 x (48 x (in_proj + out_proj) + tied head) + SSD terms
    matmul = 48 * (1536 * 6448 + 3072 * 1536) + 50280 * 1536
    ssd = 48 * 3 * (2 * 128 * 128 + 2 * 128 * 3072 + 4 * 3072 * 128)
    assert work.mamba2_flops_per_token(cfg) == 6 * matmul + ssd

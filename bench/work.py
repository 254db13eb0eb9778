"""The work each cell's algorithm needs, counted from shapes.

These counts divide device time into roofline shares and MFU.  They count
what the algorithm has to do, whatever implements it, so a change to the
program does not change them: a program that does less than this count
would read above 100%, which the driver refuses.
"""
from __future__ import annotations

import numpy as np

# float32 words of per-cell state that one PIAG write event has to move,
# each a vector of the iterate's width d: read the returning worker's
# snapshot x_read[w]; read its old table row and write the new one (the
# aggregate is kept up to date from the difference); read and write the
# aggregate; read and write the iterate; write the new snapshot x_read[w].
PIAG_STATE_WORDS = 8


def piag_grid_work(workers, *, n_samples: int, dim: int, n_workers: int,
                   itemsize: int = 4, data_itemsize: int = 2) -> dict:
    """Least bytes and operations of one PIAG grid over the logistic loss.

    ``workers`` is the (cells, events) worker index of every write event.
    Per event the grid reads each distinct worker shard (its rows of A and
    b) that any cell consumes at that event once, reads A and b once more
    for the objective that every cell records, and moves each cell's state
    (``itemsize`` bytes a word).  A is counted at ``data_itemsize`` bytes an
    element: the products run at the default precision, whose operands are
    bfloat16 on the chip, so a bfloat16 copy of A is all they need.
    Operations: two matrix-vector products over the shard per gradient and
    one over A per recorded objective, per cell.
    """
    workers = np.asarray(workers)
    cells, events = workers.shape
    rows = n_samples // n_workers
    shard_bytes = rows * (dim * data_itemsize + itemsize)
    data_bytes = n_samples * (dim * data_itemsize + itemsize)
    distinct = sum(len(np.unique(workers[:, k])) for k in range(events))
    nbytes = (distinct * shard_bytes + events * data_bytes
              + cells * events * PIAG_STATE_WORDS * dim * itemsize)
    flops = cells * events * (4 * rows * dim + 2 * n_samples * dim)
    return {"bytes": float(nbytes), "flops": float(flops)}


def least_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(work["flops"] / peak["flops_bf16"],
               work["bytes"] / peak["hbm_bytes_per_s"])


# bytes per parameter of one optimizer update: bfloat16 parameters read and
# written (4), the two float32 moments read and written (16), the bfloat16
# gradient read once (2)
UPDATE_BYTES_PER_PARAM = 22


def mamba2_params(cfg) -> int:
    """Parameters of a Mamba2 language model with tied embeddings."""
    D, din, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    G, K = cfg.ssm_n_groups, cfg.ssm_conv
    conv = din + 2 * G * N
    layer = (D * (2 * din + 2 * G * N + H) + K * conv + conv + 3 * H + din
             + din * D + D)
    return cfg.n_layers * layer + cfg.vocab * D + D


def mamba2_flops_per_token(cfg) -> float:
    """Training operations per token: 6 per weight of every matrix product
    (the in and out projections of each layer and the tied output head),
    and three times the forward operations of the chunked state-space
    scan: per token, the causal half of a chunk for C.B (over the state
    size) and for the weighted sum of inputs (over all heads), and the
    state's update and read-out."""
    D, din, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    G = cfg.ssm_n_groups
    Q = cfg.ssm_chunk
    matmul = cfg.n_layers * (D * (2 * din + 2 * G * N + H) + din * D) \
        + cfg.vocab * D
    half = Q / 2
    ssd = 2 * half * G * N + 2 * half * din + 2 * 2 * din * N
    return 6.0 * matmul + 3.0 * cfg.n_layers * ssd

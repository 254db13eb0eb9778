"""train.mfu: model operations per training token
(``bench.work.mamba2_flops_per_token``) times the tokens per second of the
window, over the chip's peak bf16 operations per second (%)."""


def read(run):
    w = run.work.get("train")
    if w is None or run.peak is None or run.window["seconds"] <= 0:
        return None
    tokens_per_s = run.window["tokens"] / run.window["seconds"]
    return 100.0 * w["flops_per_token"] * tokens_per_s / run.peak["flops_bf16"]

"""Train step (``train/step.py`` and ``models/``): forward and backward
model FLOPs per token (no recomputation) times the tokens per second of
the window, over the chip's peak.  Host clock."""


def read(run):
    if run.kind != "train" or not run.steps:
        return None
    lo, hi = run.window
    tokens = sum(s.tokens for s in run.steps)
    per_token = run.ref.train_flops_per_token(run.dims, run.seq_len)
    return 100.0 * per_token * tokens / ((hi - lo) * run.peaks["bf16_flops"])

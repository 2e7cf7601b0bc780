"""Shared set-up of the benchmark's CPU tests: the harness at reduced
sizes, driven past its look for a chip."""
from __future__ import annotations

import copy
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import device, run  # noqa: E402

#: made-up peaks: CPU runs report no device metric, these only let the
#: readers' arithmetic run
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}

TINY_QWEN = dict(hidden_size=64, intermediate_size=176,
                 num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=2, vocab_size=512)
TINY_MAMBA = dict(d_model=64, n_layer=2, vocab_size=500)
TINY_MAMBA_LAYER = dict(d_state=16, d_conv=4, expand=2, headdim=16,
                        ngroups=1, chunk_size=32, norm_epsilon=1e-5)
CONFIGS = ("qwen2.5-3b", "mamba2-2.7b")
TRAIN_CONFIGS = ("qwen2.5-3b-6l", "mamba2-2.7b-16l")

#: limits at the reduced widths, for whichever numbers a config compares:
#: sound runs there read under a third of each (seeds 31-37: a median
#: leaf's gradient up to 0.0011, a worst leaf's 0.0055, mamba2's worst
#: change 0.145 from its bfloat16 conv weights); the float8 control reads
#: 0.036-0.076 and 0.0059-0.017 on the gradients, planted faults above
TINY_LIMITS = {"serve_max_logit_gap": 0.5, "train_loss_gap": 0.05,
               "train_grad_norm_gap": 0.02, "train_median_grad_gap": 0.003,
               "train_change_norm_gap": 0.3, "train_median_change_gap": 0.05}

#: the numbers a config is to compare whose file has no limits yet
PLANNED_CHECKS = {
    "mamba2-2.7b": ["serve_max_logit_gap"],
    "mamba2-2.7b-16l": ["train_grad_norm_gap", "train_median_grad_gap",
                        "train_median_change_gap"],
}

TINY_CHAT = {
    "kind": "serve",
    "arrivals": {"process": "poisson", "rate_per_s": 8.0,
                 "order_seed": 1},
    "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.9,
                   "min": 32, "max": 128, "grid": {"count": 4, "multiple": 32}},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 2, "max": 16},
    "engine": {"slots": 4, "max_seq": 160},
    "drain_s": 30, "trace_seconds": 1.0,
    "check": {"requests": 3},
}

TINY_TRAIN = {
    "kind": "train", "batch": 2, "seq_len": 64, "check_steps": 2,
    "trace_seconds": 0.5,
    "optimizer": {"peak_lr": 1e-3, "warmup_steps": 1, "total_steps": 10000,
                  "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                  "clip_norm": 1.0},
}


def tiny_conf(name: str = "qwen2.5-3b") -> dict:
    """The config ``name`` at reduced widths, comparing the numbers its
    file compares, at the limits of ``TINY_LIMITS``."""
    conf = copy.deepcopy(run.load_config(name))
    if conf["bench"]["family"] == "mamba2":
        conf.update(TINY_MAMBA)
        conf["bench"]["assumed_layer"] = dict(TINY_MAMBA_LAYER)
    else:
        conf.update(TINY_QWEN)
    names = conf["bench"].get("checks") or PLANNED_CHECKS[name]
    conf["bench"]["checks"] = {k: TINY_LIMITS[k] for k in names}
    return conf


_METER = None


def meter():
    global _METER
    if _METER is None:
        _METER = device.CompileMeter()
    return _METER


def context(conf: dict, mix: dict, *, seed: int = 5, seconds: float = 2.0,
            trace: bool = False, metrics=None) -> run.Context:
    ref, prog = run.family(conf)
    if metrics is None:
        names = (["setup_s", "ttft_p95_ms", "itl_p95_ms"]
                 if mix["kind"] == "serve"
                 else ["setup_s", "train_tokens_per_s"])
        metrics = [{"name": n, "unit": "x"} for n in names]
    return run.Context({"name": "tiny"}, conf, mix, ref, prog, seed,
                       seconds, trace, metrics, FAKE_PEAKS, meter(),
                       lambda: 0, time.perf_counter())

"""The one traffic generator.  A mix is a JSON file of parameters in
``bench/traffic/<mix>.json``; ``kind`` says which half of this module reads
it.

Serving (``"kind": "serve"``): an open loop of independent requests.
The prompt lengths, output lengths and gaps between arrivals are drawn at
evenly spaced quantiles of the mix's distributions and put in an order
drawn from the mix's ``arrivals.order_seed``: every run of a mix offers
the same schedule, and ``--seed`` draws its prompt tokens.  (With an order
drawn from ``--seed`` the 95th percentile of time to first token moved by
half from seed to seed, far more than between two runs of one seed.)
Every prompt length is snapped to a fixed grid (``prompt_len.grid``) so
that the engine's per-length prefill programs are all compiled before the
window.

Training (``"kind": "train"``): batches that are a pure function of
(seed, step), a copy of the repo's ``SyntheticLM`` stream.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *salt]))


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def length_grid(spec: dict) -> np.ndarray:
    """``count`` distinct multiples of ``multiple`` spaced evenly in log
    between ``min`` and ``max``."""
    g = spec["grid"]
    m, lo, hi = g["multiple"], spec["min"], spec["max"]
    out = []
    for v in np.geomspace(lo, hi, g["count"]):
        snapped = int(round(v / m)) * m
        out.append(max(snapped, out[-1] + m) if out else snapped)
    return np.asarray(out, np.int64)


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles (i + 0.5) / n of a lognormal with
    the given median and sigma, clipped to [min, max]."""
    from statistics import NormalDist
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def snap(lengths: np.ndarray, grid: np.ndarray) -> np.ndarray:
    """Each length to the nearest grid value in log space."""
    d = np.abs(np.log(lengths[:, None]) - np.log(grid[None, :]))
    return grid[np.argmin(d, axis=1)]


@dataclasses.dataclass
class ServeRequest:
    rid: int
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # (prompt_len,) int32
    max_new: int


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab: int) -> list[ServeRequest]:
    """The requests due in a window of ``seconds``, in order of arrival;
    ``seed`` draws the prompt tokens only."""
    if (mix["arrivals"]["process"], mix["prompt_len"]["dist"],
            mix["output_len"]["dist"]) != ("poisson", "lognormal", "lognormal"):
        raise ValueError("the generator draws Poisson arrivals and lognormal "
                         "lengths only")
    rate = mix["arrivals"]["rate_per_s"]
    n = max(1, int(math.floor(rate * seconds)))
    # exponential gaps at mid-quantiles, mean 1/rate; their sum is n/rate
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= (n / rate) / gaps.sum()
    plen = snap(_lognormal_quantiles(mix["prompt_len"], n),
                length_grid(mix["prompt_len"]))
    olen = _lognormal_quantiles(mix["output_len"], n)
    rng = rng_for(mix["arrivals"]["order_seed"], 1)
    gaps = gaps[rng.permutation(n)]
    plen = plen[rng.permutation(n)]
    olen = olen[rng.permutation(n)]
    # the first request is due as the window opens, the last one gap
    # before n / rate <= seconds
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tok_rng = rng_for(seed, 2)
    return [ServeRequest(i, float(due[i]),
                         tok_rng.integers(0, vocab, int(plen[i]),
                                          dtype=np.int32),
                         int(olen[i]))
            for i in range(n)]


def warmup_requests(mix: dict, slots: int, vocab: int,
                    seed: int) -> list[ServeRequest]:
    """Prompts of every grid length, enough of them to visit every slot,
    two new tokens each: every program the window will run."""
    grid = length_grid(mix["prompt_len"])
    n = max(slots, len(grid))
    rng = rng_for(seed, 3)
    return [ServeRequest(-1 - i, 0.0,
                         rng.integers(0, vocab, int(grid[i % len(grid)]),
                                      dtype=np.int32), 2)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Training: a copy of repro.data.pipeline.SyntheticLM for token inputs
# ---------------------------------------------------------------------------
def train_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> dict:
    """Zipf-ish tokens with a copy pattern (token[t] repeats token[t-1]
    with p=0.5); labels are the tokens shifted by one."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    ranks = rng.zipf(1.3, size=(batch, seq_len + 1))
    tokens = np.minimum(ranks, vocab - 1).astype(np.int32)
    copy_mask = rng.random((batch, seq_len + 1)) < 0.5
    for t in range(1, seq_len + 1):
        tokens[:, t] = np.where(copy_mask[:, t], tokens[:, t - 1],
                                tokens[:, t])
    return {"tokens": tokens[:, :-1].copy(), "labels": tokens[:, 1:].copy()}

"""Roofline assembly: dry-run JSON records -> per-cell three-term table.

Terms (TPU v5e, per spec):
  compute    = HLO_FLOPs_per_chip / 197 TFLOP/s (bf16)
  memory     = HLO_bytes_per_chip / 819 GB/s HBM
  collective = collective_bytes_per_chip / 50 GB/s per ICI link
               (single-link: conservative; a 2D-torus ring phase can use 2)

cost_analysis on the SPMD-partitioned module reports per-chip numbers, and
counts every while-loop body once.  The dry-run therefore recorded three
lowerings per single-pod cell (see launch/dryrun.py): `exact1` (inner scans
unrolled), `exact2` (each layer stack executed twice).  The corrected
per-chip cost is

    corrected = exact1 + (body_repeats - 1) * (exact2 - exact1) / n_stacks

MODEL_FLOPS uses 6*N*T (train, N=active params, T=tokens/step), 2*N*T
(prefill), 2*N*B (decode: one token per sequence).
"""
from __future__ import annotations

import glob
import json
import pathlib

from repro import configs
from repro.models import SHAPES

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_LINK_BW = 50e9

_KEYS = ("flops", "bytes_accessed", "collective_bytes")


def corrected_costs(rec: dict) -> dict:
    """Scan-corrected per-chip costs for a single-pod record.

    The microbatch (grad-accumulation / chunked-admission) scan is itself a
    while loop counted once, so the layer-corrected total scales by the
    cell's microbatch factor."""
    from repro.launch.dryrun import PREFILL_MICROBATCHES, TRAIN_MICROBATCHES
    if "exact1" not in rec:
        return dict(rec["prod"])
    e1, e2 = rec["exact1"], rec["exact2"]
    r = rec["body_repeats"]
    ns = rec["n_stacks"]
    mb = 1
    if rec["shape"].startswith("train"):
        mb = TRAIN_MICROBATCHES.get(rec["arch"], 1)
    elif rec["shape"].startswith("prefill"):
        mb = PREFILL_MICROBATCHES.get(rec["arch"], 1)
    out = {}
    for k in _KEYS:
        body = max(e2[k] - e1[k], 0.0)
        out[k] = (e1[k] + (r - 1) * body / ns) * mb
    return out


def model_flops(arch_id: str, shape_name: str) -> float:
    cfg = configs.get_config(arch_id)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch          # decode: 1 token/sequence


def cell_report(rec: dict) -> dict:
    cost = corrected_costs(rec)
    n_dev = rec["n_devices"]
    compute_s = cost["flops"] / PEAK_FLOPS
    memory_s = cost["bytes_accessed"] / HBM_BW
    coll_s = cost["collective_bytes"] / ICI_LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / (cost["flops"] * n_dev) if cost["flops"] else 0.0
    bound_s = max(terms.values())
    mem = rec["prod"].get("memory", {})
    hbm_gib = (mem.get("argument_size_in_bytes", 0)
               + mem.get("temp_size_in_bytes", 0)) / 2**30
    suggestions = {
        "compute": "cut non-model FLOPs: lighter remat policy, fused "
                   "attention kernel, loss-chunk fusion",
        "memory": "raise arithmetic intensity: larger fused blocks, "
                  "bf16/int8 residuals, fewer re-streamed operands",
        "collective": "re-shard to shrink gathered operands / overlap "
                      "collectives with compute (collective matmul)",
    }
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "n_devices": n_dev,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "step_lower_bound_s": bound_s,
        "roofline_fraction": compute_s / bound_s if bound_s else 0.0,
        "model_flops": mf,
        "useful_flops_ratio": useful,
        "hbm_gib_per_device": hbm_gib,
        "fits_hbm": hbm_gib <= 16.0,
        "what_would_help": suggestions[dominant],
    }


def load_records(result_dir) -> list:
    recs = []
    for f in sorted(glob.glob(str(pathlib.Path(result_dir) / "*.json"))):
        recs.append(json.loads(pathlib.Path(f).read_text()))
    return recs


def assemble(result_dir, mesh: str = "single") -> list:
    rows = []
    for rec in load_records(result_dir):
        if rec["mesh"] != mesh or rec["status"] != "ok":
            continue
        rows.append(cell_report(rec))
    return rows


def markdown_table(rows: list) -> str:
    hdr = ("| arch | shape | compute (s) | memory (s) | collective (s) | "
           "dominant | 6N·T/HLO | HBM GiB/dev | fits |\n"
           "|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} | "
            f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
            f"**{r['dominant']}** | {r['useful_flops_ratio']:.2f} | "
            f"{r['hbm_gib_per_device']:.1f} | "
            f"{'yes' if r['fits_hbm'] else 'NO'} |")
    return "\n".join(lines)

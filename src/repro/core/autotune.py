"""Beyond-paper: the Kernel Scientist's black-box loop applied to the
FRAMEWORK itself.

The paper optimizes one kernel against an opaque timing platform.  The same
structure transfers one level up: a *framework genome* (attention tile
sizes, loss chunking, gradient-accumulation factor) is evaluated by
lowering the full distributed step and reading the roofline bound from the
compiled artifact — compile-and-analyse as the black-box 'timing' signal.
The loop is the paper's: propose experiments from the current best, submit
sequentially, keep lineage + refutation logs.

    PYTHONPATH=src python -m repro.core.autotune --arch qwen1.5-110b \\
        --shape train_4k --budget 8
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional

import jax

from repro.roofline.report import HBM_BW, ICI_LINK_BW, PEAK_FLOPS


@dataclasses.dataclass(frozen=True)
class FrameworkGenome:
    attn_q_chunk: int = 512
    attn_k_chunk: int = 1024
    loss_chunk: int = 8192
    microbatches: int = 1

    def neighbours(self):
        out = []
        for field, opts in (
            ("attn_q_chunk", (256, 512, 1024, 2048)),
            ("attn_k_chunk", (256, 512, 1024, 2048)),
            ("loss_chunk", (4096, 8192, 16384, 32768)),
            ("microbatches", (1, 2, 4, 8, 16)),
        ):
            cur = getattr(self, field)
            idx = opts.index(cur) if cur in opts else 1
            for j in (idx - 1, idx + 1):
                if 0 <= j < len(opts) and opts[j] != cur:
                    out.append((f"{field}: {cur} -> {opts[j]}",
                                dataclasses.replace(self,
                                                    **{field: opts[j]})))
        return out


class CellEvaluationService:
    """Sequential black-box evaluation: lower+compile one framework genome
    for an (arch x shape) cell; the score is the dominant roofline term."""

    def __init__(self, arch_id: str, shape_name: str, mesh=None):
        from repro import configs
        from repro.launch.mesh import make_production_mesh
        from repro.models import SHAPES
        self.cfg0 = configs.get_config(arch_id)
        self.shape = SHAPES[shape_name]
        self.mesh = mesh if mesh is not None else make_production_mesh()
        self.submissions = 0

    def submit(self, genome: FrameworkGenome) -> dict:
        from repro.dist import partition
        from repro.launch import dryrun
        from repro.roofline.collectives import collective_bytes_from_hlo
        self.submissions += 1
        cfg = dataclasses.replace(
            self.cfg0, attn_q_chunk=genome.attn_q_chunk,
            attn_k_chunk=genome.attn_k_chunk, loss_chunk=genome.loss_chunk)
        dryrun.TRAIN_MICROBATCHES = dict(dryrun.TRAIN_MICROBATCHES,
                                         **{cfg.name: genome.microbatches})
        partition.set_mesh(self.mesh)
        try:
            with self.mesh:
                fn, args, sh, osh, dn = dryrun.build_cell(cfg, self.shape,
                                                          self.mesh)
                compiled = jax.jit(
                    fn, in_shardings=sh, out_shardings=osh,
                    donate_argnums=dn).lower(*args).compile()
                cost = compiled.cost_analysis()
                mem = compiled.memory_analysis()
                coll = collective_bytes_from_hlo(compiled.as_text())
        except Exception as e:
            return {"status": "compile_error", "error": str(e)[:400]}
        finally:
            partition.set_mesh(None)
        terms = {
            "compute": cost.get("flops", 0.0) / PEAK_FLOPS,
            "memory": cost.get("bytes accessed", 0.0) / HBM_BW,
            "collective": coll / ICI_LINK_BW,
        }
        hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2**30
        return {"status": "ok", "terms": terms,
                "bound_s": max(terms.values()),
                "dominant": max(terms, key=terms.get),
                "hbm_gib": hbm, "fits": hbm <= 16.0}


def autotune_cell(arch_id: str, shape_name: str, budget: int = 8,
                  mesh=None, start: Optional[FrameworkGenome] = None,
                  verbose: bool = True) -> dict:
    """Greedy neighbourhood hillclimb with a hypothesis->measure log.
    Over-budget genomes are rejected regardless of speed (fit is a hard
    constraint, exactly like the platform's VMEM compile errors)."""
    svc = CellEvaluationService(arch_id, shape_name, mesh)
    cur = start or FrameworkGenome()
    cur_res = svc.submit(cur)
    log = [{"genome": dataclasses.asdict(cur), "result": cur_res,
            "note": "baseline"}]
    if verbose:
        print(f"baseline: {cur_res}")
    tried = {cur}
    while svc.submissions < budget:
        candidates = [c for c in cur.neighbours() if c[1] not in tried]
        if not candidates:
            break
        progressed = False
        for note, cand in candidates:
            if svc.submissions >= budget:
                break
            tried.add(cand)
            res = svc.submit(cand)
            ok = (res["status"] == "ok" and res["fits"]
                  and res["bound_s"] < cur_res.get("bound_s", 1e30))
            log.append({"genome": dataclasses.asdict(cand), "result": res,
                        "note": note,
                        "verdict": "accepted" if ok else "rejected"})
            if verbose:
                b = res.get("bound_s")
                print(f"{note}: bound={b if b is None else round(b, 4)} "
                      f"fits={res.get('fits')} -> "
                      f"{'ACCEPT' if ok else 'reject'}")
            if ok:
                cur, cur_res = cand, res
                progressed = True
                break
        if not progressed:
            break
    return {"best_genome": dataclasses.asdict(cur), "best": cur_res,
            "log": log, "submissions": svc.submissions}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    result = autotune_cell(args.arch, args.shape, args.budget)
    if args.out:
        import pathlib
        pathlib.Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result["best"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, on 512 host (CPU) devices, as a compile proof.

For each cell this prints/records:
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — the compiler's FLOPs / bytes (a while-loop
    body is counted once)
  * collective bytes parsed from the optimized HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute operand sizes)

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import os
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.dist import partition
from repro.launch.mesh import make_production_mesh
from repro.models import SHAPES, api
from repro.models.config import ArchConfig, ShapeConfig
from repro.optim import adamw
from repro.roofline.collectives import collective_bytes_from_hlo
from repro.train import step as train_step_mod

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Abstract model inputs for one cell."""
    b, s = shape.global_batch, shape.seq_len
    batch = {}
    if shape.kind == "decode":
        batch["tokens"] = SDS((b,), jnp.int32)
        return batch
    if cfg.inputs == "embeddings":
        batch["embeds"] = SDS((b, s, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = SDS((b, s), jnp.int32)
    if shape.kind == "train":
        batch["labels"] = SDS((b, s), jnp.int32)
    if cfg.mrope:
        batch["positions"] = SDS((3, b, s), jnp.int32)
    return batch


def _abstract(tree):
    return jax.tree.map(lambda x: SDS(x.shape, x.dtype), tree)


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


# Gradient-accumulation / chunked-admission factors per cell (§Perf
# iterations: activation + MoE-dispatch transients scale with tokens/pass;
# these bring every train/prefill cell under the 16 GiB v5e budget).
TRAIN_MICROBATCHES = {
    "qwen1.5-110b": 4, "command-r-plus-104b": 4, "qwen2-vl-72b": 4,
    "deepseek-v2-236b": 8, "qwen3-moe-235b-a22b": 8, "mamba2-2.7b": 2,
}
PREFILL_MICROBATCHES = {
    "deepseek-v2-236b": 4, "qwen3-moe-235b-a22b": 4,
    "command-r-plus-104b": 2, "qwen1.5-110b": 2, "qwen2-vl-72b": 2,
}


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Returns (fn, abstract_args tuple, in_shardings tuple, donate)."""
    params = api.abstract_params(cfg)
    batch = input_specs(cfg, shape)
    bspecs = partition.batch_specs(batch, mesh)

    if shape.kind == "train":
        pspecs = partition.param_specs(params, mesh, mode="train")
        opt = jax.eval_shape(adamw.init, params)
        ospecs = {"m": pspecs, "v": pspecs, "count": P()}
        fn = train_step_mod.make_train_step(
            cfg, microbatches=TRAIN_MICROBATCHES.get(cfg.name, 1))
        args = (params, opt, batch, SDS((), jnp.int32))
        shardings = (pspecs, ospecs, bspecs, P())
        # donated buffers only alias when output shardings match exactly
        metrics = jax.eval_shape(fn, params, opt, batch, SDS((), jnp.int32))[2]
        out_shardings = (pspecs, ospecs,
                         jax.tree.map(lambda _: P(), metrics))
        donate = (0, 1)
    elif shape.kind == "prefill":
        pspecs = partition.param_specs(params, mesh, mode="train")
        fn = train_step_mod.make_prefill_step(
            cfg, shape.seq_len,
            microbatches=PREFILL_MICROBATCHES.get(cfg.name, 1))
        args = (params, batch)
        shardings = (pspecs, bspecs)
        logits_s, cache_s = jax.eval_shape(fn, params, batch)
        out_shardings = (P(), partition.cache_specs(cache_s, mesh)
                         if cache_s is not None else P())
        donate = ()
    else:
        # decode: weight-stationary wide TP for dense archs (no per-token
        # FSDP gathers).  MoE archs keep EP+FSDP — wide TP would leave each
        # device with 1/|model| of ALL experts fully materialised (observed
        # 86-90 GiB/dev on deepseek/qwen3 decode).
        mode = "train" if cfg.moe is not None else "serve"
        pspecs = partition.param_specs(params, mesh, mode=mode)
        cache = jax.eval_shape(
            lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = partition.cache_specs(cache, mesh)
        fn = train_step_mod.make_decode_step(cfg)
        args = (params, cache, batch["tokens"])
        shardings = (pspecs, cspecs, partition.batch_specs(
            {"tokens": batch["tokens"]}, mesh)["tokens"])
        out_shardings = (P(), cspecs)
        donate = (1,)
    return fn, args, _named(shardings, mesh), _named(out_shardings, mesh), \
        donate


# ---------------------------------------------------------------------------
def _lower_costs(cfg: ArchConfig, shape: ShapeConfig, mesh) -> dict:
    fn, args, shardings, out_shardings, donate = build_cell(cfg, shape, mesh)
    jfn = jax.jit(fn, in_shardings=shardings, out_shardings=out_shardings,
                  donate_argnums=donate)
    compiled = jfn.lower(*args).compile()
    cost = compiled.cost_analysis()
    return {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": collective_bytes_from_hlo(compiled.as_text()),
        "memory": _mem_dict(compiled.memory_analysis()),
    }


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    """One dry-run cell: the production config lowered and compiled, with
    its memory analysis, the compiler's costs and its collective bytes."""
    cfg = configs.get_config(arch_id)
    shape = SHAPES[shape_name]
    ok, why = configs.applicable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    partition.set_mesh(mesh)
    t0 = time.time()
    try:
        with mesh:
            result = {
                "arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "ok", "n_devices": mesh.size,
                "prod": _lower_costs(cfg, shape, mesh),
            }
            result["compile_s"] = round(time.time() - t0, 1)
    except Exception as e:
        result = {
            "arch": arch_id, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "status": "error", "error": f"{type(e).__name__}: {e}"[:2000],
            "compile_s": round(time.time() - t0, 1),
        }
    finally:
        partition.set_mesh(None)
    if verbose:
        _print_cell(result)
    return result


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _print_cell(r: dict) -> None:
    tag = f"{r['arch']} x {r['shape']} [{r['mesh']}]"
    if r["status"] == "skipped":
        print(f"SKIP  {tag}: {r['reason']}")
    elif r["status"] == "error":
        print(f"FAIL  {tag}: {r['error'][:300]}")
    else:
        p = r["prod"]
        mem = p.get("memory", {})
        per_dev = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0))
        print(f"OK    {tag}: {r['compile_s']}s compile, "
              f"flops={p['flops']:.3e}, bytes={p['bytes_accessed']:.3e}, "
              f"collective={p['collective_bytes']:.3e}, "
              f"mem/device={per_dev/2**30:.2f} GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None,
                    help="directory for per-cell JSON records")
    args = ap.parse_args(argv)
    if not args.all and not (args.arch and args.shape):
        ap.error("give --arch and --shape, or --all")
    # read when the backend starts, which nothing before this line does
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    cells = []
    if args.all:
        for aid in configs.ARCH_IDS:
            for sname in SHAPES:
                cells.append((aid, sname))
    else:
        cells.append((args.arch, args.shape))
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    outdir = pathlib.Path(args.out) if args.out else None
    if outdir:
        outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for aid, sname in cells:
        for mp in meshes:
            key = f"{aid}__{sname}__{'multi' if mp else 'single'}"
            if outdir and (outdir / f"{key}.json").exists():
                print(f"CACHED {key}")
                continue
            r = run_cell(aid, sname, mp)
            if r["status"] == "error":
                failures += 1
            if outdir:
                (outdir / f"{key}.json").write_text(json.dumps(r, indent=1))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

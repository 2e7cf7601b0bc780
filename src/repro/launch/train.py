"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \\
        --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Production posture in miniature: deterministic-by-step data (restart-safe
without data-service state), periodic + preemption-triggered atomic
checkpoints, automatic resume from the latest committed step, SIGTERM ->
barrier -> checkpoint -> exit 143 (the k8s/Borg preemption contract), and
per-step heartbeat lines a fleet supervisor can parse (see
launch/elastic.py for the re-mesh side).
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from repro import configs
from repro.checkpoint import Checkpointer
from repro.data import DataConfig, SyntheticLM
from repro.dist import partition
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (
    make_debug_mesh, make_mesh, make_production_mesh, parse_mesh_shape,
)
from repro.models import api
from repro.optim import adamw
from repro.train import make_train_step

EXIT_PREEMPTED = 143


class TrainResult(NamedTuple):
    exit_code: int
    losses: list      # per step run in this process
    params: Any       # the final (sharded) parameters


def run(argv=None) -> TrainResult:
    """Train as ``argv`` describes; ``main`` is the CLI around it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) config of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    type=parse_mesh_shape,
                    help="train on a (data, model) mesh over the first D*M "
                         "devices (default: a debug mesh over all devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (scaling the reduced config)")
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)

    if args.mesh:
        mesh = make_mesh(args.mesh, ("data", "model"))
    elif args.production_mesh:
        mesh = make_production_mesh()
    else:
        mesh = make_debug_mesh()
    partition.set_mesh(mesh)
    dev = mesh.devices.flat[0]
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name}  "
          f"params~{cfg.param_count()/1e6:.1f}M  "
          f"device: {dev.platform} {dev.device_kind} x{mesh.devices.size}")

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, inputs=cfg.inputs, d_model=cfg.d_model,
        mrope=cfg.mrope))

    params = api.init_params(cfg, jax.random.key(args.seed))
    opt_state = adamw.init(params)
    pspecs = partition.param_specs(params, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    named = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, named(pspecs))
    opt_state = jax.device_put(opt_state, named(
        {"m": pspecs, "v": pspecs, "count": P()}))

    step0 = 0
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and ckpt.latest_step() is not None:
        step0 = ckpt.latest_step()
        state = ckpt.restore(
            step0, {"params": params, "opt": opt_state},
            {"params": named(pspecs),
             "opt": named({"m": pspecs, "v": pspecs, "count": P()})})
        params, opt_state = state["params"], state["opt"]
        print(f"resumed from step {step0}")

    train_step = make_train_step(cfg, peak_lr=args.peak_lr,
                                 total_steps=args.steps)
    bspecs = named(partition.batch_specs(data.at_step(0), mesh))
    jstep = jax.jit(train_step, donate_argnums=(0, 1))

    preempted = {"flag": False}

    def _sigterm(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, _sigterm)

    losses = []
    t_start = time.time()
    for step in range(step0, args.steps):
        batch = jax.tree.map(jax.device_put, data.at_step(step), bspecs)
        params, opt_state, metrics = jstep(
            params, opt_state, batch, np.int32(step))
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == args.steps - 1:
            dt = time.time() - t_start
            print(f"HEARTBEAT step={step} loss={loss:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"elapsed={dt:.1f}s", flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, {"params": params, "opt": opt_state},
                            extra={"loss": loss})
        if preempted["flag"]:
            print(f"SIGTERM at step {step}: checkpoint + exit "
                  f"{EXIT_PREEMPTED}", flush=True)
            if ckpt:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          extra={"loss": loss, "preempted": True})
            partition.set_mesh(None)
            return TrainResult(EXIT_PREEMPTED, losses, params)

    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state},
                  extra={"loss": losses[-1]})
        ckpt.wait()
    partition.set_mesh(None)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return TrainResult(0, losses, params)


def main(argv=None) -> int:
    return run(argv).exit_code


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Bring-up check on the chip: serving, the kernel scientist and training,
each driven through its normal entry point.

    python3 chip_smoke.py              # one TPU: serve, scientist, train
    python3 chip_smoke.py --chips 4    # four TPUs: sharded train step and
                                       # sharded serve prefill vs one chip
    python3 chip_smoke.py --rehearse   # the same phases, reduced, on the CPU

Everything runs in this one process, because a chip belongs to one process.
Without ``--rehearse`` the script exits non-zero before any phase unless JAX
finds a TPU.  Any failed phase or check exits non-zero, and then no result
line is printed.  Each phase prints one ``[phase]`` line naming the device;
the last line of stdout is ``{"ok": true, "device": {...}}``.

Weights are random (from ``--seed``), so checks compare paths of the system
against each other and against plain references, never against published
quality numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
ARCH = "qwen2.5-3b"
# Tolerances (relative L2 of logits; absolute loss) for bf16 paths that
# differ only in reduction order: cached decode vs full forward, and
# sharded vs single-device programs.
DECODE_LOGITS_RTOL = 5e-2
NEAR_TOP_FRACTION = 5e-2
MESH_LOGITS_RTOL = 5e-2
MESH_LOSS_ATOL = 2e-2
# What a phase may leave on the device once its objects are dropped.
MAX_LIVE_BYTES_BETWEEN_PHASES = 256 << 20


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Compile accounting (set-up time), from JAX's own monitoring events
# ---------------------------------------------------------------------------
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Seconds in XLA's backend compile (a persistent-cache hit skips it)
    and the number of persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += secs

    def _count(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.cache_hits


def device_tag():
    import jax
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{jax.device_count()}"


def free_phase(name: str) -> int:
    """Drop what a phase left behind and check the device is empty again."""
    import jax
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    check(live <= MAX_LIVE_BYTES_BETWEEN_PHASES,
          f"{name}: {live} bytes still live on the device after the phase")
    return live


def rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------
def phase_serve(opts, meter) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import api

    slots, requests, max_new = 8, 8, 16
    max_seq = 256 if opts.rehearse else 2048
    argv = ["--arch", ARCH, "--slots", str(slots), "--max-seq", str(max_seq),
            "--requests", str(requests), "--max-new", str(max_new),
            "--seed", str(opts.seed)] + (["--reduced"] if opts.rehearse else [])
    c0, h0 = meter.snapshot()
    t0 = time.time()
    engine, finished = serve.run(argv)
    wall = time.time() - t0
    compile_s = meter.seconds - c0
    cfg = engine.cfg
    tokens = sum(len(r.generated) for r in finished)
    check(len(finished) == requests,
          f"serve: {len(finished)} of {requests} requests finished")
    for r in finished:
        check(len(r.generated) == max_new,
              f"serve: req{r.rid} has {len(r.generated)} tokens")
        check(all(0 <= t < cfg.vocab for t in r.generated),
              f"serve: req{r.rid} has a token outside the vocab")

    # Reference on one request: decoding through the cache must agree with
    # the full forward over the same tokens, and the engine's second token
    # must be at the top of the full forward's logits.
    req = min(finished, key=lambda r: r.rid)
    prompt = [int(t) for t in req.prompt]
    params = engine.params
    prefill = jax.jit(api.prefill, static_argnums=(1, 3))
    full = {"tokens": jnp.asarray([prompt + req.generated[:1]], jnp.int32)}
    logits_full, _ = prefill(params, cfg, full, len(prompt) + 1)
    pre = {"tokens": jnp.asarray([prompt], jnp.int32)}
    _, cache = prefill(params, cfg, pre, len(prompt) + 1)
    logits_dec, _ = jax.jit(api.decode_step, static_argnums=1)(
        params, cfg, cache, jnp.asarray(req.generated[:1], jnp.int32))
    ref = np.asarray(logits_full[0], np.float32)
    check(np.all(np.isfinite(ref)), "serve: non-finite full-forward logits")
    err = rel_l2(logits_dec[0], ref)
    check(err <= DECODE_LOGITS_RTOL,
          f"serve: cached decode vs full forward logits rel L2 {err:.4g} "
          f"> {DECODE_LOGITS_RTOL}")
    g1 = req.generated[1]
    gap = float(ref.max() - ref[g1]) / float(ref.max() - ref.min())
    check(gap <= NEAR_TOP_FRACTION,
          f"serve: engine token {g1} sits {gap:.3g} of the logit range "
          f"below the full forward's top")

    del engine, finished, params, cache, logits_full, logits_dec
    live = free_phase("serve")
    return (f"[serve] ok on {device_tag()}: {cfg.name} d={cfg.d_model} "
            f"L={cfg.n_layers} slots={slots} max_seq={max_seq}, {requests} "
            f"requests x {max_new} tokens ({tokens} tokens), req{req.rid} "
            f"tokens {req.generated}; decode-vs-forward logits rel L2 "
            f"{err:.3g}, engine token gap {gap:.3g}; set-up: compile "
            f"{compile_s:.1f} s ({meter.cache_hits - h0} persistent-cache "
            f"hits), wall {wall:.1f} s incl. compile; live after {live} B")


def phase_scientist(opts, meter) -> str:
    from repro.core.evalpool import EvalPool
    from repro.core.evaluator import EvaluationService
    from repro.core.genome import SEED_LIBRARY, SEED_MXU
    from repro.core.llm import ScriptedLLM
    from repro.core.population import BENCH_CONFIGS_6
    from repro.core.scientist import KernelScientist
    from repro.kernels import default_interpret

    interpret = default_interpret()
    check(interpret == opts.rehearse,
          f"scientist: kernels would run with interpret={interpret}")
    configs = ((256, 256, 256), (512, 256, 384)) if opts.rehearse \
        else BENCH_CONFIGS_6
    c0, h0 = meter.snapshot()
    pool = EvalPool.of(EvaluationService(backend="wall_clock",
                                         bench_configs=configs),
                       workers=1, transport="inprocess")
    try:
        sci = KernelScientist(llm=ScriptedLLM(seed=opts.seed), backend=pool)
        sci.seed()
        sci.run(1)
        records = list(sci.population)
    finally:
        pool.close()
    compile_s = meter.seconds - c0

    lines = []
    for r in records:
        g = r.genome
        desc = g.describe() if g else "(no genome)"
        us = " ".join(f"{k}={v:.1f}" for k, v in sorted(r.timings_us.items()))
        lines.append(f"  gen{r.generation} {r.rid} {r.status:<13} {desc}"
                     + (f" | us/config on {device_tag()}: {us}" if us
                        else f" | {r.error[:160]}"))
    print("\n".join(lines))

    def ok(pred):
        return [r for r in records if r.status == "ok" and pred(r)]

    check(ok(lambda r: r.genome == SEED_LIBRARY), "scientist: SEED_LIBRARY not ok")
    check(ok(lambda r: r.genome == SEED_MXU), "scientist: SEED_MXU not ok")
    designed = ok(lambda r: r.generation >= 1 and r.genome is not None
                  and r.genome.style == "blocked")
    check(designed, "scientist: no designed blocked genome came back ok")
    for r in ok(lambda r: True):
        check(len(r.timings_us) == len(configs),
              f"scientist: {r.rid} timed {len(r.timings_us)} configs")
    del sci, pool, records
    live = free_phase("scientist")
    n_ok = len([ln for ln in lines if " ok " in ln])
    return (f"[scientist] ok on {device_tag()}: wall_clock backend, "
            f"interpret={interpret}, {len(lines)} candidates over "
            f"{len(configs)} configs, {n_ok} ok (incl. SEED_LIBRARY, "
            f"SEED_MXU and {len(designed)} designed blocked); set-up: "
            f"compile {compile_s:.1f} s ({meter.cache_hits - h0} "
            f"persistent-cache hits); live after {live} B")


def _train_seq(opts) -> int:
    return 64 if opts.rehearse else 2048


def _train_argv(opts, extra=()):
    return (["--arch", ARCH, "--n-layers", "2", "--batch", "4",
             "--seq", str(_train_seq(opts)),
             "--steps", "3", "--seed", str(opts.seed)]
            + (["--reduced"] if opts.rehearse else []) + list(extra))


def phase_train(opts, meter) -> str:
    import math
    from repro.launch import train

    c0, h0 = meter.snapshot()
    res = train.run(_train_argv(opts))
    compile_s = meter.seconds - c0
    check(res.exit_code == 0, f"train: exit code {res.exit_code}")
    check(len(res.losses) == 3 and all(math.isfinite(x) for x in res.losses),
          f"train: losses {res.losses}")
    losses = res.losses
    del res
    live = free_phase("train")
    widths = "reduced" if opts.rehearse else "published"
    return (f"[train] ok on {device_tag()}: {ARCH} at {widths} widths, 2 "
            f"layers, batch 4 x seq {_train_seq(opts)}, losses "
            f"{[round(x, 5) for x in losses]}; set-up: compile "
            f"{compile_s:.1f} s ({meter.cache_hits - h0} persistent-cache "
            f"hits); live after {live} B")


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------
def _spread(leaf) -> int:
    """Distinct devices holding a strict part of ``leaf``."""
    shards = leaf.addressable_shards
    check(all(s.data.shape != leaf.shape for s in shards),
          f"a shard of {leaf.shape} holds the whole array")
    return len({s.device for s in shards})


def phase_train_mesh(opts, meter) -> str:
    from repro.launch import train

    res = train.run(_train_argv(opts, ["--mesh", "2x2"]))
    check(res.exit_code == 0, f"train 2x2: exit code {res.exit_code}")
    spread = _spread(res.params["layers"]["ffn"]["w_up"])
    check(spread == 4, f"train 2x2: w_up spread over {spread} devices")
    losses4 = res.losses
    del res
    free_phase("train 2x2")
    res = train.run(_train_argv(opts, ["--mesh", "1x1"]))
    check(res.exit_code == 0, f"train 1x1: exit code {res.exit_code}")
    losses1 = res.losses
    del res
    live = free_phase("train 1x1")
    diff = max(abs(a - b) for a, b in zip(losses4, losses1))
    check(diff <= MESH_LOSS_ATOL,
          f"train: 2x2 losses {losses4} vs one chip {losses1}")
    return (f"[train-mesh] ok on {device_tag()}: 2x2 (data, model) mesh, "
            f"w_up over 4 devices, losses {[round(x, 5) for x in losses4]} "
            f"vs one chip {[round(x, 5) for x in losses1]}, max |diff| "
            f"{diff:.3g} <= {MESH_LOSS_ATOL}; live after {live} B")


def phase_serve_mesh(opts, meter) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.dist import partition
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.serve import Engine

    cfg = (configs.get_reduced(ARCH) if opts.rehearse
           else configs.get_config(ARCH))
    max_seq = 256 if opts.rehearse else 2048
    prefill = jax.jit(api.prefill, static_argnums=(1, 3))
    rng = np.random.default_rng(opts.seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (1, 24)),
                                   jnp.int32)}
    params = api.init_params(cfg, jax.random.key(opts.seed))
    want = np.asarray(prefill(params, cfg, batch, max_seq)[0], np.float32)

    engine = Engine(cfg, params, slots=8, max_seq=max_seq,
                    mesh=make_mesh((1, 4), ("data", "model")))
    del params
    try:
        spread = _spread(engine.params["layers"]["ffn"]["w_up"])
        check(spread == 4, f"serve 1x4: w_up spread over {spread} devices")
        got = np.asarray(prefill(engine.params, cfg, batch, max_seq)[0],
                         np.float32)
    finally:
        partition.set_mesh(None)
    del engine
    live = free_phase("serve 1x4")
    err = rel_l2(got, want)
    check(np.all(np.isfinite(got)), "serve 1x4: non-finite logits")
    check(err <= MESH_LOGITS_RTOL,
          f"serve 1x4: prefill logits rel L2 {err:.4g} vs one device")
    return (f"[serve-mesh] ok on {device_tag()}: prefill on a 1x4 (data, "
            f"model) mesh, w_up over 4 devices, logits rel L2 {err:.3g} "
            f"<= {MESH_LOGITS_RTOL} vs one device; live after {live} B")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, against one chip")
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced configs on the CPU (no chip needed)")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    if opts.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if opts.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={opts.chips}")
    import jax

    platform = jax.devices()[0].platform
    want = "cpu" if opts.rehearse else "tpu"
    if platform != want or jax.device_count() < opts.chips:
        print(f"chip_smoke: needs {opts.chips} {want} device(s), JAX found "
              f"{jax.device_count()} {platform}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke on {device_tag()}, jax {jax.__version__}, compile "
          f"cache {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    phases = ((phase_train_mesh, phase_serve_mesh) if opts.chips > 1
              else (phase_serve, phase_scientist, phase_train))
    for phase in phases:
        print(phase(opts, meter), flush=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving driver: continuous-batching engine over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \\
        --requests 8 --slots 4

Without ``--reduced`` the arch is served at its published widths.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, parse_mesh_shape
from repro.models import api
from repro.serve import Engine, Request


def run(argv=None):
    """Serve the synthetic requests ``argv`` describes.

    Returns ``(engine, finished)``, or None for an encoder-only arch."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced (smoke) config of the arch")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM", type=parse_mesh_shape,
                    help="serve on a (data, model) mesh, e.g. 2x4 "
                         "(needs data*model visible devices)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    if cfg.encoder_only:
        print(f"{args.arch} is encoder-only: no serving path")
        return None
    mesh = make_mesh(args.mesh, ("data", "model")) if args.mesh else None
    engine = Engine(cfg, api.init_params(cfg, jax.random.key(args.seed)),
                    slots=args.slots, max_seq=args.max_seq, mesh=mesh)

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new=args.max_new))
    finished = engine.run()
    dt = time.time() - t0
    tokens = sum(len(r.generated) for r in finished)
    dev = jax.devices()[0]
    print(f"served {len(finished)} requests, {tokens} tokens in {dt:.1f}s "
          f"wall, compiles included ({dev.platform} {dev.device_kind} "
          f"x{jax.device_count()})")
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"  req{r.rid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> {r.generated}")
    return engine, finished


def main(argv=None) -> int:
    return 0 if run(argv) is not None else 2


if __name__ == "__main__":
    sys.exit(main())

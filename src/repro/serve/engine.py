"""Continuous-batching serving engine.

Slot-based scheduler over the family-generic model API: new requests are
prefilled one at a time into a free slot of the shared padded cache;
every engine tick runs one fused decode step across all active slots;
finished requests free their slot immediately (no head-of-line blocking).
This is the serving analogue of the paper's evaluation loop — sequential
admission, batched execution.

The device programs are named ``jit_prefill``, ``jit_write_slot`` and
``jit_decode_step``; the last two take the cache donated and update it in
place (`programs`).  Each step of the host loop runs in a ``repro.*`` span
(``repro.obs``): ``admit`` (``rid``, ``slot``, ``tokens``) around one
request's admission, holding ``prefill`` (``rid``, ``tokens``,
``attn_steps``: the flash-loop steps per attention layer),
``write_slot`` (``rid``, ``slot``) and ``first_token`` (``rid``); then per
tick ``decode`` (``live``), ``sample`` (``live``) and ``retire``
(``finished``).  A request's ``t_admit`` is when its admission began.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist import partition
from repro.models import api
from repro.models.config import ArchConfig
from repro.obs import span


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new: int = 16
    eos_id: Optional[int] = None
    generated: list = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    t_admit: Optional[float] = None  # time.perf_counter(): admission began

    @property
    def done(self) -> bool:
        if self.eos_id is not None and self.generated \
                and self.generated[-1] == self.eos_id:
            return True
        return len(self.generated) >= self.max_new


def _batch_axis(key: str) -> int:
    return 0 if key == "len" else 1


def write_slot(cache, cache1, slot):
    """``cache`` with slot ``slot`` (traced) replaced by the one-slot
    ``cache1``; in place where ``cache`` is donated."""
    return {k: jax.lax.dynamic_update_slice_in_dim(
                v, cache1[k].astype(v.dtype), slot, _batch_axis(k))
            for k, v in cache.items()}


def programs(cfg: ArchConfig, max_seq: int):
    """The engine's device programs, jitted from named functions:
    ``jit_prefill``; ``jit_decode_step`` and ``jit_write_slot``, which donate
    the cache they are given, so that the engine's one cache is updated in
    place and never copied whole."""
    def prefill(p, b):
        return api.prefill(p, cfg, b, max_seq)

    def decode_step(p, c, t):
        return api.decode_step(p, cfg, c, t)

    return (jax.jit(prefill), jax.jit(decode_step, donate_argnums=1),
            jax.jit(write_slot, donate_argnums=0))


class Engine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_seq: int = 512, mesh=None):
        assert not cfg.encoder_only, "encoder-only models cannot serve"
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.mesh = mesh
        self.cache = api.init_cache(cfg, slots, max_seq,
                                    dtype=jnp.dtype(cfg.param_dtype))
        if mesh is not None:
            # register the mesh for in-graph shard_named constraints and
            # place weights once, weight-stationary (serve-mode wide TP)
            partition.set_mesh(mesh)
            self.params = jax.device_put(
                params,
                _named(partition.param_specs(params, mesh, mode="serve"),
                       mesh))
            self.cache = jax.device_put(
                self.cache,
                _named(partition.cache_specs(self.cache, mesh), mesh))
        self.free = deque(range(slots))
        self.active: dict[int, Request] = {}
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []

        self._prefill, self._decode, self._write_slot = programs(cfg,
                                                                 max_seq)

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        while self.queue and self.free:
            req = self.queue.popleft()
            slot = self.free.popleft()
            req.slot = slot
            req.t_admit = time.perf_counter()
            with span("admit", rid=req.rid, slot=slot,
                      tokens=len(req.prompt)):
                n = len(req.prompt)
                with span("prefill", rid=req.rid, tokens=n,
                          attn_steps=api.attn_steps(self.cfg, n)):
                    batch = {"tokens":
                             jnp.asarray(req.prompt, jnp.int32)[None]}
                    if self.cfg.mrope:
                        pos = jnp.arange(n, dtype=jnp.int32)[None]
                        batch["positions"] = jnp.stack(
                            [pos, pos * 0, pos * 0], 0)
                    # the prefill's cache holds its length
                    logits, cache1 = self._prefill(self.params, batch)
                with span("write_slot", rid=req.rid, slot=slot):
                    self.cache = self._write_slot(self.cache, cache1,
                                                  np.int32(slot))
                # last-position logits are the first new token
                with span("first_token", rid=req.rid):
                    req.generated.append(int(jnp.argmax(logits[0])))
                self.active[slot] = req

    # --------------------------------------------------------------- tick
    def tick(self) -> int:
        """Admit, run one decode step for all active slots, retire done."""
        self._admit()
        if not self.active:
            return 0
        live = len(self.active)
        with span("decode", live=live):
            tokens = np.zeros((self.slots,), np.int32)
            for slot, req in self.active.items():
                tokens[slot] = (req.generated[-1] if req.generated
                                else req.prompt[-1])
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tokens))
        with span("sample", live=live):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        with span("retire") as s:
            done = 0
            for slot in list(self.active):
                req = self.active[slot]
                req.generated.append(int(nxt[slot]))
                if req.done:
                    del self.active[slot]
                    self.free.append(slot)
                    self.finished.append(req)
                    done += 1
            s.set_metadata(finished=done)
        return len(self.active)

    def run(self, max_ticks: int = 1000) -> list:
        ticks = 0
        while (self.queue or self.active) and ticks < max_ticks:
            self.tick()
            ticks += 1
        return self.finished

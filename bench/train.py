"""Training cells: the jitted, donated step of ``repro.train``, driven as
``repro.launch.train`` drives it (host batch, ``device_put``, step,
``float(loss)``).

Set-up builds the step and its state from the seed and drives it through
its first ``check_steps`` steps, reading what the check compares; the same
step and state then run on through the window.  Once the window has closed
and the state is freed, the plain reference follows those first steps from
the same weights and batches, and three numbers are compared:

- ``loss_gap``: the largest |program - reference| / reference over the
  checked steps' losses;
- ``grad_norm_gap``: the first gradient as the optimizer gets it, before
  its clipping (the program's read from its first moment as
  m / ((1 - b1) s), s being the clipping factor of the gradient norm that
  the step reports), leaf by leaf (a layer's slice of a stacked leaf is a
  leaf): the largest gap between the two norms, over the larger of the
  reference leaf's norm and the median leaf's;
- ``change_norm_gap``: the same for the parameters' change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's: Adam moves those by round-off alone.

``median_grad_gap`` and ``median_change_gap`` are the median leaf's gaps.
A configuration's ``checks`` name the numbers that its cells compare.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import record, traffic

clock = time.perf_counter
ZERO_GRAD_FRACTION = 1e-3


# ---------------------------------------------------------------------------
# Leaf norms, on reference-layout trees
# ---------------------------------------------------------------------------
def leaf_norms(tree) -> dict:
    """name -> norm (float32), one per layer for the stacked leaves."""
    out = {}
    for name, x in tree.items():
        if name == "layers":
            for k, v in x.items():
                v = v.astype(jnp.float32)
                out[f"layers.{k}"] = jnp.sqrt(jnp.sum(
                    v * v, axis=tuple(range(1, v.ndim))))
        else:
            x = x.astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def flat(norms: dict) -> dict:
    """{(name, layer): float} from ``leaf_norms``' arrays."""
    return {(k, i): float(v) for k, arr in norms.items()
            for i, v in enumerate(np.asarray(arr).reshape(-1))}


def leaf_gaps(got: dict, want: dict, keep=None) -> dict:
    """|got - want| over max(want, median of want), by leaf."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keys}


def norm_gap(got: dict, want: dict, keep=None) -> float:
    """The largest of ``leaf_gaps``."""
    return max(leaf_gaps(got, want, keep).values())


def median_gap(got: dict, want: dict, keep=None) -> float:
    """The median of ``leaf_gaps``."""
    return float(np.median(list(leaf_gaps(got, want, keep).values())))


def moving_leaves(grad: dict) -> set:
    med = float(np.median(list(grad.values())))
    return {k for k, v in grad.items() if v >= ZERO_GRAD_FRACTION * med}


# ---------------------------------------------------------------------------
# The reference: the plain model, AdamW written out, float32
# ---------------------------------------------------------------------------
def lr_at(step: int, opt: dict) -> float:
    """The learning rate of ``step`` (0-based): linear warm-up to the peak
    over ``warmup_steps``, then a cosine to a tenth of it."""
    import math
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1) / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * frac)))


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def _row_grads(w, dm, tokens, labels, ref, mode):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ref.loss_of_row)(w, dm, tokens, labels,
                                                   mode)


def ref_grads(w, dm, tokens, labels, ref, mode: str = "f32"):
    """Mean loss over the rows and its gradient, on the host.  One row at
    a time, each row's gradient summed on the host, so that the device
    holds the weights, one gradient and one row's work at most."""
    loss, acc = 0.0, None
    for t, lab in zip(tokens, labels):
        l, g = _row_grads(w, dm, jnp.asarray(t), jnp.asarray(lab), ref, mode)
        g = jax.device_get(g)
        acc = g if acc is None else jax.tree.map(np.add, acc, g)
        loss += float(l)
    n = len(tokens)
    return loss / n, jax.tree.map(lambda x: x / np.float32(n), acc)


def _decay(p, wd):
    """The program's rule: weight decay on every leaf of two or more
    dimensions, layers stacked."""
    return wd if p.ndim >= 2 else 0.0


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7), donate_argnums=0)
def _adam_first(w, g, s, lr, b1, b2, eps, wd):
    """AdamW's first step: m / c1 = g and sqrt(v / c2) = |g|."""
    return jax.tree.map(lambda p, g: p - lr * (
        (g * s) / (jnp.abs(g * s) + eps) + _decay(p, wd) * p), w, g)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9),
                   donate_argnums=(0, 1, 2))
def _adam_second(w, ga, gb, sa, sb, lr, b1, b2, eps, wd):
    """AdamW's second step, the moments rebuilt from both gradients."""
    def upd(p, ga, gb):
        ga, gb = ga * sa, gb * sb
        m = b1 * (1 - b1) * ga + (1 - b1) * gb
        v = b2 * (1 - b2) * ga * ga + (1 - b2) * gb * gb
        step = (m / (1 - b1 ** 2)) / (jnp.sqrt(v / (1 - b2 ** 2)) + eps)
        return p - lr * (step + _decay(p, wd) * p)
    return jax.tree.map(upd, w, ga, gb)


def reference_steps(ref, conf: dict, mix: dict, seed: int, steps: int,
                    mode: str = "f32", rows=None):
    """The reference's losses, first gradient's leaf norms (before
    clipping), and leaf norms of the parameters' change after ``steps``
    (1 or 2) steps.  ``rows`` limits every batch to those rows (a planted fault).

    Adam's moments after two steps are rebuilt from the two gradients; the
    first waits on the host while the second is computed."""
    assert steps in (1, 2), "m and v are rebuilt from the first gradient"
    o = mix["optimizer"]
    b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
    dm = ref.dims(conf)
    w = jax.tree.map(lambda x: x.astype(jnp.float32),
                     ref.make_weights(conf, seed))
    losses, g1, s1, grad_norms = [], None, None, None
    for k in range(steps):
        b = traffic.train_batch(seed, k, mix["batch"], mix["seq_len"],
                                dm.vocab)
        toks, labs = b["tokens"], b["labels"]
        if rows is not None:
            toks, labs = toks[rows], labs[rows]
        loss, g_host = ref_grads(w, dm, toks, labs, ref, mode)
        losses.append(loss)
        g = jax.device_put(g_host)
        gnorm = jax.jit(lambda g: jnp.sqrt(sum(
            jnp.sum(x * x) for x in jax.tree.leaves(g))))(g)
        s = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-12))  # clipping
        lr = lr_at(k, o)
        if k == 0:
            s1 = s
            grad_norms = flat(jax.jit(leaf_norms)(g))
            w = _adam_first(w, g, s, lr, b1, b2, eps, wd)
            g1 = g_host                 # on the host while step 2 runs
        else:
            w = _adam_second(w, jax.device_put(g1), g, s1, s, lr,
                             b1, b2, eps, wd)
        del g
    w0 = ref.make_weights(conf, seed)
    change = flat(jax.jit(leaf_norms)(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), w, w0)))
    return losses, grad_norms, change


def compare(prog_read: dict, ref_read) -> dict:
    """The gaps between the program's readings and the reference's."""
    losses, grad, change = ref_read
    keep = moving_leaves(grad)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog_read["losses"], losses)),
        "grad_norm_gap": norm_gap(prog_read["grad"], grad),
        "median_grad_gap": median_gap(prog_read["grad"], grad),
        "change_norm_gap": norm_gap(prog_read["change"], change, keep),
        "median_change_gap": median_gap(prog_read["change"], change, keep),
    }


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
class Trainer:
    """The program's step and state, and what its first steps showed."""

    def __init__(self, ctx):
        from repro.optim import adamw
        from repro.train import make_train_step

        self.ctx = ctx
        conf, mix = ctx.conf, ctx.mix
        self.cfg = ctx.prog.arch_config(conf)
        o = mix["optimizer"]
        self.opt_cfg = adamw.AdamWConfig(
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
        step = make_train_step(self.cfg, peak_lr=o["peak_lr"],
                               warmup_steps=o["warmup_steps"],
                               total_steps=o["total_steps"],
                               opt_cfg=self.opt_cfg)
        self.jstep = jax.jit(step, donate_argnums=(0, 1))
        self.params = ctx.prog.program_weights(conf, self.cfg, ctx.seed)
        self.opt = jax.jit(adamw.init)(self.params)
        self.step = 0
        vocab = self.cfg.vocab
        self.norms = jax.jit(
            lambda t: leaf_norms(ctx.prog.from_program(t, vocab)))
        self.delta_norms = jax.jit(lambda a, b: leaf_norms(
            ctx.prog.from_program(jax.tree.map(
                lambda x, y: x.astype("float32") - y.astype("float32"),
                a, b), vocab)))

    def batch(self):
        m = self.ctx.mix
        return traffic.train_batch(self.ctx.seed, self.step, m["batch"],
                                   m["seq_len"], self.cfg.vocab)

    def run_step(self, span=None) -> float:
        span = span or (lambda name: contextlib.nullcontext())
        with span("bench.batch"):
            batch = jax.tree.map(jax.device_put, self.batch())
        with span("bench.train_step"):
            self.params, self.opt, self.metrics = self.jstep(
                self.params, self.opt, batch, np.int32(self.step))
            loss = float(self.metrics["loss"])
        self.step += 1
        return loss

    def first_steps(self, n: int) -> dict:
        """Run ``n`` steps, reading the losses, the first gradient's leaf
        norms (from the first moment, the clipping undone) and the change's
        leaf norms."""
        p0 = jax.device_get(self.params)
        losses = [self.run_step()]
        clip = min(1.0, self.opt_cfg.clip_norm
                   / (float(self.metrics["grad_norm"]) + 1e-12))
        grad = {k: v / ((1 - self.opt_cfg.b1) * clip)
                for k, v in flat(self.norms(self.opt["m"])).items()}
        losses += [self.run_step() for _ in range(n - 1)]
        change = flat(self.delta_norms(self.params, p0))
        del p0
        return {"losses": losses, "grad": grad, "change": change}


def run(ctx) -> dict:
    from bench.trace import Spans

    mix, conf = ctx.mix, ctx.conf
    trainer = Trainer(ctx)
    read = trainer.first_steps(mix["check_steps"])
    spans = Spans()
    steps, losses = [], []
    compiles0 = ctx.meter.compiles
    tokens = mix["batch"] * mix["seq_len"]

    capture = None
    if ctx.trace:
        from bench import trace as tr
        capture = tr.Capture(min(ctx.seconds, mix["trace_seconds"]))

    def window():
        if capture is not None:
            capture.start()
        t_open = clock()
        close = t_open + ctx.seconds
        while clock() < close:
            s = clock()
            losses.append(trainer.run_step(spans))
            steps.append(record.Step(s, clock(), tokens))
            if capture is not None:
                capture.poll(clock())
        if capture is not None:
            capture.stop()
        return t_open, clock()

    gc.collect()
    gc.disable()
    try:
        win = window()
    finally:
        gc.enable()
    compiles = ctx.meter.compiles - compiles0
    mem = ctx.memory_peak()
    del trainer
    gc.collect()

    gaps = compare(read, reference_steps(ctx.ref, conf, mix, ctx.seed,
                                         mix["check_steps"]))
    limits = conf["bench"]["checks"]
    checks = {k: {"value": v, "limit": limits[f"train_{k}"]}
              for k, v in gaps.items() if f"train_{k}" in limits}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    failed = sum(1 for x in losses if not np.isfinite(x))
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and failed == 0)
    run_rec = record.Run("train", ctx.ref, ctx.ref.dims(conf), ctx.peaks,
                         win, steps=steps, seq_len=mix["seq_len"])
    if capture is not None:                 # per-layer: the traced part
        run_rec.trace = capture.read(spans.recorded)
        run_rec.window = capture.window
        run_rec.steps = [s for s in steps if s.end <= capture.window[1]]
        metrics = record.read_metrics(run_rec, ctx.metrics)
        summary = run_rec.trace_summary()
    else:
        dt = win[1] - win[0]
        values = {"setup_s": win[0] - ctx.t0,
                  "train_tokens_per_s": len(steps) * tokens / dt}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in ctx.metrics if m["name"] in values}
        summary = {}
    return {"correct": bool(correct), "attempted": len(steps),
            "failed": failed, "metrics": metrics, "memory_peak_bytes": mem,
            "summary": summary, "checks": checks}

"""EvaluationService — the black-box testing/benchmark platform (paper §3.4).

Reproduces the competition interface constraints exactly:
  * submissions are **source text**, compiled server-side; compile/lowering
    failures come back as feedback strings;
  * numerical correctness is verified against a reference oracle before any
    timing is reported;
  * the only performance signal is end-to-end time per benchmark MxKxN
    configuration — no profiler;
  * submissions are processed **sequentially** ("good citizen", §3.4) — the
    service raises a typed ``ServiceBusyError`` on concurrent use.  Scaling
    comes from running *several* services behind ``core.evalpool.EvalPool``,
    never from violating the per-service contract.

Two timing backends:
  * ``cost_model`` — analytic TPU-v5e timing from the submission's GENOME
    metadata (a formula, never a measurement).  Its
    terms are the §Roofline terms: max(MXU, HBM, VPU) + pipeline overheads.
  * ``wall_clock`` — really executes the submitted kernel as one jitted
    program and times it.  ``interpret`` is passed to the submission's
    ``run`` explicitly and is true only on the CPU backend, so on a TPU the
    kernel is compiled by Mosaic; on the CPU (tests, small configurations)
    the timing is of the Pallas interpreter and says nothing about a chip.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import threading
import time
from typing import Optional

import numpy as np

from . import codegen
from .resilience import ServiceBusyError
from .genome import (
    HBM_BW, MXU_BF16_FLOPS, MXU_F32_FLOPS, SCALE_BLOCK, VMEM_USABLE,
    VPU_F32_FLOPS, KernelGenome,
)
from .population import BENCH_CONFIGS_18, config_key

LAUNCH_OVERHEAD_US = 15.0


class PlatformCompileError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Analytic TPU-v5e timing model (the platform's ground truth in this repo)
# ---------------------------------------------------------------------------
def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def estimate_us(genome: KernelGenome, m: int, n: int, k: int) -> float:
    """Estimated execution time in microseconds on one TPU v5e chip."""
    if genome.style == "library":
        # separate f32 dequant pass (read fp8 + write bf16, both operands),
        # then a well-blocked XLA matmul at ~75% MXU utilisation
        deq = 3 * (m * k + k * n) / HBM_BW
        mm_bytes = 2 * (m * k + k * n) + 2 * m * n
        mm = max(2 * m * n * k / (MXU_BF16_FLOPS * 0.75), mm_bytes / HBM_BW)
        return (deq + mm) * 1e6 + LAUNCH_OVERHEAD_US

    if genome.style == "naive":
        vmem = (m * k + k * n) + 4 * m * n + 2 * m * n
        if vmem > VMEM_USABLE:
            raise PlatformCompileError(
                f"RESOURCE_EXHAUSTED: single-program kernel requires "
                f"{vmem/2**20:.0f} MiB VMEM ({VMEM_USABLE/2**20:.0f} MiB "
                f"available): program allocation failed")
        t = max(2 * m * n * k / MXU_F32_FLOPS,
                (m * k + k * n + 2 * m * n) / HBM_BW)
        return t * 1e6 + LAUNCH_OVERHEAD_US

    # ---- blocked kernel: mirror run()'s clamping/padding exactly ----------
    bm = min(genome.block_m, _ceil(m, 128))
    bn = min(genome.block_n, _ceil(n, 128))
    bk = min(genome.block_k, _ceil(k, 128))
    mp, np_, kp = _ceil(m, bm), _ceil(n, bn), _ceil(k, bk)
    gm, gn, gk_total = mp // bm, np_ // bn, kp // bk
    ks = min(genome.k_split, gk_total)
    while gk_total % ks:
        ks -= 1

    # HBM traffic: A re-streamed once per N-block, B once per M-block
    # (index-map invariance gives no further reuse with K innermost).
    a_bytes = mp * kp * gn
    b_bytes = kp * np_ * gm
    scale_bytes = (mp * (kp // SCALE_BLOCK) * 4 * gn
                   + (kp // SCALE_BLOCK) * (np_ // SCALE_BLOCK) * 4 * gm)
    if ks > 1:  # f32 partials: write ks copies, read back, write bf16 final
        out_bytes = 4 * mp * np_ * ks * 2 + 2 * mp * np_
    else:
        out_bytes = 2 * mp * np_
    hbm = (a_bytes + b_bytes + scale_bytes + out_bytes) / HBM_BW

    rate = (MXU_BF16_FLOPS if genome.compute_dtype == "bfloat16"
            else MXU_F32_FLOPS)
    # accumulator revisit cost shrinks as the K tile grows
    util = 1.0 - 0.15 * (SCALE_BLOCK / bk)
    compute = 2 * mp * np_ * kp / (rate * util)

    n_sub_total = kp // SCALE_BLOCK
    if genome.scale_application == "scale_acc":
        vpu_flops = 3.0 * mp * np_ * n_sub_total
    else:  # dequantize both tiles on every use
        vpu_flops = 2.0 * (mp * kp * gn + kp * np_ * gm)
    if ks > 1:
        vpu_flops += ks * mp * np_  # final partial-sum reduction
    vpu = vpu_flops / VPU_F32_FLOPS

    # pipeline prologue/epilogue: first input fetch + last output drain
    overhead = 2 * (bm * bk + bk * bn) / HBM_BW
    return max(compute, hbm, vpu) * 1e6 + overhead * 1e6 + LAUNCH_OVERHEAD_US


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EvalResult:
    # ok | compile_error | runtime_error | incorrect — platform verdicts —
    # plus pool-level outcomes worker_error (requeue budget exhausted) and
    # quarantined (content hash blacklisted by core.integrity.Quarantine)
    status: str
    error: str = ""
    timings_us: dict = dataclasses.field(default_factory=dict)


class EvaluationService:
    def __init__(self, backend: str = "cost_model",
                 bench_configs=BENCH_CONFIGS_18,
                 correctness_config=(256, 256, 256),
                 noise: float = 0.0, seed: int = 0,
                 rtol: float = 0.06, latency_s: float = 0.0) -> None:
        if backend not in ("cost_model", "wall_clock"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.bench_configs = tuple(bench_configs)
        self.correctness_config = correctness_config
        self.noise = noise
        self.seed = seed
        self.rtol = rtol
        self.latency_s = latency_s   # models the shared queue's service delay
        self.submissions = 0
        self._lock = threading.Lock()
        # per-(config, seed) memo of problem tensors and the reference-oracle
        # output: the correctness config never changes within a campaign, so
        # the quantization + reference matmul are computed once, not per
        # submission
        self._memo: dict = {}

    # ------------------------------------------------------------------ api
    def submit(self, source: str) -> EvalResult:
        """Sequential black-box evaluation of one kernel source."""
        if not self._lock.acquire(blocking=False):
            raise ServiceBusyError(
                "EvaluationService is sequential-only (paper §3.4): a "
                "submission is already in flight")
        try:
            self.submissions += 1
            if self.latency_s:
                time.sleep(self.latency_s)
            return self._evaluate(source)
        finally:
            self._lock.release()

    def clone(self) -> "EvaluationService":
        """An identically-configured independent worker (for ``EvalPool``).

        The clone shares the timing seed: benchmark jitter is keyed on
        ``(seed, sha256(source), config)``, so any worker evaluating a given
        source reports the same timings — which worker a submission lands on
        never affects the campaign trajectory."""
        return EvaluationService(
            backend=self.backend, bench_configs=self.bench_configs,
            correctness_config=self.correctness_config, noise=self.noise,
            seed=self.seed, rtol=self.rtol, latency_s=self.latency_s)

    def service_spec(self) -> dict:
        """JSON-serializable constructor spec, so a subprocess worker
        (``core.eval_worker``) rebuilds an identically-seeded service.  The
        timing seed travels with the spec, so a respawned worker reports
        exactly the timings its predecessor would have (content-keyed
        jitter makes the verdict a pure function of the spec + source)."""
        return {"kind": "evaluation", "backend": self.backend,
                "bench_configs": [list(c) for c in self.bench_configs],
                "correctness_config": list(self.correctness_config),
                "noise": self.noise, "seed": self.seed, "rtol": self.rtol,
                "latency_s": self.latency_s}

    # ------------------------------------------------- resumable campaigns
    def state_dict(self) -> dict:
        """Counters to persist across a campaign restart.  Since benchmark
        jitter became content-keyed, nothing here affects decisions — the
        counter is restored for accurate submissions/hour accounting only."""
        return {"submissions": self.submissions}

    def load_state_dict(self, d: dict) -> None:
        self.submissions = d["submissions"]

    # ------------------------------------------------------------ internals
    def _evaluate(self, source: str) -> EvalResult:
        # content address of the submission: benchmark jitter keys on it (not
        # on the submission counter), so identical sources always time
        # identically regardless of submission order or worker assignment —
        # the invariant that makes concurrent pools and result caches safe
        skey = hashlib.sha256(source.encode()).hexdigest()
        try:
            run, genome_json = codegen.load_kernel(source)
        except Exception as e:  # platform 'compile' feedback
            return EvalResult("compile_error", f"{type(e).__name__}: {e}")

        run = self._executable(run)
        ok, err = self._check_correctness(run)
        if err is not None:
            # the kernel compiled/loaded but blew up while executing — a
            # distinct platform verdict so the selector/designer see accurate
            # feedback (a tiling bug, not a syntax error)
            return EvalResult("runtime_error", err)
        if not ok:
            return EvalResult("incorrect",
                              "output mismatch vs reference oracle "
                              f"(rtol {self.rtol}) on "
                              f"{self.correctness_config}")

        if self.backend == "cost_model":
            if not genome_json:
                return EvalResult(
                    "compile_error",
                    "platform rejected submission: missing GENOME metadata "
                    "(required for scheduling on the timing fleet)")
            try:
                genome = KernelGenome.from_json(genome_json)
                timings = {}
                for cfg in self.bench_configs:
                    t = estimate_us(genome, *cfg)
                    timings[config_key(cfg)] = self._jitter(t, cfg, skey)
            except PlatformCompileError as e:
                return EvalResult("compile_error", str(e))
            return EvalResult("ok", timings_us=timings)

        timings = {}
        for cfg in self.bench_configs:
            try:
                timings[config_key(cfg)] = self._time_wall(run, cfg)
            except Exception as e:
                return EvalResult("runtime_error",
                                  f"{type(e).__name__} on {cfg}: {e}")
        return EvalResult("ok", timings_us=timings)

    def _problem(self, cfg, seed=0):
        """Quantized problem tensors for one config, memoized per
        ``(config, seed)`` — regenerating + requantizing them for every
        submission was pure waste (the config set is fixed per campaign)."""
        memo_key = ("problem", tuple(cfg), seed)
        if memo_key in self._memo:
            return self._memo[memo_key]
        from repro.kernels import ref
        import jax.numpy as jnp
        m, n, k = cfg
        rng = np.random.default_rng(seed)
        a32 = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b32 = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        aq, a_s = ref.quantize_blockwise(a32, jnp.float8_e4m3fn)
        bq, b_s = ref.quantize_blockwise_2d(b32, jnp.float8_e4m3fn)
        self._memo[memo_key] = (aq, bq, a_s, b_s)
        return self._memo[memo_key]

    def _oracle(self, cfg, seed) -> np.ndarray:
        """Reference-oracle output, memoized per ``(config, seed)``: the
        quantization + reference matmul run once per service, not once per
        submission."""
        memo_key = ("oracle", tuple(cfg), seed)
        if memo_key in self._memo:
            return self._memo[memo_key]
        from repro.kernels import ref
        aq, bq, a_s, b_s = self._problem(cfg, seed=seed)
        want = np.asarray(ref.scaled_gemm(aq, bq, a_s, b_s), dtype=np.float32)
        self._memo[memo_key] = want
        return want

    @staticmethod
    def _executable(run):
        """The submission as the platform runs it: one jitted program, with
        ``interpret`` fixed by the backend (``kernels.default_interpret``)."""
        import jax
        from repro.kernels import default_interpret
        return jax.jit(functools.partial(run, interpret=default_interpret()))

    def _check_correctness(self, run) -> tuple:
        """Returns (is_correct, compile_error_or_None)."""
        m, n, k = self.correctness_config
        aq, bq, a_s, b_s = self._problem((m, n, k), seed=1234)
        want = self._oracle((m, n, k), seed=1234)
        try:
            got = np.asarray(run(aq, bq, a_s, b_s), dtype=np.float32)
        except Exception as e:
            return False, f"{type(e).__name__} during execution: {e}"
        if got.shape != want.shape:
            return False, None
        scale = float(np.max(np.abs(np.asarray(want)))) or 1.0
        return bool(np.max(np.abs(got - np.asarray(want))) <= self.rtol * scale), None

    def _time_wall(self, run, cfg) -> float:
        import jax
        args = self._problem(cfg)
        out = run(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    def _jitter(self, t_us: float, cfg, source_key: str) -> float:
        """Deterministic benchmark noise, keyed on the submission's content
        address (``sha256(source)``) rather than the global submission
        counter: a concurrent pool has no stable submission ordering, so the
        counter would make timings depend on scheduling.  Content keying
        makes the reported timings a pure function of (platform seed,
        source, config) — identical across workers, resubmissions, and
        resumed campaigns."""
        if not self.noise:
            return t_us
        h = hashlib.sha256(
            f"{self.seed}:{source_key}:{cfg}".encode()).digest()
        u = int.from_bytes(h[:8], "big") / 2**64
        v = int.from_bytes(h[8:16], "big") / 2**64
        gauss = math.sqrt(-2 * math.log(max(u, 1e-12))) * math.cos(2 * math.pi * v)
        return t_us * math.exp(self.noise * gauss)

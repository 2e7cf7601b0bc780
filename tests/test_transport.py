"""Distributed eval workers behind the unified ``EvalBackend`` API.

The acceptance scenarios of the transport layer:
  * wire protocol — length-prefixed JSONL frames round-trip; EOF is a clean
    ``None``; torn/corrupt frames raise instead of desyncing the stream;
  * worker death — a job whose worker dies is requeued at its original
    priority and re-evaluates to the identical verdict (content-keyed
    jitter); ``max_requeues`` bounds pathological crash loops;
  * subprocess transport — real ``eval_worker`` children speak the
    protocol, injected ``os._exit`` deaths respawn with stepped
    incarnations, job deadlines catch wedged evaluations;
  * pause/resume — a paused pool starts no new jobs but keeps queueing,
    and ``close()`` drains everything queued;
  * cache eviction — ``max_entries`` caps the LRU and compaction keeps
    ``eval_cache.jsonl`` O(max_entries);
  * the ``backend=`` constructor surface and its deprecated-kwarg shims;
  * @slow soak — a subprocess campaign with >= 20% injected worker-death
    rate finishes population-identical to an uninterrupted in-process
    ``workers=1`` run (the cross-transport determinism contract).
"""
import io
import json
import os
import pathlib
import threading
import time
import warnings

import pytest

import repro.core as core
from repro.core import codegen
from repro.core.evalpool import (
    PRIORITY_CAMPAIGN, EvalBackend, EvalCache, EvalPool,
)
from repro.core.eval_worker import EchoService, SleepyService, build_service
from repro.core.evaluator import EvalResult, EvaluationService
from repro.core.events import EventLog
from repro.core.genome import SEED_MXU
from repro.core.llm import ScriptedLLM
from repro.core.resilience import NO_WAIT_POLICY, CrashService, FlakyService
from repro.core.scientist import KernelScientist
from repro.core.transport import (
    InProcessTransport, RemoteEvalError, SubprocessTransport,
    WorkerDiedError, WorkerTransport, make_transport, read_frame,
    service_spec_of, write_frame,
)

SRC_OK = codegen.render_source(SEED_MXU, "transport test kernel")

#: Subprocess options tuned for tests: fast heartbeats, a deadline generous
#: enough for a cold child (jax import) but short enough to fail fast.
FAST_SUB = dict(heartbeat_interval_s=0.1, deadline_s=30.0,
                poll_interval_s=0.02)


# ---------------------------------------------------------------------------
# Wire protocol
# ---------------------------------------------------------------------------
def test_frame_round_trip():
    buf = io.BytesIO()
    frames = [{"frame": "submit", "job_id": 1, "source": "x = 1\n"},
              {"frame": "result", "timings_us": {"m1_n1_k1": 2.5},
               "note": "unicode µs → ok"},
              {"frame": "heartbeat"},
              {"frame": "submit", "source": "L" * 100_000}]  # large payload
    for f in frames:
        write_frame(buf, f)
    buf.seek(0)
    assert [read_frame(buf) for _ in frames] == frames
    assert read_frame(buf) is None           # clean EOF after the last frame


def test_frame_torn_and_corrupt_inputs():
    assert read_frame(io.BytesIO(b"")) is None
    with pytest.raises(ValueError, match="corrupt frame length"):
        read_frame(io.BytesIO(b"not-a-number\n{}\n"))
    whole = io.BytesIO()
    write_frame(whole, {"frame": "hello"})
    torn = io.BytesIO(whole.getvalue()[:-5])  # truncated payload
    with pytest.raises(ValueError, match="truncated"):
        read_frame(torn)
    with pytest.raises(ValueError, match="payload"):
        read_frame(io.BytesIO(b"8\n{\"frame\"\n"))  # right length, bad JSON


def test_service_spec_round_trip_rebuilds_equivalent_stack():
    svc = FlakyService(EvaluationService(noise=0.05, seed=9, latency_s=0.0),
                       seed=4, error_rate=0.2)
    spec = service_spec_of(svc)
    rebuilt = build_service(json.loads(json.dumps(spec)))  # via the wire
    assert type(rebuilt).__name__ == "FlakyService"
    assert (rebuilt.seed, rebuilt.error_rate) == (4, 0.2)
    assert rebuilt.inner.seed == 9 and rebuilt.inner.noise == 0.05
    # content-pure: the rebuilt stack times sources identically
    assert rebuilt.inner.submit(SRC_OK).timings_us == \
        svc.inner.submit(SRC_OK).timings_us
    with pytest.raises(TypeError, match="service_spec"):
        service_spec_of(object())


# ---------------------------------------------------------------------------
# EvalBackend protocol + the public surface
# ---------------------------------------------------------------------------
def test_evalpool_satisfies_evalbackend_protocol():
    pool = EvalPool([EvaluationService()], retry_policy=NO_WAIT_POLICY)
    assert isinstance(pool, EvalBackend)
    pool.close()

    class Incomplete:                        # no probe/state_dict/...
        def submit_async(self, source, priority=0, tag=None):
            pass

    assert not isinstance(Incomplete(), EvalBackend)


def test_core_all_exports_exactly_the_public_surface():
    assert len(core.__all__) == len(set(core.__all__))
    for name in core.__all__:
        assert not name.startswith("_"), f"{name} is private"
        assert getattr(core, name, None) is not None, f"{name} missing"
    ns = {}
    exec("from repro.core import *", ns)     # star import honours __all__
    assert set(core.__all__) <= set(ns)
    assert not {k for k in ns if k.startswith("_") and k != "__builtins__"}


# ---------------------------------------------------------------------------
# Worker death -> requeue (transport-agnostic, via a scripted transport)
# ---------------------------------------------------------------------------
class _DyingTransport(WorkerTransport):
    """Raises WorkerDiedError for the first ``deaths`` runs of each source,
    then answers with a content-keyed verdict — the subprocess failure mode
    without the subprocess."""

    kind = "scripted"

    def __init__(self, deaths=1, workers=1):
        self.deaths = deaths
        self.attempts = {}
        self.runs = 0
        self._workers = workers

    @property
    def num_workers(self):
        return self._workers

    def run(self, idx, source):
        self.runs += 1
        n = self.attempts[source] = self.attempts.get(source, 0) + 1
        if n <= self.deaths:
            self._emit("worker_died", worker=idx, incarnation=n - 1,
                       reason="scripted death", transport=self.kind)
            raise WorkerDiedError(f"scripted death #{n}")
        return EvalResult("ok", timings_us={"len": float(len(source))})

    def worker_states(self):
        return [None] * self._workers

    def load_worker_states(self, states):
        pass

    @property
    def submissions(self):
        return self.runs


def test_worker_death_requeues_job_to_identical_verdict():
    events = EventLog()
    transport = _DyingTransport(deaths=2)
    pool = EvalPool(transport=transport, events=events,
                    retry_policy=NO_WAIT_POLICY)
    handle = pool.submit_async("some kernel", tag="00042")
    res = handle.result(timeout=30)
    assert res.status == "ok"
    assert res.timings_us == {"len": float(len("some kernel"))}
    assert handle.requeues == 2              # died twice, landed the third
    requeues = events.select("worker_requeue")
    assert [r["tag"] for r in requeues] == ["00042", "00042"]
    assert [r["requeues"] for r in requeues] == [1, 2]
    assert len(events.select("worker_died")) == 2
    assert events.worker_lifecycle(worker=0)  # the lifecycle query sees both
    pool.close()


def test_requeue_keeps_original_priority():
    """A probe requeued after a death must not jump ahead of campaign work."""
    order = []

    class _Tracking(_DyingTransport):
        def run(self, idx, source):
            res = super().run(idx, source)
            order.append(source)
            return res

    gate = threading.Event()
    transport = _Tracking(deaths=0)
    real_run = transport.run

    def gated_run(idx, source):
        if source == "BLOCK":
            gate.wait(timeout=30)
            order.append(source)
            return EvalResult("ok", timings_us={})
        return real_run(idx, source)

    transport.run = gated_run
    pool = EvalPool(transport=transport, retry_policy=NO_WAIT_POLICY)
    blocker = pool.submit_async("BLOCK")
    time.sleep(0.05)                         # worker occupied on BLOCK
    probe = pool.probe("PROBE")
    campaign = pool.submit_async("CAMPAIGN")
    urgent = pool.urgent("URGENT")
    gate.set()
    for h in (blocker, probe, campaign, urgent):
        h.result(timeout=30)
    assert order == ["BLOCK", "URGENT", "CAMPAIGN", "PROBE"]
    pool.close()


def test_max_requeues_bounds_crash_loops():
    # requeue exhaustion is a terminal *verdict*, not an exception: the
    # campaign logs the doomed kernel (score inf) and keeps draining
    events = EventLog()
    pool = EvalPool(transport=_DyingTransport(deaths=10 ** 6), events=events,
                    retry_policy=NO_WAIT_POLICY, max_requeues=3)
    handle = pool.submit_async("doomed")
    res = handle.result(timeout=30)
    assert res.status == "worker_error"
    assert "gave up after 4 worker deaths" in res.error
    assert handle.requeues == 4              # 1 initial + 3 requeues
    assert len(events.select("worker_requeue")) == 4
    pool.close()


# ---------------------------------------------------------------------------
# Pause / resume
# ---------------------------------------------------------------------------
def test_pause_blocks_new_jobs_and_resume_drains():
    events = EventLog()
    svc = EchoService()
    pool = EvalPool([svc], events=events, retry_policy=NO_WAIT_POLICY,
                    idle_timeout_s=0.05)
    pool.pause()
    assert pool.paused and pool.stats()["paused"]
    handles = [pool.submit_async(f"k{i}") for i in range(3)]
    time.sleep(0.3)
    assert not any(h.done() for h in handles)  # nothing started while paused
    assert svc.submissions == 0
    pool.resume()
    assert not pool.paused
    for h in handles:
        assert h.result(timeout=30).status == "ok"
    assert [e["event"] for e in events.worker_lifecycle()] == \
        ["pool_pause", "pool_resume"]
    pool.close()


def test_close_unpauses_so_queued_work_drains():
    pool = EvalPool([EchoService()], retry_policy=NO_WAIT_POLICY,
                    idle_timeout_s=0.05)
    pool.pause()
    handle = pool.submit_async("queued while paused")
    pool.close(wait=True)                    # must not strand the job
    assert handle.result(timeout=30).status == "ok"


def test_pause_lets_inflight_job_finish():
    svc = EchoService(latency_s=0.3)
    pool = EvalPool([svc], retry_policy=NO_WAIT_POLICY, idle_timeout_s=0.05)
    first = pool.submit_async("inflight")
    time.sleep(0.1)                          # worker is mid-evaluation
    pool.pause()
    second = pool.submit_async("held")
    assert first.result(timeout=30).status == "ok"   # in-flight completes
    time.sleep(0.3)
    assert not second.done()                 # but nothing new starts
    pool.resume()
    assert second.result(timeout=30).status == "ok"
    pool.close()


# ---------------------------------------------------------------------------
# EvalCache LRU eviction + compaction
# ---------------------------------------------------------------------------
def _res(tag):
    return EvalResult("ok", timings_us={"t": float(tag)})


def test_cache_eviction_respects_max_entries():
    cache = EvalCache(max_entries=2)
    cache.put("k1", _res(1))
    cache.put("k2", _res(2))
    cache.get("k1")                          # refresh k1: k2 is now LRU
    cache.put("k3", _res(3))
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.get("k2") is None           # the LRU entry was evicted
    assert cache.get("k1").timings_us == {"t": 1.0}
    assert cache.get("k3").timings_us == {"t": 3.0}
    stats = cache.stats()
    assert stats["max_entries"] == 2 and stats["evictions"] == 1
    with pytest.raises(ValueError, match="max_entries"):
        EvalCache(max_entries=0)


def test_cache_compaction_keeps_file_bounded(tmp_path):
    path = tmp_path / "cache.jsonl"
    cache = EvalCache(path, max_entries=2)
    for i in range(8):
        cache.put(f"k{i}", _res(i))
    assert len(cache) == 2 and cache.compactions >= 1
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    assert len(lines) <= 2 + 2               # O(max_entries), not O(puts)
    cache.compact()
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    assert len(lines) == 2                   # exactly the live entries
    # reload reconstructs the survivors (most recent two, recency order)
    reloaded = EvalCache(path, max_entries=2)
    assert reloaded.get("k6").timings_us == {"t": 6.0}
    assert reloaded.get("k7").timings_us == {"t": 7.0}
    assert reloaded.get("k0") is None


def test_cache_reload_trims_overfull_file_to_cap(tmp_path):
    path = tmp_path / "cache.jsonl"
    unbounded = EvalCache(path)              # grown without a cap...
    for i in range(5):
        unbounded.put(f"k{i}", _res(i))
    capped = EvalCache(path, max_entries=3)  # ...then reopened with one
    assert len(capped) == 3
    assert capped.get("k4") is not None and capped.get("k0") is None


# ---------------------------------------------------------------------------
# SubprocessTransport against real eval_worker children
# ---------------------------------------------------------------------------
def test_subprocess_round_trip_matches_inprocess_verdicts():
    events = EventLog()
    pool = EvalPool.of(EchoService(), workers=2, events=events,
                       retry_policy=NO_WAIT_POLICY, transport="subprocess",
                       transport_options=FAST_SUB)
    sources = [f"kernel variant {i}\n" for i in range(4)]
    handles = [pool.submit_async(s, tag=str(i))
               for i, s in enumerate(sources)]
    results = [h.result(timeout=60) for h in handles]
    local = EchoService()
    for src, res in zip(sources, results):
        assert res.status == "ok"
        assert res.timings_us == local.submit(src).timings_us
    assert pool.stats()["transport"] == "subprocess"
    assert pool.submissions == len(sources)
    spawns = events.select("worker_spawn")
    assert spawns and all(s["transport"] == "subprocess" for s in spawns)
    pool.close()
    # close() shut the children down cleanly
    assert len(events.select("worker_exit")) == len(spawns)


def test_subprocess_worker_kill_requeues_and_respawns():
    """CrashService(seed=0) inside the child os._exit()s deterministically;
    the parent must detect each death, respawn with a stepped incarnation,
    and requeue to the same content-keyed verdicts."""
    events = EventLog()
    svc = CrashService(EchoService(), seed=0, crash_rate=0.25)
    pool = EvalPool.of(svc, workers=1, events=events,
                       retry_policy=NO_WAIT_POLICY, transport="subprocess",
                       transport_options=FAST_SUB)
    sources = [f"crashy kernel {i}\n" for i in range(6)]
    handles = [pool.submit_async(s) for s in sources]
    results = [h.result(timeout=120) for h in handles]
    local = EchoService()
    for src, res in zip(sources, results):
        assert res.status == "ok"
        assert res.timings_us == local.submit(src).timings_us
    deaths = events.select("worker_died")
    assert deaths, "crash_rate=0.25 at seed 0 must kill at least one worker"
    assert len(events.select("worker_requeue")) == len(deaths)
    assert sum(h.requeues for h in handles) == len(deaths)
    # every respawn stepped the incarnation: 0, 1, 2, ...
    incs = [s["incarnation"] for s in events.select("worker_spawn")]
    assert incs == list(range(len(deaths) + 1))
    pool.close()


def test_subprocess_job_deadline_reaps_wedged_worker():
    events = EventLog()
    svc = SleepyService(EchoService(), match="STALL", sleep_s=60.0)
    opts = dict(FAST_SUB, job_timeout_s=2.0)
    pool = EvalPool.of(svc, workers=1, events=events,
                       retry_policy=NO_WAIT_POLICY, transport="subprocess",
                       transport_options=opts)
    handle = pool.submit_async("kernel with STALL marker\n")
    res = handle.result(timeout=120)         # incarnation 1 does not sleep
    assert res.status == "ok" and handle.requeues == 1
    [death] = events.select("worker_died")
    assert "job deadline" in death["reason"]
    pool.close()


def test_subprocess_remote_retry_exhaustion_is_not_a_death(tmp_path):
    """A child whose own retries are exhausted reports an error frame —
    the pool marks the submission failed instead of requeueing forever."""
    transport = SubprocessTransport(
        [{"kind": "flaky", "error_rate": 1.0, "seed": 0,
          "inner": {"kind": "echo"}}],
        policy=NO_WAIT_POLICY, **FAST_SUB)
    try:
        with pytest.raises(RemoteEvalError, match="TransientError"):
            transport.run(0, "always fails\n")
    finally:
        transport.close()


def test_make_transport_resolution():
    svc = EchoService()
    assert isinstance(make_transport("inprocess", [svc]), InProcessTransport)
    inst = InProcessTransport([svc])
    assert make_transport(inst, []) is inst
    sub = make_transport("subprocess", [svc])
    assert isinstance(sub, SubprocessTransport)
    sub.close()
    with pytest.raises(ValueError, match="unknown transport"):
        make_transport("carrier-pigeon", [svc])


def test_subprocess_refuses_kernel_workers_on_a_tpu(monkeypatch):
    """One process owns the chip: kernel-executing subprocess workers are
    refused there, with a pointer to the in-process transport."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    flaky_eval = FlakyService(EvaluationService(backend="wall_clock"))
    with pytest.raises(ValueError, match="transport='inprocess'"):
        make_transport("subprocess", [flaky_eval])
    # services that run no kernel still get subprocess workers
    sub = make_transport("subprocess", [EchoService()])
    assert isinstance(sub, SubprocessTransport)
    sub.close()
    pool = EvalPool.of(EvaluationService(backend="wall_clock"), workers=1,
                       transport="inprocess")
    assert isinstance(pool.transport, InProcessTransport)
    pool.close()


# ---------------------------------------------------------------------------
# The backend= constructor surface + deprecated shims
# ---------------------------------------------------------------------------
def test_backend_accepts_a_constructed_pool_as_is():
    pool = EvalPool.of(EvaluationService(seed=2), workers=2,
                       cache=EvalCache(), retry_policy=NO_WAIT_POLICY)
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # the new surface must not warn
        sci = KernelScientist(llm=ScriptedLLM(seed=2), backend=pool,
                              retry_policy=NO_WAIT_POLICY)
    assert sci.pool is pool
    assert pool.events is sci.events         # events attached on adoption
    pool.close()


def test_backend_wraps_a_bare_service_in_a_cached_pool(tmp_path):
    sci = KernelScientist(llm=ScriptedLLM(seed=2),
                          backend=EvaluationService(seed=2),
                          workdir=tmp_path / "wd",
                          retry_policy=NO_WAIT_POLICY)
    assert isinstance(sci.pool, EvalPool)
    assert sci.pool.cache is not None
    assert sci.pool.cache.path == tmp_path / "wd" / "eval_cache.jsonl"
    sci.pool.close()


def test_legacy_kwargs_still_work_with_deprecation_warning():
    with pytest.warns(DeprecationWarning, match="backend="):
        sci = KernelScientist(llm=ScriptedLLM(seed=5),
                              service=EvaluationService(seed=5, noise=0.05),
                              workers=3, retry_policy=NO_WAIT_POLICY)
    assert sci.pool.stats()["workers"] == 3
    sci.pool.close()

    with pytest.warns(DeprecationWarning):
        plain = KernelScientist(llm=ScriptedLLM(seed=5),
                                eval_cache=False,
                                retry_policy=NO_WAIT_POLICY)
    assert plain.pool.cache is None
    plain.pool.close()


def test_backend_and_legacy_kwargs_are_mutually_exclusive():
    with pytest.raises(TypeError, match="not both"):
        KernelScientist(backend=EvaluationService(),
                        service=EvaluationService())


def test_legacy_and_new_surface_produce_identical_campaigns():
    def snap(sci):
        return [(r.rid, r.parents, r.status, r.timings_us)
                for r in sci.population]

    with pytest.warns(DeprecationWarning):
        old = KernelScientist(llm=ScriptedLLM(seed=5),
                              service=EvaluationService(seed=5, noise=0.05),
                              retry_policy=NO_WAIT_POLICY)
    old.run(2)
    new = KernelScientist(
        llm=ScriptedLLM(seed=5),
        backend=EvalPool.of(EvaluationService(seed=5, noise=0.05),
                            cache=EvalCache(),
                            retry_policy=NO_WAIT_POLICY),
        retry_policy=NO_WAIT_POLICY)
    new.run(2)
    assert snap(new) == snap(old)
    old.pool.close()
    new.pool.close()


def test_service_setter_preserves_custom_cache_instance():
    """Regression: assigning .service used to rebuild the pool with a fresh
    default cache, silently dropping a custom EvalCache (and its path)."""
    custom = EvalCache(max_entries=50)
    sci = KernelScientist(
        llm=ScriptedLLM(seed=3),
        backend=EvalPool.of(EvaluationService(seed=3), cache=custom,
                            retry_policy=NO_WAIT_POLICY),
        retry_policy=NO_WAIT_POLICY)
    sci.service = EvaluationService(seed=4)
    assert sci.pool.cache is custom          # the very same instance
    assert sci.service.seed == 4
    sci.pool.close()


# ---------------------------------------------------------------------------
# @slow soak: the cross-transport determinism acceptance scenario
# ---------------------------------------------------------------------------
def _norm_population(workdir):
    d = json.loads((pathlib.Path(workdir) / "population.json").read_text())
    return json.dumps(d, sort_keys=True)


@pytest.mark.slow
def test_soak_subprocess_kills_match_inprocess_population(tmp_path):
    """A subprocess campaign with >= 20% injected worker-death rate must
    finish with the same final population (normalized population.json) as
    an uninterrupted in-process workers=1 run on the same seed."""
    soak_dir = pathlib.Path(os.environ.get("TRANSPORT_SOAK_DIR", tmp_path))
    soak_dir.mkdir(parents=True, exist_ok=True)
    seed, gens = 5, 6

    ref = KernelScientist(
        llm=ScriptedLLM(seed=seed),
        backend=EvalPool.of(EvaluationService(seed=seed, noise=0.05),
                            workers=1, cache=EvalCache(),
                            retry_policy=NO_WAIT_POLICY),
        workdir=soak_dir / "inprocess", retry_policy=NO_WAIT_POLICY)
    ref.run(gens)
    ref.pool.close()

    crashy = CrashService(EvaluationService(seed=seed, noise=0.05),
                          seed=0, crash_rate=0.25)   # >= 20% death rate
    sub = KernelScientist(
        llm=ScriptedLLM(seed=seed),
        backend=EvalPool.of(crashy, workers=2, cache=EvalCache(),
                            retry_policy=NO_WAIT_POLICY,
                            transport="subprocess",
                            transport_options=FAST_SUB),
        workdir=soak_dir / "subprocess", retry_policy=NO_WAIT_POLICY)
    sub.run(gens)
    stats = sub.pool.stats()
    counts = sub.events.counts()
    sub.pool.close()

    assert len(sub.logbook) == gens          # zero aborted generations
    assert counts.get("worker_died", 0) > 0, \
        "the soak must actually exercise worker deaths"
    assert counts.get("worker_requeue", 0) >= counts["worker_died"] > 0
    assert stats["transport"] == "subprocess"
    assert _norm_population(soak_dir / "subprocess") == \
        _norm_population(soak_dir / "inprocess")

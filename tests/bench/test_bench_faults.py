"""Whole runs of the harness, past its look for a chip, with the timed path
broken underneath: each fault that a cell can have makes ``correct``
false, and the sound run beside them is correct.  Reduced widths, CPU.

A one-chip cell has no exchange between chips to leave out, so that
fault has no case here."""
import jax
import numpy as np
import pytest

from bench_testkit import (CONFIGS, TINY_CHAT, TINY_TRAIN, TRAIN_CONFIGS,
                          context, tiny_conf)
from bench import serve, train

per_train_config = pytest.mark.parametrize("name", TRAIN_CONFIGS)


@per_train_config
def test_sound_train_run_is_correct(name):
    out = train.run(context(tiny_conf(name), TINY_TRAIN, seed=31,
                            seconds=1.0))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def _patch_step(monkeypatch, wrap):
    import repro.train
    real = repro.train.make_train_step

    def make(cfg, **kw):
        return wrap(real(cfg, **kw))
    monkeypatch.setattr(repro.train, "make_train_step", make)


@per_train_config
def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch,
                                                           name):
    def wrap(step):
        def broken(params, opt, batch, i):
            _, _, metrics = step(params, opt, batch, i)
            return params, opt, metrics
        return broken
    _patch_step(monkeypatch, wrap)
    out = train.run(context(tiny_conf(name), TINY_TRAIN, seed=32,
                            seconds=0.5))
    assert not out["correct"]
    change = [c for k, c in out["checks"].items() if "change" in k]
    # no leaf moved: each change reads its own norm over itself or over
    # the median leaf's, 1 for most
    assert change and all(c["value"] > 0.9 for c in change)


@per_train_config
def test_half_the_batch_left_out_is_caught(monkeypatch, name):
    def wrap(step):
        def broken(params, opt, batch, i):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt, half, i)
        return broken
    _patch_step(monkeypatch, wrap)
    out = train.run(context(tiny_conf(name), TINY_TRAIN, seed=33,
                            seconds=0.5))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CONFIGS)
def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch, name):
    from repro.serve import Engine
    real = Engine.tick

    def tick(self):
        out = real(self)
        for req in self.active.values():      # the token just decoded
            req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab
        return out
    monkeypatch.setattr(Engine, "tick", tick)
    out = serve.run(context(tiny_conf(name), TINY_CHAT, seed=34,
                            seconds=1.0))
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("name", CONFIGS)
def test_float8_control_reads_wider_than_the_program(name):
    """The control of the serving check at a size a test can hold: the
    reference in float8 in the program's place reads a wider gap than the
    program does, on the same prompts and served tokens."""
    ctx = context(tiny_conf(name), TINY_CHAT, seed=35, seconds=1.0)
    d = serve.drive(ctx)
    sample = serve.check_sample(d["loop"], 6, ctx.seed)
    del d
    program = serve.max_gap(ctx, sample)
    control = serve.max_gap(ctx, sample, "fp8", "mode")
    assert np.isfinite(control) and control > program


@per_train_config
def test_float8_control_fails_the_train_check(name):
    """The training check's control at a size a test can hold: the
    reference in float8 in the program's place fails at least one of the
    numbers compared, where the program passes them all.  The control
    trains: every leaf's gradient is within a factor of 1.5 of the
    reference's, so it fails by its precision, not by a gradient lost."""
    conf = tiny_conf(name)
    ctx = context(conf, TINY_TRAIN, seed=36)
    limits = conf["bench"]["checks"]
    want = train.reference_steps(ctx.ref, conf, TINY_TRAIN, 36, 2)
    fp8 = train.reference_steps(ctx.ref, conf, TINY_TRAIN, 36, 2,
                                mode="fp8")
    ratio = np.asarray([fp8[1][k] / want[1][k] for k in want[1]])
    assert np.all((ratio > 1 / 1.5) & (ratio < 1.5)), ratio
    gaps = train.compare({"losses": fp8[0], "grad": fp8[1],
                          "change": fp8[2]}, want)
    assert any(gaps[k] > limits[f"train_{k}"] for k in gaps
               if f"train_{k}" in limits), gaps


def test_a_config_without_limits_is_refused(tmp_path):
    """A configuration whose limits were never read on the chip does not
    run as a cell."""
    import json
    import shutil
    from bench_testkit import ROOT, meter
    from bench import run
    (tmp_path / "bench/configs").mkdir(parents=True)
    shutil.copy(ROOT / "bench/configs/mamba2-2.7b.json",
                tmp_path / "bench/configs")
    bench = {"workloads": [{"name": "m.chat", "config": "mamba2-2.7b",
                            "traffic": "azure-conv-20slots", "chips": 1}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    args = run.parse(["--workload", "m.chat", "--seed", "1",
                      "--seconds", "1"])
    with pytest.raises(SystemExit, match="not a cell yet"):
        run.run_cell(args, jax.devices()[:1], {}, meter(), root=tmp_path)

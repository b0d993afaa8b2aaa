"""Multi-device behaviour via subprocesses (the main process keeps 1 CPU
device; --xla_force_host_platform_device_count must be set before jax init).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_search_converges():
    out = run_with_devices("""
import jax, numpy as np
from repro.launch.mesh import auto_mesh
from repro.core import env as env_lib, reinforce
from repro.distributed import dist_search
from repro.costmodel.layers import LayerSpec
wl = [LayerSpec.conv(32,16,28,28,3,3), LayerSpec.dwconv(64,14,14,3,3),
      LayerSpec.gemm(64,256,128)]
mesh = auto_mesh((4,2), ("data","model"))
state, hist = dist_search.run_distributed_search(
    wl, env_lib.EnvConfig(platform="iot"), mesh,
    reinforce.ReinforceConfig(epochs=80, lr=3e-3),
    dist_search.DistConfig(episodes_per_device=2))
assert np.isfinite(float(state.best_value)), hist["best_value"][-5:]
first = hist["best_value"][np.isfinite(hist["best_value"])][0]
assert float(state.best_value) <= first
print("OK", float(state.best_value))
""")
    assert "OK" in out


def test_straggler_masking_preserves_convergence():
    out = run_with_devices("""
import jax, numpy as np
from repro.launch.mesh import auto_mesh
from repro.core import env as env_lib, reinforce
from repro.distributed import dist_search
from repro.costmodel.layers import LayerSpec
wl = [LayerSpec.conv(32,16,28,28,3,3), LayerSpec.gemm(64,256,128)]
mesh = auto_mesh((4,2), ("data","model"))
mask = np.ones(8, bool); mask[[2,6]] = False
state, hist = dist_search.run_distributed_search(
    wl, env_lib.EnvConfig(platform="iot"), mesh,
    reinforce.ReinforceConfig(epochs=80, lr=3e-3),
    dist_search.DistConfig(episodes_per_device=2), straggler_mask=mask)
assert np.isfinite(float(state.best_value))
print("OK")
""")
    assert "OK" in out


def test_int8_psum_error_bound():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from jax.sharding import PartitionSpec as P
from repro.distributed.dist_search import psum_int8
mesh = auto_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
def f(xs):
    local = xs[0]
    exact = jax.lax.psum(local, "pod")
    approx = psum_int8(local, "pod")
    return exact[None], approx[None]
exact, approx = jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                              out_specs=P("pod", None))(x)
err = float(jnp.abs(exact - approx).max())
scale = float(jnp.abs(x).max()) / 127.0
assert err <= 8 * scale * 0.51 + 1e-6, (err, scale)  # n * scale/2 bound
print("OK", err)
""")
    assert "OK" in out


def test_int8_compressed_pod_reduction_converges():
    out = run_with_devices("""
import jax, numpy as np
from repro.launch.mesh import auto_mesh
from repro.core import env as env_lib, reinforce
from repro.distributed import dist_search
from repro.costmodel.layers import LayerSpec
wl = [LayerSpec.conv(32,16,28,28,3,3), LayerSpec.gemm(64,256,128)]
mesh = auto_mesh((2,2,2), ("pod","data","model"))
state, hist = dist_search.run_distributed_search(
    wl, env_lib.EnvConfig(platform="iot"), mesh,
    reinforce.ReinforceConfig(epochs=80, lr=3e-3),
    dist_search.DistConfig(episodes_per_device=2, compress_pod_axis=True))
assert np.isfinite(float(state.best_value))
print("OK")
""")
    assert "OK" in out


def test_masked_int8_pod_reduction_matches_plain_masked_psum():
    """Hierarchical masked+compressed reduction == flat masked_psum within
    int8 quantization tolerance, on 1-pod, 4-pod and asymmetric-alive
    meshes (regression: the old path divided by the axis count and a
    hardcoded npods=2)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from jax.sharding import PartitionSpec as P
from repro.distributed.dist_search import masked_psum, masked_hierarchical_psum

def run_case(mesh_shape, axes, alive_np):
    n = int(np.prod(mesh_shape))
    mesh = auto_mesh(mesh_shape, axes)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
    def f(xs, al):
        local, a = xs[0], al[0]
        plain = masked_psum({"g": local}, a, axes)["g"]
        comp = masked_hierarchical_psum({"g": local}, a, axes,
                                        compress=True)["g"]
        return plain[None], comp[None]
    plain, comp = jax.shard_map(
        f, mesh=mesh, in_specs=(P(axes, None), P(axes)),
        out_specs=(P(axes, None), P(axes, None)),
        check_vma=False)(x, jnp.asarray(alive_np))
    plain, comp = np.asarray(plain[0]), np.asarray(comp[0])
    rel = np.abs(plain - comp).max() / max(np.abs(plain).max(), 1e-9)
    assert rel < 0.05, (mesh_shape, axes, rel)
    return rel

# 1-pod mesh (pod axis of size 1: the cross-pod hop is a no-op).
run_case((1, 4), ("pod", "data"), np.ones(4, bool))
# 4-pod mesh, all alive (old code scaled by npods=2 -> 2x error).
run_case((4, 2), ("pod", "data"), np.ones(8, bool))
# Asymmetric alive: pod 0 keeps 1 of 2 devices, others keep 2 -- per-pod
# means averaged across pods would NOT equal the global masked mean.
mask = np.ones(8, bool); mask[[1, 2, 3]] = False
run_case((4, 2), ("pod", "data"), mask)
# Pod-only mesh: empty in-pod axis set.
mask = np.ones(8, bool); mask[5] = False
run_case((8,), ("pod",), mask)
print("OK")
""")
    assert "OK" in out


def test_fanout_device_backend_bit_identical_to_serial():
    """fanout backend='device' == backend='serial' for reinforce and ga."""
    out = run_with_devices("""
import numpy as np
from repro import api
from repro.core import env as env_lib
from repro.costmodel.layers import LayerSpec

wl = [LayerSpec.conv(32,16,28,28,3,3), LayerSpec.gemm(64,256,128)]
ecfg = env_lib.EnvConfig(platform="cloud")
for inner, eps, iopts in [("reinforce", 40, {}),
                          ("ga", 200, {"population": 20})]:
    outs = {}
    for backend in ("serial", "device"):
        outs[backend] = api.run_search(api.SearchRequest(
            workload=wl, env=ecfg, eps=eps, seed=3, method="fanout",
            options={"inner": inner, "n_shards": 4, "backend": backend,
                     "inner_options": iopts}))
    a, b = outs["serial"], outs["device"]
    assert a.best_value == b.best_value, (inner, a.best_value, b.best_value)
    assert a.history.tobytes() == b.history.tobytes(), inner
    np.testing.assert_array_equal(a.pe, b.pe)
    np.testing.assert_array_equal(a.kt, b.kt)
    np.testing.assert_array_equal(a.df, b.df)
    assert a.extras["shard_best_values"] == b.extras["shard_best_values"]
    assert a.extras["best_seed"] == b.extras["best_seed"]
print("OK")
""", n=4)
    assert "OK" in out


def test_fanout_device_backend_streams_tagged_progress():
    """Device backend streams shard-tagged, per-shard-monotone chunks."""
    out = run_with_devices("""
from repro import api
from repro.core import env as env_lib
from repro.costmodel.layers import LayerSpec

wl = [LayerSpec.conv(32,16,28,28,3,3), LayerSpec.gemm(64,256,128)]
trials = []
out = api.run_search(api.SearchRequest(
    workload=wl, env=env_lib.EnvConfig(platform="cloud"), eps=40, seed=3,
    method="fanout", progress_every=10, on_progress=trials.append,
    options={"inner": "reinforce", "n_shards": 4, "backend": "device"}))
assert sorted({t.shard for t in trials}) == [0, 1, 2, 3]
for s in range(4):
    steps = [t.step for t in trials if t.shard == s]
    assert steps == sorted(steps) and steps[-1] == 40, steps
bests = [t.best_value for t in trials]
assert all(b2 <= b1 for b1, b2 in zip(bests, bests[1:]))
print("OK")
""", n=4)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """pjit train step on a (2,2) mesh == unsharded result."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np, dataclasses, functools
from repro.launch.mesh import auto_mesh
from repro import configs
from repro.models import lm
from repro.training import optim
from repro.distributed import sharding
cfg = dataclasses.replace(configs.get_smoke("qwen1p5_0p5b"),
                          param_dtype="float32", compute_dtype="float32")
opt = optim.Adam(lr=1e-3)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
ost = opt.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": tokens}
step = functools.partial(lm.train_step, cfg=cfg, optimizer=opt)
p1, o1, l1 = jax.jit(step)(params, ost, batch)

mesh = auto_mesh((2, 2), ("data", "model"))
psh = sharding.tree_shardings(mesh, params)
params_s = jax.device_put(params, psh)
ost_s = jax.device_put(ost, sharding.tree_shardings(mesh, ost))
batch_s = {k: jax.device_put(v, sharding.batch_sharding(mesh, 4))
           for k, v in batch.items()}
pol = sharding.make_policy(mesh, batch=4, kind="train")
step_s = functools.partial(lm.train_step, cfg=cfg, optimizer=opt, pol=pol)
with mesh:
    p2, o2, l2 = jax.jit(step_s)(params_s, ost_s, batch_s)
assert abs(float(l1) - float(l2)) < 1e-4, (float(l1), float(l2))
# Adam update with lr=1e-3: reduction-order f32 noise in grads moves params
# by O(lr * eps_rel); 5e-4 = half an optimizer step of slack.
d = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.abs(a - np.asarray(b)).max()), p1, p2)))
assert d < 5e-4, d
print("OK", float(l1), d)
""")
    assert "OK" in out

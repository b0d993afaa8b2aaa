"""Sharding-mode correctness: tp / tp_serve / fsdp / dp must all produce
the same numbers, and their parameter placements must match their
contracts (SPerf hillclimb machinery)."""
import os
import subprocess
import sys

import numpy as np

from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_param_spec_modes():
    import jax
    from repro.distributed import sharding
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1, 1), ("data", "model"))

    # tp: rules fire (divisibility-guarded; 1-sized axes always divide).
    spec = sharding.param_spec(mesh, "blocks/mlp/w_gate", (64, 256), "tp")
    assert spec == P("data", "model")
    # tp_serve: the data/FSDP dim is dropped, model TP kept.
    spec = sharding.param_spec(mesh, "blocks/mlp/w_gate", (64, 256),
                               "tp_serve")
    assert spec == P(None, "model")
    # dp: everything replicated.
    assert sharding.param_spec(mesh, "blocks/mlp/w_gate", (64, 256),
                               "dp") == P()
    # fsdp: largest divisible dim over all axes.
    spec = sharding.param_spec(mesh, "blocks/mlp/w_gate", (64, 256), "fsdp")
    assert spec == P(None, ("data", "model"))


def _run(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_all_modes_agree_numerically():
    """One train step under tp / fsdp / dp == the unsharded result."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses, functools
from repro.launch.mesh import auto_mesh
from repro import configs
from repro.models import lm
from repro.training import optim
from repro.distributed import sharding
cfg = dataclasses.replace(configs.get_smoke("qwen2p5_3b"),
                          param_dtype="float32", compute_dtype="float32")
opt = optim.Adam(lr=1e-3)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
ost = opt.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                            cfg.vocab_size)
batch = {"tokens": tokens, "labels": tokens}
ref_step = functools.partial(lm.train_step, cfg=cfg, optimizer=opt)
p_ref, _, l_ref = jax.jit(ref_step)(params, ost, batch)

mesh = auto_mesh((4, 2), ("data", "model"))
for mode in ("tp", "fsdp", "dp"):
    psh = sharding.tree_shardings(mesh, params, mode)
    params_s = jax.device_put(params, psh)
    ost_s = jax.device_put(ost, sharding.tree_shardings(mesh, ost, mode))
    bsh = sharding.batch_sharding(mesh, 8, mode=mode)
    batch_s = {k: jax.device_put(v, bsh) for k, v in batch.items()}
    pol = sharding.make_policy(mesh, batch=8, kind="train", mode=mode)
    step = functools.partial(lm.train_step, cfg=cfg, optimizer=opt, pol=pol)
    with mesh:
        p2, _, l2 = jax.jit(step)(params_s, ost_s, batch_s)
    assert abs(float(l_ref) - float(l2)) < 1e-4, (mode, float(l_ref),
                                                  float(l2))
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - np.asarray(b)).max()), p_ref, p2)))
    assert d < 5e-4, (mode, d)
    print("OK", mode, float(l2), d)
""")
    assert out.count("OK") == 3


def test_remat_policies_agree():
    """full / dots / none remat produce identical losses and gradients."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses, functools
from repro.launch.mesh import auto_mesh
from repro import configs
from repro.models import lm
cfg = dataclasses.replace(configs.get_smoke("qwen1p5_0p5b"),
                          param_dtype="float32", compute_dtype="float32")
params = lm.init_params(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                            cfg.vocab_size)
vals = {}
for remat in ("full", "dots", "none"):
    f = functools.partial(lm.lm_loss, remat=remat)
    l, g = jax.jit(jax.value_and_grad(f), static_argnums=(1,))(
        params, cfg, tokens, tokens)
    vals[remat] = (float(l), g)
for remat in ("dots", "none"):
    assert abs(vals["full"][0] - vals[remat][0]) < 1e-5
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()),
        vals["full"][1], vals[remat][1])))
    assert d < 1e-4, (remat, d)
print("OK")
""", n=1)
    assert "OK" in out


def test_wire_accounting_reduce_scatter_and_dtype():
    from repro.distributed import hlo_analysis
    hlo = """
HloModule m
ENTRY %main (p: f32[256,128]) -> f32[32,128] {
  %p = f32[256,128]{1,0} parameter(0)
  %rs = f32[32,128]{1,0} reduce-scatter(%p), channel_id=1, replica_groups=[2,8]<=[16], dimensions={0}, to_apply=%add
  ROOT %out = f32[32,128]{1,0} copy(%rs)
}
"""
    stats = hlo_analysis.collective_stats(hlo)
    # result 32*128*4 = 16384 B; group size 8 -> operand-equivalent 131072.
    assert stats["reduce-scatter"] == 32 * 128 * 4 * 8
    stats2 = hlo_analysis.collective_stats(hlo, f32_elem_bytes=2)
    assert stats2["reduce-scatter"] == 32 * 128 * 2 * 8

"""Framework throughput: the batched cost-model evaluation hot-spot.

Design-point evaluations / second for (a) the pure-jnp oracle and (b) the
Pallas kernel in interpret mode (correctness path; the TPU path uses the
same kernel compiled).  Also measures the end-to-end REINFORCE epoch rate
-- the number the paper reports as "search time" (Table V) collapses from
minutes to milliseconds with the env inside the XLA program (DESIGN.md S3).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import env as env_lib, reinforce
from repro.costmodel import workloads
from repro.costmodel.layers import layers_to_array
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _bench(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def run(budget_name: str = "quick") -> dict:
    full = common.budget(budget_name)["rows"] == "all"
    wl = workloads.mobilenet_v2()
    layers = jnp.asarray(layers_to_array(wl), jnp.float32)
    N = layers.shape[0]
    rows, payload = [], {}
    for B in ((256, 2048, 16384) if full else (256, 2048)):
        key = jax.random.PRNGKey(0)
        pe = jax.random.uniform(key, (B, N), minval=1.0, maxval=128.0)
        kt = jax.random.uniform(key, (B, N), minval=1.0, maxval=12.0)

        ref_fn = jax.jit(lambda l, p, k: kref.cost_eval_ref(
            l.T, p, k, jnp.zeros_like(p)))
        t_ref = _bench(ref_fn, layers, pe, kt)
        evals = B * N
        rows.append([f"oracle (jnp)", B, f"{evals/t_ref:,.0f}"])
        payload[f"oracle_B{B}_evals_per_s"] = evals / t_ref

        if B <= 2048:  # interpret mode is python-speed; keep it bounded
            kern_fn = jax.jit(lambda l, p, k: kops._batched_cost_pallas(
                l, p, k, 0.0))
            t_k = _bench(kern_fn, layers, pe, kt, iters=2)
            rows.append([f"pallas (interpret)", B, f"{evals/t_k:,.0f}"])
            payload[f"pallas_interp_B{B}_evals_per_s"] = evals / t_k

    # End-to-end epoch rate (env-in-the-graph REINFORCE).
    ecfg = env_lib.EnvConfig(platform="iot")
    env = env_lib.make_env(wl, ecfg)
    import repro.core.policy as policy_lib
    from repro.training import optim
    pcfg = policy_lib.PolicyConfig(obs_dim=ecfg.obs_dim)
    rcfg = reinforce.ReinforceConfig(episodes_per_epoch=1)
    opt = optim.Adam(lr=3e-3)
    state = reinforce.init_search(env, ecfg, pcfg, rcfg, opt)
    epoch_fn = reinforce.make_epoch_fn(ecfg, pcfg, rcfg, env, opt)
    chunk = jax.jit(lambda s: jax.lax.scan(epoch_fn, s, None, length=100))
    state2, _ = chunk(state)
    jax.block_until_ready(state2.params)
    t0 = time.time()
    state2, _ = chunk(state2)
    jax.block_until_ready(state2.params)
    dt = time.time() - t0
    rows.append(["REINFORCE epochs/s (52-layer)", 100, f"{100/dt:,.0f}"])
    payload["reinforce_epochs_per_s"] = 100 / dt
    payload["paper_faithful_search_5000ep_seconds"] = 5000 * dt / 100

    common.print_table("Cost-model / search throughput (CPU host)",
                       ["path", "batch", "rate"], rows)
    print(f"=> full 5000-epoch paper search: "
          f"{payload['paper_faithful_search_5000ep_seconds']:.1f}s wall "
          "(the paper's PyTorch+binary setup: 25 min - 27 hrs, Table V)")
    return payload


if __name__ == "__main__":
    common.save_json("costmodel_throughput", run())

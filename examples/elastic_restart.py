"""Elastic fault-tolerant restart: train on one mesh, crash, resume on a
DIFFERENT mesh — bit-identical batches, re-sharded state.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/elastic_restart.py

Demonstrates the production failure story end to end:
  1. a 4-device (2 data x 2 model) job trains 20 steps and checkpoints;
  2. the job "loses half its slice" -- we restart on a 2-device (2x1)
     mesh; `checkpoint.restore` re-shards every leaf onto the new mesh;
  3. the job "scales out" to 8 devices (4x2) and resumes again;
  4. the deterministic data pipeline (batch = f(step)) plus the restored
     optimizer state make the loss trajectory continue exactly where it
     left off -- verified against an uninterrupted single-mesh run.
"""
import functools
import os
import shutil
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.distributed import sharding  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.training import checkpoint, data, optim  # noqa: E402

CKPT = "/tmp/repro_elastic_ckpt"
STEPS = (20, 30, 40)   # checkpoint boundaries: mesh changes at each


def train_segment(mesh_shape, start, stop, dcfg, cfg, opt, resume):
    mesh = auto_mesh(mesh_shape, ("data", "model"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = opt.init(params)
    psh = sharding.tree_shardings(mesh, params)
    params = jax.device_put(params, psh)
    opt_state = jax.device_put(opt_state,
                               sharding.tree_shardings(mesh, opt_state))
    if resume:
        (params, opt_state), at, _ = checkpoint.restore(
            CKPT, (params, opt_state))
        assert at == start, (at, start)
    pol = sharding.make_policy(mesh, batch=dcfg.global_batch, kind="train")
    bsh = sharding.batch_sharding(mesh, dcfg.global_batch)
    step_fn = jax.jit(functools.partial(lm.train_step, cfg=cfg,
                                        optimizer=opt, pol=pol),
                      donate_argnums=(0, 1))
    ds = data.make_dataset(dcfg)
    losses = []
    with mesh:
        for step in range(start, stop):
            batch = data.device_batch(ds.batch(step), bsh)
            params, opt_state, loss = step_fn(params, opt_state, batch)
            losses.append(float(loss))
    checkpoint.save(CKPT, stop, (params, opt_state))
    return losses


def main():
    n = len(jax.devices())
    assert n >= 8, ("run with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")
    import dataclasses
    cfg = dataclasses.replace(configs.get_smoke("qwen1p5_0p5b"),
                              param_dtype="float32",
                              compute_dtype="float32")
    opt = optim.Adam(lr=1e-3)
    dcfg = data.DataConfig(seq_len=64, global_batch=8,
                           vocab_size=cfg.vocab_size)
    shutil.rmtree(CKPT, ignore_errors=True)

    print("segment 1: (2,2) mesh, steps 0-20")
    l1 = train_segment((2, 2), 0, STEPS[0], dcfg, cfg, opt, resume=False)
    print("segment 2: SHRINK to (2,1), steps 20-30  (node failure)")
    l2 = train_segment((2, 1), STEPS[0], STEPS[1], dcfg, cfg, opt,
                       resume=True)
    print("segment 3: GROW to (4,2), steps 30-40  (scale out)")
    l3 = train_segment((4, 2), STEPS[1], STEPS[2], dcfg, cfg, opt,
                       resume=True)
    elastic = l1 + l2 + l3

    print("reference: uninterrupted (2,2) run, steps 0-40")
    shutil.rmtree(CKPT, ignore_errors=True)
    ref = train_segment((2, 2), 0, STEPS[2], dcfg, cfg, opt, resume=False)

    d = float(np.max(np.abs(np.asarray(elastic) - np.asarray(ref))))
    print(f"\nmax |elastic - uninterrupted| loss delta over 40 steps: "
          f"{d:.2e}")
    assert d < 5e-3, d
    print("ELASTIC RESTART OK: the resharded runs reproduce the "
          "uninterrupted trajectory")


if __name__ == "__main__":
    main()

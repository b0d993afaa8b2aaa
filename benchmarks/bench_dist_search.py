"""Distributed-search scaling: episode-parallel REINFORCE over a mesh.

Measures (a) epoch throughput and samples/sec as the device count grows
1 -> 4 -> 8, (b) the solution-quality effect of the scale knobs: straggler
masking (2 dead shards of 8) and the int8-compressed cross-pod gradient
reduction.  This is the paper's own workload at pod scale -- on a real
256-chip pod the same shard_map program runs 256x the episode batch per
epoch.  On an accelerator each mesh is built over the host's real local
devices in this process, and configurations needing more devices than the
host has are skipped; the CPU rehearsal forces host devices in a child
process per configuration (``common.run_on_devices``).
"""
from __future__ import annotations

import time

from benchmarks import common


def measure(n: int, mesh_shape, mesh_axes, epochs: int, dead: int = 0,
            compress: bool = False) -> dict:
    """One run_distributed_search over the first ``n`` local devices."""
    import jax
    import numpy as np

    from repro.core import env as env_lib
    from repro.core import reinforce
    from repro.costmodel import workloads
    from repro.distributed import dist_search
    from repro.launch.mesh import auto_mesh

    wl = workloads.mobilenet_v2()[:20]
    mesh = auto_mesh(mesh_shape, mesh_axes, devices=jax.devices()[:n])
    mask = np.ones(n, bool)
    mask[:dead] = False
    t0 = time.time()
    state, _ = dist_search.run_distributed_search(
        wl, env_lib.EnvConfig(platform="iot"), mesh,
        reinforce.ReinforceConfig(epochs=epochs, lr=3e-3),
        dist_search.DistConfig(episodes_per_device=2,
                               compress_pod_axis=compress),
        straggler_mask=mask)
    best = float(state.best_value)
    dt = time.time() - t0
    return {"devices": n, "epochs": epochs, "seconds": dt,
            "episodes_per_sec": epochs * 2 * int(mask.sum()) / dt,
            "best_value": best}


CONFIGS = [
    # (tag, devices, mesh shape, mesh axes, knob label, kwargs)
    ("d1", 1, (1,), ("data",), "-", {}),
    ("d4", 4, (2, 2), ("data", "model"), "-", {}),
    ("d8", 8, (2, 2, 2), ("pod", "data", "model"), "-", {}),
    ("d8_straggler", 8, (2, 2, 2), ("pod", "data", "model"),
     "2 dead shards", {"dead": 2}),
    ("d8_int8pod", 8, (2, 2, 2), ("pod", "data", "model"),
     "int8 pod-axis AR", {"compress": True}),
]


def run(budget_name: str = "quick") -> dict:
    import jax

    epochs = 150 if budget_name == "quick" else 600
    on_cpu = jax.default_backend() == "cpu"
    rows, payload = [], {"platform": jax.default_backend()}
    for tag, n, shape, axes, knob, kw in CONFIGS:
        if not on_cpu and n > len(jax.devices()):
            payload[tag] = f"skipped: needs {n} devices"
            continue
        r = common.run_on_devices(
            "benchmarks.bench_dist_search", "measure", n, timeout=900,
            n=n, mesh_shape=shape, mesh_axes=axes, epochs=epochs, **kw)
        payload[tag] = r
        rows.append([n, knob, r["episodes_per_sec"], r["best_value"]])
    common.print_table(
        f"Distributed search scaling (epochs={epochs}, 2 episodes/device)",
        ["devices", "knob", "episodes/s", "best value"], rows)
    knobs = [payload[t] for t in ("d8_straggler", "d8_int8pod")
             if isinstance(payload[t], dict)]
    # None: neither knob ran (they need 8 devices).
    ok = (all(r["best_value"] < float("inf") for r in knobs) if knobs
          else None)
    print(f"straggler-masked and int8-compressed runs converge: {ok} "
          f"({len(knobs)} of 2 run)")
    payload["fault_knobs_converge"] = ok
    return payload


if __name__ == "__main__":
    common.save_json("dist_search", run())

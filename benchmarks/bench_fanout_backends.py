"""Fanout backend scaling: serial vs threads vs device.

The ``fanout`` optimizer runs n independent seeds of an inner search and
merges the best -- the paper's sample-efficiency claim evaluated as a
wall-clock ensemble.  This benchmark measures how the three execution
backends spend that wall-clock for the two JAX-native inners (reinforce,
ga):

  * serial  -- n compiles + n sequential executions (the PR-1 baseline)
  * threads -- n compiles + n executions, overlapped by host threads
  * device  -- ONE compile of a shard_map'd program + all shards executing
               concurrently, one shard per local device

All backends produce bit-identical merged outcomes (asserted), so the only
difference is time.  On an accelerator the shards run in this process over
the real local devices (4 on the CPU rehearsal, which forces host devices
in a child process -- see ``common.run_on_devices``).
"""
from __future__ import annotations

import time

from benchmarks import common


def measure(inner: str, eps: int, shards: int, inner_opts: dict) -> dict:
    """Time each backend on one fanout request; asserts identical merges."""
    from repro import api
    from repro.costmodel import workloads

    req = dict(workload=workloads.mobilenet_v2()[:12],
               env=api.EnvConfig(platform="iot"), eps=eps, seed=0,
               method="fanout")
    res = {}
    for backend in ("serial", "threads", "device"):
        t0 = time.time()
        out = api.run_search(api.SearchRequest(
            **req, options={"inner": inner, "n_shards": shards,
                            "backend": backend, "inner_options": inner_opts}))
        res[backend] = {"seconds": time.time() - t0,
                        "best_value": out.best_value,
                        "history_tail": float(out.history[-1])}
        assert out.extras["backend"] == backend
    # All three must merge to the same ensemble result.
    assert len({r["best_value"] for r in res.values()}) == 1, res
    return res


def run(budget_name: str = "quick") -> dict:
    import jax

    eps = 300 if budget_name == "quick" else 2000
    shards = (4 if jax.default_backend() == "cpu"
              else min(4, len(jax.devices())))
    payload = {"n_shards": shards, "eps": eps,
               "platform": jax.default_backend()}
    rows = []
    for inner, iopts in [("reinforce", {}), ("ga", {"population": 50})]:
        r = common.run_on_devices(
            "benchmarks.bench_fanout_backends", "measure", shards,
            inner=inner, eps=eps, shards=shards, inner_opts=iopts)
        payload[inner] = r
        base = r["serial"]["seconds"]
        for backend in ("serial", "threads", "device"):
            rows.append([inner, backend, r[backend]["seconds"],
                         base / r[backend]["seconds"],
                         r[backend]["best_value"]])
    common.print_table(
        f"Fanout backends ({shards} shards, eps={eps}/shard, identical "
        f"merged outcomes)",
        ["inner", "backend", "seconds", "speedup vs serial", "best value"],
        rows)
    payload["speedup_device"] = {
        inner: payload[inner]["serial"]["seconds"]
        / payload[inner]["device"]["seconds"]
        for inner in ("reinforce", "ga")}
    return payload


if __name__ == "__main__":
    common.save_json("fanout_backends", run())

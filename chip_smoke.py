"""Smoke run of the search path on a TPU, through the entry points users call.

    python chip_smoke.py                  # phases a-d, one chip
    python chip_smoke.py --chips 4        # phase e only: needs 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

Phases run in this one process (a second process could not get the chip)
and each prints one line: its name, compile seconds (XLA/Mosaic compile,
or the persistent-cache load on a hit, summed over every program the phase
compiled, on whatever thread), run seconds (the rest of the phase's wall
clock), persistent-cache hits, and what it checked.

  a. device   jax.devices()[0].platform must be "tpu" (no silent fallback
              to the CPU); nothing else runs otherwise.
  b. kernels  ops.batched_cost (mobilenet_v2, GA population),
              ops.batched_cost_multi (the batcher's point-row shape over a
              mixed mobilenet_v2/resnet50/ncf batch) and ops.lstm_step (the
              policy's shape), each against its kernels/ref.py oracle on the
              same chip at the kernel tests' tolerance; each lowering must
              hold a Mosaic kernel (tpu_custom_call).  Reports the largest
              relative error and the count of values beyond rtol 1e-5, how
              many points the two cost kernels evaluate to different bits
              (service == serial rests on none), and the hard model's
              CPU-pinned golden values as computed here.
  c. search   api.run_search(method="two_stage") on mobilenet_v2/iot: the
              in-graph env, the LSTM kernel in the episode scan and the
              stage-2 GA on the cost kernel; checks the outcome schema.
  d. service  SearchService (persistent cache_dir) runs ga, nsga2 and random
              on mobilenet_v2/cloud, plus one ga over the HTTP front door;
              each best_value equals serial api.run_search (bit-identical where
              both evaluate on the kernel, rtol 1e-5 for random, whose serial
              path is the jnp model), and the batcher dispatched only the
              kernel (obs spans: cost_eval_kernel > 0, cost_eval_jnp == 0).
  e. fanout4  (--chips 4 only, and only this phase) fanout over 4 devices,
              backend="device" vs "serial" for inner reinforce and ga (equal
              best_value), then dist_reinforce over a 4-device mesh.

The last line of standard output is {"ok": true, "device": {...}}; any
failed check exits non-zero without it.  --rehearse runs the phases on any
platform (on the CPU the ops take their kernels/ref.py oracles), skips the
checks only a TPU can pass, and never prints the ok line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "iot"          # phase c: the paper's tight IoT budget
SERVED_PLATFORM = "cloud"  # phases d, e: budgets where GA finds feasible
#                            points, so the compared best_values are finite
# Hard-model values pinned on the CPU: tests/test_relaxed.py GOLDEN_CONV
# (conv 32x64x28x28, 3x3) and tests/test_env_constraints.py GOLDEN_LAYERS
# (dwconv / gemm rows at DLA).  (pe, kt, df) -> (lat, en, area, pw).
GOLDEN = [
    ("conv", (16.0, 4.0, 0), (778776.0, 69904.8203125, 115200.0,
                              24.6560001373291)),
    ("conv", (37.0, 7.0, 1), (524186.09375, 109936.4140625, 199800.0,
                              51.02300262451172)),
    ("conv", (128.0, 16.0, 2), (129055.3125, 80536.5390625, 819200.0,
                                188.03201293945312)),
    ("conv", (1.0, 1.0, 0), (12460053.0, 391123.375, 4200.0,
                             1.2710000276565552)),
    ("conv", (160.0, 12.0, 1), (179744.65625, 86053.25, 1184000.0,
                                249.44000244140625)),
    ("dwconv", (37.0, 7.0, 0), (42614.08203125, 63259.84765625, 377400.0,
                                67.00699615478516)),
    ("gemm", (128.0, 16.0, 0), (131103.3125, 117588.171875, 716800.0,
                                178.8159942626953)),
]


class Failed(Exception):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise Failed(what)


class CompileClock:
    """Sums XLA/Mosaic compile (or persistent-cache load) seconds and
    counts persistent-cache hits, across every thread of this process."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == self.HIT:
            with self._lock:
                self.hits += 1

    def snapshot(self):
        with self._lock:
            return self.seconds, self.hits


def run_phase(name, fn, clock: CompileClock) -> bool:
    """Run ``fn(checked)``, which fills ``checked`` as it goes, and print the
    phase's line; on failure the line keeps what was checked before it, the
    traceback goes to stderr, and the result is False."""
    c0, h0 = clock.snapshot()
    t0 = time.perf_counter()
    checked = {}
    try:
        fn(checked)
        ok = True
    except Exception as e:  # noqa: BLE001 -- reported; the script exits 1
        traceback.print_exc()
        checked["FAILED"] = f"{type(e).__name__}: {e}"
        ok = False
    wall = time.perf_counter() - t0
    c1, h1 = clock.snapshot()
    print(f"[{name}] compile_s={c1 - c0:.3f} "
          f"run_s={max(wall - (c1 - c0), 0.0):.3f} cache_hits={h1 - h0} "
          f"{json.dumps(checked, default=float)}", flush=True)
    return ok


def compare(got, want) -> dict:
    """Largest relative error and count of values beyond rtol 1e-5 (atol 0)
    of kernel outputs against their oracle."""
    got = np.concatenate([np.asarray(g, np.float64).ravel() for g in got])
    want = np.concatenate([np.asarray(w, np.float64).ravel() for w in want])
    check(np.all(np.isfinite(got)), "non-finite kernel output")
    err = np.abs(got - want)
    rel = err / np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    return {"n": int(got.size), "max_rel_err": float(rel.max()),
            "beyond_rtol_1e-5": int(np.sum(err > 1e-5 * np.abs(want)))}


def lowered_has_kernel(fn, *args, tpu: bool) -> bool:
    text = jax.jit(fn).lower(*args).as_text()
    found = "tpu_custom_call" in text
    if tpu:
        check(found, f"{getattr(fn, '__name__', fn)} lowered without a "
                     "Mosaic kernel (tpu_custom_call)")
    return found


# ---------------------------------------------------------------------------
# b. kernels
# ---------------------------------------------------------------------------
def phase_kernels(out: dict, tpu: bool) -> None:
    from repro.core import env as env_lib
    from repro.core import policy as policy_lib
    from repro.costmodel import maestro, workloads
    from repro.costmodel.layers import NUM_FIELDS, LayerSpec
    from repro.kernels import ops, ref
    from repro.kernels.costmodel_eval import TN
    from repro.serving import batcher

    rng = np.random.default_rng(0)
    ecfg = env_lib.EnvConfig(platform=PLATFORM)
    pe_tab, kt_tab = (np.asarray(t, np.float32)
                      for t in env_lib.action_tables(ecfg))
    tol = dict(rtol=1e-5, atol=1e-2)   # tests/test_kernels.py's tolerance

    def genomes(n_layers, pop):
        return (pe_tab[rng.integers(0, len(pe_tab), (pop, n_layers))],
                kt_tab[rng.integers(0, len(kt_tab), (pop, n_layers))],
                rng.integers(0, 3, (pop, n_layers)).astype(np.float32))

    # GA population against one workload: the broadcast kernel.
    layers = np.asarray(env_lib.make_env(workloads.mobilenet_v2(),
                                         ecfg).layers)
    pe, kt, df = genomes(layers.shape[0], 256)
    got = jax.block_until_ready(ops.batched_cost(layers, pe, kt, df))
    want = jax.jit(ref.cost_eval_ref)(layers.T, pe, kt, df)
    out["batched_cost"] = {
        "shape": [256, layers.shape[0]], **compare(got, want),
        "tpu_custom_call": lowered_has_kernel(ops.batched_cost, layers, pe,
                                              kt, df, tpu=tpu)}
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **tol)

    # Serial GA evaluates on the broadcast kernel, the service's batcher on
    # the per-row one: service == serial, bit for bit, needs both to give
    # the same bits for the same point.
    bcast = np.stack([np.asarray(g) for g in got], -1).reshape(-1, 4)
    per_row = batcher.eval_point_rows(
        batcher.pack_point_rows(layers, pe, kt, df), use_kernel=True)
    out["kernels_bitwise"] = {"n": int(bcast.size),
                              "differ": int(np.sum(bcast != per_row))}

    # Mixed-workload point rows in the batcher's (B', TN, NUM_FIELDS) shape.
    rows = []
    for name in ("mobilenet_v2", "resnet50", "ncf"):
        wl = np.asarray(env_lib.make_env(workloads.get_workload(name),
                                         ecfg).layers)
        rows.append(batcher.pack_point_rows(wl, *genomes(wl.shape[0], 32)))
    rows = np.concatenate(rows)
    rows = rows[rng.permutation(len(rows))][:len(rows) // TN * TN]
    lay = rows[:, :NUM_FIELDS].reshape(-1, TN, NUM_FIELDS)
    cols = [rows[:, NUM_FIELDS + i].reshape(-1, TN) for i in range(3)]
    got = jax.block_until_ready(ops.batched_cost_multi(lay, *cols))
    want = jax.jit(ref.cost_eval_multi_ref)(lay.transpose(0, 2, 1), *cols)
    out["batched_cost_multi"] = {
        "shape": list(lay.shape), **compare(got, want),
        "tpu_custom_call": lowered_has_kernel(ops.batched_cost_multi, lay,
                                              *cols, tpu=tpu)}
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **tol)

    # One policy step at the policy's own shapes.
    pcfg = policy_lib.PolicyConfig(obs_dim=ecfg.obs_dim)
    lp = policy_lib.init_params(jax.random.PRNGKey(0), pcfg)["lstm"]
    x = rng.standard_normal((1, pcfg.obs_dim)).astype(np.float32)
    h = rng.standard_normal((1, pcfg.hidden)).astype(np.float32)
    c = rng.standard_normal((1, pcfg.hidden)).astype(np.float32)
    args = (x, h, c, lp["wx"], lp["wh"], lp["b"])
    got = jax.block_until_ready(ops.lstm_step(*args))
    want = jax.jit(ref.lstm_cell_ref)(*args)
    out["lstm_step"] = {
        "shape": list(x.shape), **compare(got, want),
        "tpu_custom_call": lowered_has_kernel(ops.lstm_step, *args,
                                              tpu=tpu)}
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)

    # The hard model's CPU-pinned values, computed on this device by the
    # jnp model and by the kernel.
    specs = {"conv": LayerSpec.conv(32, 64, 28, 28, 3, 3),
             "dwconv": LayerSpec.dwconv(192, 28, 28, 3, 3),
             "gemm": LayerSpec.gemm(128, 256, 512)}
    g_layers = np.stack([specs[k].as_row() for k, _, _ in GOLDEN]
                        ).astype(np.float32)
    g_pe, g_kt, g_df = (np.asarray([p[i] for _, p, _ in GOLDEN], np.float32)
                        for i in range(3))
    pinned = np.asarray([v for _, _, v in GOLDEN], np.float32)
    model = jax.jit(maestro.evaluate)(g_layers, g_pe, g_kt, g_df)
    model = np.stack([np.asarray(model.latency), np.asarray(model.energy),
                      np.asarray(model.area), np.asarray(model.power)], 1)
    kern = ops.batched_cost_multi(g_layers[None], g_pe[None], g_kt[None],
                                  g_df[None])
    kern = np.stack([np.asarray(k)[0] for k in kern], 1)
    out["golden"] = {
        "n": int(pinned.size),
        "model_exact": int(np.sum(model == pinned)),
        "kernel_exact": int(np.sum(kern == pinned)),
        "model_max_rel_err": float(np.max(np.abs(model - pinned)
                                          / np.abs(pinned))),
        "kernel_max_rel_err": float(np.max(np.abs(kern - pinned)
                                           / np.abs(pinned)))}
    for got in (model, kern):
        np.testing.assert_allclose(got, pinned, **tol)


# ---------------------------------------------------------------------------
# c. search
# ---------------------------------------------------------------------------
def check_outcome(out, eps: int) -> dict:
    hist = np.asarray(out.history)
    check(len(hist) == eps, f"len(history) {len(hist)} != eps {eps}")
    check(np.all(hist[1:] <= hist[:-1]), "history is not monotone")
    check(hist[-1] == out.best_value, "history[-1] != best_value")
    check(out.feasible == bool(np.isfinite(out.best_value)),
          "feasible flag disagrees with best_value")
    check(np.isfinite(out.best_value) or out.best_value == np.inf,
          f"best_value {out.best_value}")
    return {"method": out.method, "best_value": out.best_value,
            "feasible": out.feasible}


def phase_search(res: dict) -> None:
    from repro import api

    eps = 300
    out = api.run_search(api.SearchRequest(
        workload="mobilenet_v2", env=api.EnvConfig(platform=PLATFORM),
        eps=eps, seed=0, method="two_stage",
        options={"ga": {"population": 20, "generations": 5}}))
    res["stage1_value"] = out.extras["stage1_value"]
    res.update(check_outcome(out, eps))
    check(out.best_value <= out.extras["stage1_value"],
          "stage-2 GA worsened the stage-1 value")


# ---------------------------------------------------------------------------
# d. service
# ---------------------------------------------------------------------------
SERVICE_REQUESTS = [
    # (method, eps, seed, options, exact vs serial)
    ("ga", 400, 1, {"population": 40}, True),
    ("nsga2", 320, 2, {"population": 32}, True),
    ("random", 512, 3, {}, False),
]
HTTP_REQUEST = ("ga", 200, 4, {"population": 20}, True)


def phase_service(res: dict, tpu: bool) -> None:
    from repro import api, obs
    from repro.obs import instrument
    from repro.serving import (HttpConfig, SearchClient, SearchHTTPService,
                               SearchService, ServiceConfig)

    ecfg = api.EnvConfig(platform=SERVED_PLATFORM)

    def request(method, eps, seed, opts):
        return api.SearchRequest(workload="mobilenet_v2", env=ecfg, eps=eps,
                                 seed=seed, method=method,
                                 options=dict(opts))

    serial = [api.run_search(request(*r[:4]))
              for r in SERVICE_REQUESTS + [HTTP_REQUEST]]

    obs.reset()
    obs.enable(trace=True)
    try:
        with tempfile.TemporaryDirectory() as cache_dir:
            svc = SearchService(ServiceConfig(max_workers=4,
                                              cache_dir=cache_dir))
            hub = SearchHTTPService(http_cfg=HttpConfig(port=0),
                                    service=svc).start()
            try:
                tickets = [svc.submit(request(*r[:4]))
                           for r in SERVICE_REQUESTS]
                method, eps, seed, opts, _ = HTTP_REQUEST
                uid = SearchClient(port=hub.port).submit(
                    {"workload": "mobilenet_v2", "method": method,
                     "eps": eps, "seed": seed,
                     "platform": SERVED_PLATFORM,
                     **opts})["uid"]
                outs = [t.result(timeout=900) for t in tickets]
                statuses = [t.status for t in tickets]
                wire = SearchClient(port=hub.port).result(uid, timeout=900)
                statuses.append(hub.front.get(uid).status)
            finally:
                hub.close()
                svc.close()
            shards = sum(len(f) for _, _, f in os.walk(cache_dir))
        spans = obs.tracer().spans()
        dropped = obs.tracer().dropped
    finally:
        obs.disable()

    res["statuses"] = statuses
    got = [o.best_value for o in outs] + [wire["best_value"]]
    res["requests"], mismatched = [], []
    for spec, want, g in zip(SERVICE_REQUESTS + [HTTP_REQUEST], serial, got):
        method, exact = spec[0], spec[4]
        w = want.best_value
        same = g == w
        res["requests"].append({"method": method, "best_value": g,
                                "serial": w, "bit_identical": same})
        if not (same if exact else abs(g - w) <= 1e-5 * abs(w)):
            mismatched.append(f"{method}: service {g!r} vs serial {w!r}")
    programs = [s["attrs"]["program"] for s in spans
                if s["name"] == "xla.dispatch"]
    res["dispatches"] = {p: programs.count(p) for p in sorted(set(programs))}
    res["dispatch_seconds"] = {
        p: instrument.DISPATCH_SECONDS.stats(program=p)["sum"]
        for p in sorted(set(programs))}
    res["persistent_cache_files"] = shards
    check(statuses == ["done"] * 4, f"ticket statuses {statuses}")
    check(not mismatched, "; ".join(mismatched))
    check(dropped == 0, f"span ring dropped {dropped} spans")
    check(shards > 0, "persistent cost cache wrote nothing")
    if tpu:
        check(programs.count("cost_eval_kernel") > 0,
              "no cost_eval_kernel dispatch")
        check(programs.count("cost_eval_jnp") == 0,
              "the batcher dispatched the jnp model on the TPU")


# ---------------------------------------------------------------------------
# e. four chips
# ---------------------------------------------------------------------------
def phase_fanout4(res: dict) -> None:
    from repro import api

    ecfg = api.EnvConfig(platform=SERVED_PLATFORM)
    mismatched = []
    for inner, eps, iopts in [("reinforce", 200, {}),
                              ("ga", 400, {"population": 40})]:
        outs = {}
        for backend in ("serial", "device"):
            outs[backend] = api.run_search(api.SearchRequest(
                workload="mobilenet_v2", env=ecfg, eps=eps, seed=3,
                method="fanout",
                options={"inner": inner, "n_shards": 4, "backend": backend,
                         "inner_options": iopts}))
        a, b = outs["serial"], outs["device"]
        check(b.extras["backend"] == "device", "device backend not taken")
        res[inner] = {
            "serial": a.best_value, "device": b.best_value,
            "history_bit_identical":
                a.history.tobytes() == b.history.tobytes(),
            "shard_bests_equal": (a.extras["shard_best_values"]
                                  == b.extras["shard_best_values"])}
        if a.best_value != b.best_value:
            mismatched.append(f"fanout {inner}: serial {a.best_value!r} != "
                              f"device {b.best_value!r}")
        check_outcome(b, eps)
    eps = 400
    out = api.run_search(api.SearchRequest(
        workload="mobilenet_v2", env=ecfg, eps=eps, seed=0,
        method="dist_reinforce", options={"episodes_per_device": 2}))
    check(out.extras["devices"] == 4,
          f"dist_reinforce ran on {out.extras['devices']} devices")
    res["dist_reinforce"] = check_outcome(out, eps)
    check(not mismatched, "; ".join(mismatched))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-device phase (e)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any platform, skip the TPU-only checks, "
                    "never print the ok line")
    args = ap.parse_args(argv)

    # a. device -- before anything else touches the repo.
    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    tpu = dev.platform == "tpu"
    if not (tpu or args.rehearse):
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {device}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    cache_files = (sum(len(f) for _, _, f in os.walk(cache_dir))
                   if os.path.isdir(cache_dir) else 0)
    clock = CompileClock()
    print(f"[device] compile_s=0.000 "
          f"run_s={time.perf_counter() - t0:.3f} cache_hits=0 "
          + json.dumps({**device, "compile_cache": cache_dir,
                        "compile_cache_files_at_start": cache_files,
                        "rehearsal": args.rehearse}), flush=True)

    if args.chips == 4:
        phases = [("fanout4", phase_fanout4)]
    else:
        phases = [("kernels", lambda res: phase_kernels(res, tpu)),
                  ("search", phase_search),
                  ("service", lambda res: phase_service(res, tpu))]
    failed = [name for name, fn in phases if not run_phase(name, fn, clock)]
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print("chip_smoke: rehearsal passed (no ok line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

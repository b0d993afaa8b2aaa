"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``chipbench/configs/<config>.json``, its traffic mix
``chipbench/traffic/<traffic>.json``, the limits of its comparison
``chipbench/limits/<cell>.json``, the mix's driver
``chipbench/drivers/<driver>.py`` and each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  Adding a cell or a metric adds files;
no file here changes.

Set-up (process start to window open) builds the system and warms up every
program the window runs; the window offers the mix's load for ``--seconds``;
afterwards the peak device memory is read, the system is closed, and what
the timed path produced is compared with the frozen reference.  With
``--trace 1`` the window runs under the JAX profiler (the mix says which
part of it) and ``repro.obs`` metrics, and the line carries the per-layer
metrics instead of the end-to-end ones.  With ``--control 1`` the
reference one precision lower takes the reference's place, so a sound
comparison reads ``correct`` false.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``);
the last stderr lines give each number compared beside its limit.  Without
a TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCH = os.path.join(ROOT, "chipbench")
TRACE_DIR = os.path.join(ROOT, ".chipbench_traces")


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = _load_json(os.path.join(BENCH, "configs",
                                     cell["config"] + ".json"))
    mix = _load_json(os.path.join(BENCH, "traffic",
                                  cell["traffic"] + ".json"))
    limits = _load_json(os.path.join(BENCH, "limits", name + ".json"))
    return bench, cell, config, mix, limits


def load_metric(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a driver and the metric readers share about one run."""

    def __init__(self, cell, config, mix, limits, seed, seconds, trace):
        self.cell, self.config, self.mix = cell, config, mix
        self.limits = limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_process = T_PROCESS
        self.at_close = []

    def closed(self):
        """Called by the driver when the window closes (answers still due
        may follow): takes the readings that end with the window."""
        for fn in self.at_close:
            fn()

    def log(self, msg: str):
        print(msg, file=sys.stderr, flush=True)


class Reading:
    """Inputs of the per-layer metric readers (see chipbench/metrics)."""

    def __init__(self, ctx, driver, trace, obs0, obs1, device_kind, chips):
        self.ctx, self.driver, self.trace = ctx, driver, trace
        self.obs0, self.obs1 = obs0, obs1
        self.device_kind, self.chips = device_kind, chips
        self.seconds = ctx.seconds

    def obs_delta(self, name: str, labels: str = "", field=None) -> float:
        def get(snap):
            v = snap.get(name, {}).get("values", {}).get(labels)
            if v is None:
                return 0.0
            return float(v[field]) if field else float(v)
        return get(self.obs1) - get(self.obs0)


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, control: bool = False,
            out=sys.stdout) -> int:
    bench, cell, config, mix, limits = load_cell(cell_name)
    import jax
    from repro.launch.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    devices = jax.devices()
    print(f"devices found {time.perf_counter() - T_PROCESS:.3f} s",
          file=sys.stderr)
    platform = devices[0].platform
    if require_chip and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    from chipbench import drivers
    from repro import obs

    ctx = Context(cell, config, mix, limits, seed, seconds, trace)
    driver = drivers.load(mix["driver"])(ctx)
    if trace:
        # On through set-up too, so the programs it warms count as seen.
        obs.enable(trace=False)
    driver.setup()
    ctx.log(f"set-up done at {time.perf_counter() - T_PROCESS:.3f} s")
    obs0 = obs1 = {}
    trace_dir = os.path.join(TRACE_DIR, f"{cell_name}.{seed}")
    tracer = None
    if trace:
        obs0 = obs.REGISTRY.snapshot()
        tracer = _Tracer(trace_dir, mix.get("trace_offset_s", 0.0),
                         mix.get("trace_seconds", seconds),
                         mix.get("trace_options", {}))
    setup_s = time.perf_counter() - T_PROCESS
    if tracer:
        tracer.start()
    programs = _ProgramCount()
    ctx.at_close.append(programs.stop)
    snaps = []
    if trace:
        ctx.at_close.append(lambda: snaps.append(obs.REGISTRY.snapshot()))
    driver.window(seconds)
    ctx.log(f"programs in the window: {programs.compiled} compiled, "
            f"{programs.loaded} loaded from the compile cache")
    if tracer:
        tracer.join()
        obs1 = snaps[0]
        obs.disable()
    peak = _memory_peak(devices)
    driver.close()

    if control:
        for n, v, lim, op in driver.check():
            ctx.log(f"sound {n} = {float(v)!r} (limit {op} {lim!r})")
    numbers = driver.check(control=control)
    correct = all(_holds(v, lim, op) for _, v, lim, op in numbers)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": driver.attempted,
            "failed": driver.failed}
    metric_defs = {m["name"]: m for m in bench["end_to_end"]
                   + bench["per_layer"]}
    metrics = {}
    if trace:
        from chipbench import trace as trace_lib
        tr = trace_lib.Trace(trace_lib.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.log(f"trace read at {time.perf_counter() - T_PROCESS:.3f} s")
        busy = tr.busy_s()
        device["busy_s"] = (sum(busy.values()) / len(busy)) if busy else 0.0
        device["window_s"] = tr.window_s
        reading = Reading(ctx, driver, tr, obs0, obs1, devices[0].device_kind,
                          len(devices))
        for m in bench["per_layer"]:
            if cell_name not in m.get("workloads", [cell_name]):
                continue
            v = load_metric(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    else:
        e2e = dict(driver.e2e, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if cell_name in m.get("workloads", [cell_name]) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": metric_defs[m["name"]]["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {n: {"value": v if math.isfinite(v) else None,
                          "limit": lim, "holds": op}
                      for n, v, lim, op in numbers}
    for n, v, lim, op in numbers:
        print(f"check {n} = {float(v)!r} (limit {op} {lim!r})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return 0


def _holds(v, lim, op) -> bool:
    return v >= lim if op == ">=" else v <= lim


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class _ProgramCount:
    """Programs compiled, and loaded from the persistent compile cache,
    from construction until ``stop()`` (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.requests = self.loaded = 0
        self.on = True

        def on_event(event, **_):
            if not self.on:
                return
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.loaded += 1

        jax.monitoring.register_event_listener(on_event)

    def stop(self):
        self.on = False

    @property
    def compiled(self) -> int:
        return self.requests - self.loaded


class _Tracer:
    """Profiles ``seconds`` of the window, starting ``offset`` in, on a
    thread of its own; the span is annotated ``chipbench.window``.
    ``advanced`` is the profiler's advanced configuration (the mix's
    ``trace_options``, such as the TPU's ``tpu_trace_mode``)."""

    def __init__(self, path, offset, seconds, advanced):
        self.path, self.offset, self.seconds = path, offset, seconds
        self.advanced = advanced
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.error = None

    def start(self):
        self.thread.start()

    def join(self):
        self.thread.join()
        if self.error is not None:
            raise self.error

    def _run(self):
        import jax
        try:
            time.sleep(self.offset)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            if self.advanced:
                opts.advanced_configuration = dict(self.advanced)
            jax.profiler.start_trace(self.path, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    time.sleep(self.seconds)
            finally:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                print(f"trace stopped at {t_stop - T_PROCESS:.3f} s, "
                      f"written in {time.perf_counter() - t_stop:.3f} s",
                      file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 -- re-raised by join()
            self.error = e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare against the reference one precision lower "
                         "(a sound run then reads correct false); the sound "
                         "numbers go to stderr beside it")
    args = ap.parse_args(argv)
    return execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   control=bool(args.control))


if __name__ == "__main__":
    sys.exit(main())

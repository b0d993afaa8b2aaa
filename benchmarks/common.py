"""Shared benchmark harness utilities.

Every benchmark module exposes ``run(budget) -> dict`` where budget scales
the sample counts ("quick" for CI-sized runs, "full" for the paper's
Eps=5000).  Results are printed as aligned tables and written to
``results/<bench>.json`` so EXPERIMENTS.md can cite them.
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(REPO, "results")

# Sample budgets (paper: Eps = 5000).
BUDGETS = {
    "quick": {"eps": 600, "ga_gens": 300, "rows": "subset"},
    "full": {"eps": 5000, "ga_gens": 2000, "rows": "all"},
}


def budget(name: str) -> Dict:
    return BUDGETS[name]


def fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == float("inf"):
            return "NAN"          # the paper's notation for infeasible
        if v != 0 and (abs(v) >= 1e4 or abs(v) < 1e-2):
            return f"{v:.2e}"
        return f"{v:.3g}"
    return str(v)


def print_table(title: str, headers: Sequence[str],
                rows: Sequence[Sequence]) -> None:
    cells = [[fmt(c) for c in r] for r in rows]
    widths = [max(len(h), *(len(r[i]) for r in cells)) if cells else len(h)
              for i, h in enumerate(headers)]
    print(f"\n== {title} ==")
    print("  ".join(h.ljust(w) for h, w in zip(headers, widths)))
    print("  ".join("-" * w for w in widths))
    for r in cells:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)))


def save_json(name: str, payload: Dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=_jsonable)
    return path


def _jsonable(o):
    import numpy as np

    if isinstance(o, (np.floating, np.integer)):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def run_on_devices(module: str, func: str, n_devices: int, /,
                   timeout: float = 1800, **kwargs):
    """``module.func(**kwargs)`` where ``n_devices`` devices exist; returns
    its JSON-able result.

    On an accelerator the call runs in this process over the real local
    devices: this process already holds the chip, so a child could not get
    it.  On the CPU it runs in a child started with
    ``--xla_force_host_platform_device_count=n_devices`` (the flag must
    precede JAX start-up) -- a rehearsal of the multi-device path, not a device
    measurement.
    """
    import jax

    if jax.default_backend() != "cpu":
        if len(jax.devices()) < n_devices:
            raise RuntimeError(f"{module}.{func} needs {n_devices} devices, "
                               f"this host has {len(jax.devices())}")
        return getattr(importlib.import_module(module), func)(**kwargs)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.pathsep.join([REPO, os.path.join(REPO, "src")])
    code = (f"import json\nfrom {module} import {func}\n"
            f"print(json.dumps({func}(**json.loads({json.dumps(kwargs)!r}))))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0


def stamp_metrics(payload: Dict, key: str = "metrics") -> Dict:
    """Attach the current ``repro.obs`` metrics snapshot to a results
    payload (no-op when telemetry is disabled) -- benchmarks call this just
    before ``save_json`` so ``results/*.json`` carry the registry state
    that produced them."""
    from repro import obs

    if obs.enabled():
        payload[key] = obs.REGISTRY.snapshot()
    return payload


def write_metrics_prom(name: str) -> str:
    """Write the current registry as ``results/<name>.prom`` (Prometheus
    text exposition) and return the path."""
    from repro import obs

    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.prom")
    obs.write_prometheus(path)
    return path

"""How each kind of traffic drives the system under test, and what it checks.

A driver builds the system, warms up every program the window will run
(set-up), offers the load of its mix for the window, and afterwards compares
what the timed path produced with :mod:`chipbench.reference`.  The mix's
``driver`` field names the module ``chipbench/drivers/<driver>.py`` that
holds it (its ``DRIVER``), so a new kind of traffic is a new file:

* ``service_closed``: a closed loop of ``clients`` threads, each submitting
  its next search to one in-process ``SearchService`` when the previous one
  ends.

Every driver records, around the service's batcher, each call that
evaluates fresh points (rows in, costs out), so the per-point values the
batcher served can be checked after the window.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

from chipbench import reference, rl_reference

DATAFLOWS = reference.DATAFLOWS


def load(name: str):
    """The driver class of ``chipbench/drivers/<name>.py``."""
    return importlib.import_module(f"chipbench.drivers.{name}").DRIVER


def work_in_window(marks, t_open: float, t_close: float) -> float:
    """Whole-model evaluations done inside [t_open, t_close].

    ``marks`` holds, per search, its progress as [(time, evals so far)],
    starting at (submission time, 0).  The evaluations between two marks
    are spread evenly over the time between them, so a chunk that straddles
    an edge of the window counts for the share of it inside."""
    total = 0.0
    for m in marks:
        for (t0, s0), (t1, s1) in zip(m, m[1:]):
            if t1 <= t0:
                total += (s1 - s0) * (t_open <= t1 <= t_close)
                continue
            inside = min(t1, t_close) - max(t0, t_open)
            if inside > 0:
                total += (s1 - s0) * inside / (t1 - t0)
    return total


class Outcome:
    """One finished search as the user got it."""

    def __init__(self, spec, best_value, pe, kt, df, history=None):
        self.spec = spec
        self.best_value = float(best_value)
        self.pe = np.asarray(pe, np.float64)
        self.kt = np.asarray(kt, np.float64)
        self.df = np.asarray(df, np.float64)
        self.history = history


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.mix = ctx.mix
        self.layers = np.asarray(self.config["layers"], np.float32)
        self.outcomes: List[Outcome] = []
        self.attempted = 0
        self.failed = 0
        self.fresh: List[tuple] = []        # (rows, costs) per kernel call
        self.e2e: Dict[str, float] = {}
        self.evals = 0.0

    # -- helpers ------------------------------------------------------------
    def request(self, spec, on_progress=None):
        from repro import api
        from repro.core import env as env_lib
        ecfg = env_lib.EnvConfig(
            objective=spec["objective"], constraint="area",
            platform=spec["platform"], scenario="LP",
            dataflow=DATAFLOWS.index(spec["dataflow"]),
            levels=self.config["levels"])
        return api.SearchRequest(
            workload=self.config["workload"], env=ecfg, eps=spec["eps"],
            seed=spec["seed"], method=spec["method"],
            options=dict(spec["options"]), on_progress=on_progress,
            progress_every=self.mix["progress_every"])

    def record_fresh(self, batcher):
        """Wrap the batcher's call into the cost kernel: record its rows
        and results, and in a traced run annotate it with its row count."""
        import jax
        inner = batcher._eval_points
        traced = self.ctx.trace

        def eval_points(rows):
            if traced:
                with jax.profiler.TraceAnnotation(
                        f"chipbench.eval_points:{len(rows)}"):
                    out = inner(rows)
            else:
                out = inner(rows)
            self.fresh.append((np.array(rows, copy=True),
                               np.array(out, copy=True)))
            return out

        batcher._eval_points = eval_points

    def service_config(self):
        from repro.serving.search_service import ServiceConfig
        s = self.config["service"]
        return ServiceConfig(max_workers=s["max_workers"],
                             cache_entries=s["cache_entries"],
                             window_ms=s["window_ms"])

    # -- correctness --------------------------------------------------------
    def budgets(self, dtype) -> Dict[tuple, float]:
        out = {}
        for o in self.outcomes:
            key = (o.spec["platform"], o.spec["dataflow"])
            if key not in out:
                out[key] = reference.budget(
                    self.layers, DATAFLOWS.index(key[1]),
                    self.config["platform_fractions"][key[0]],
                    self.config["levels"], dtype=dtype)
        return out

    def check(self, control: bool = False) -> List[tuple]:
        """[(name, value, limit, op)] -- the numbers compared, each with its
        limit; the run is correct iff every value holds against its limit.

        ``best_gap``: the widest relative gap between a finished search's
        reported best value and the reference's cost of the design it
        reports, where a design the reference finds over the platform's
        budget reads as an infinite gap.  ``control`` puts the reference
        computed one precision lower in the reference's place (bfloat16
        cost model, three-pass LSTM matmuls): what a sound comparison must
        refuse."""
        import jax.numpy as jnp
        dtype = jnp.bfloat16 if control else jnp.float32
        lim = self.ctx.limits
        budgets = self.budgets(dtype)
        best_gap, n_feasible = 0.0, 0
        for o in self.outcomes:
            if not np.isfinite(o.best_value):
                continue
            n_feasible += 1
            cost = reference.model_costs(self.layers, o.pe, o.kt, o.df,
                                         dtype)
            ref = cost[0 if o.spec["objective"] == "latency" else 1]
            fits = cost[2] <= budgets[(o.spec["platform"],
                                       o.spec["dataflow"])]
            gap = abs(o.best_value - ref) / abs(ref) if fits else np.inf
            best_gap = max(best_gap, gap)
        self.ctx.log(f"answers checked: {len(self.outcomes)}, "
                     f"{n_feasible} feasible")
        if not n_feasible:
            best_gap = float("inf")   # nothing was shown correct
        nums = [("best_gap", best_gap, lim["best_gap"], "<=")]
        if "point_gap" in lim:
            nums.append(("point_gap", self.point_gap(dtype),
                         lim["point_gap"], "<="))
        if "rl_value_gap" in lim:
            nums.append(("rl_value_gap", self.rl_value_gap(control),
                         lim["rl_value_gap"], "<="))
        return nums

    def point_gap(self, dtype) -> float:
        """Widest relative gap of a sample of the served per-point costs."""
        if not self.fresh:
            return float("inf")
        rows = np.concatenate([r for r, _ in self.fresh])
        out = np.concatenate([c for _, c in self.fresh])
        n = min(len(rows), self.mix.get("check_points", 4096))
        rng = np.random.default_rng([self.ctx.seed, 7])
        pick = rng.choice(len(rows), size=n, replace=False)
        r = rows[pick]
        ref = reference.point_costs(r[:, :8], r[:, 8], r[:, 9], r[:, 10],
                                    dtype)
        got = out[pick].astype(np.float64)
        ref = ref.astype(np.float64)
        return float(np.max(np.abs(got - ref)
                            / np.maximum(np.abs(ref), 1e-30)))

    def rl_value_gap(self, control: bool) -> float:
        """Widest relative gap of the first epochs' episode values against
        the replay of each finished search from its seed: the episodes
        follow the policy's sampled actions, so a policy step (LSTM kernel,
        heads, update) that strays changes the designs they evaluate."""
        epochs = self.mix.get("replay_epochs", 3)
        gap, checked = 0.0, 0
        for o in self.outcomes:
            if o.history is None:
                continue
            b = reference.budget(
                self.layers, DATAFLOWS.index(o.spec["dataflow"]),
                self.config["platform_fractions"][o.spec["platform"]],
                self.config["levels"])
            _, value = rl_reference.replay_np(
                o.spec["seed"], self.layers, np.float32(b),
                objective=o.spec["objective"],
                dataflow=DATAFLOWS.index(o.spec["dataflow"]),
                levels=self.config["levels"], epochs=epochs,
                control=control)
            got = np.asarray(o.history["mean_value"][:epochs], np.float64)
            gap = max(gap, float(np.max(np.abs(got - value)
                                        / np.abs(value))))
            checked += 1
        return gap if checked else float("inf")

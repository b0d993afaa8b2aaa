"""``service_closed`` on a DNN with instance rows (BMM, EXPERTS).

The closed loop, warm-up and window of :mod:`chipbench.drivers.
service_closed`; the answers and the served per-point costs are checked
against :mod:`chipbench.reference_inst`, the frozen reference extended to
the two instance types.  Set-up first resolves the configuration's
workload, so a program that lacks it fails within seconds.  After the
check the log gives, at the answers' best designs, the BMM rows' share of
latency and the EXPERTS rows' share of energy and area.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import reference_inst
from chipbench.drivers import DATAFLOWS
from chipbench.drivers.service_closed import ServiceClosed


class ServiceClosedInst(ServiceClosed):

    def setup(self):
        from repro.costmodel import workloads
        workloads.get_workload(self.config["workload"])
        super().setup()

    def budgets(self, dtype) -> Dict[tuple, float]:
        out = {}
        for o in self.outcomes:
            key = (o.spec["platform"], o.spec["dataflow"])
            if key not in out:
                out[key] = reference_inst.budget(
                    self.layers, DATAFLOWS.index(key[1]),
                    self.config["platform_fractions"][key[0]],
                    self.config["levels"], dtype=dtype)
        return out

    def check(self, control: bool = False) -> List[tuple]:
        """``best_gap`` and ``point_gap`` as in :meth:`Driver.check`, against
        the reference extended to instance rows."""
        import jax.numpy as jnp
        dtype = jnp.bfloat16 if control else jnp.float32
        lim = self.ctx.limits
        budgets = self.budgets(dtype)
        best_gap, shares = 0.0, []
        for o in self.outcomes:
            if not np.isfinite(o.best_value):
                continue
            cost = reference_inst.model_costs(self.layers, o.pe, o.kt, o.df,
                                              dtype)
            ref = cost[0 if o.spec["objective"] == "latency" else 1]
            fits = cost[2] <= budgets[(o.spec["platform"],
                                       o.spec["dataflow"])]
            gap = abs(o.best_value - ref) / abs(ref) if fits else np.inf
            best_gap = max(best_gap, gap)
            shares.append(reference_inst.shares(self.layers, o.pe, o.kt,
                                                o.df))
        self.ctx.log(f"answers checked: {len(self.outcomes)}, "
                     f"{len(shares)} feasible")
        for k in (shares[0] if shares else ()):
            v = [s[k] for s in shares]
            self.ctx.log(f"best designs' {k} share: min {min(v):.4f} "
                         f"median {float(np.median(v)):.4f} "
                         f"max {max(v):.4f}")
        if not shares:
            best_gap = float("inf")   # nothing was shown correct
        return [("best_gap", best_gap, lim["best_gap"], "<="),
                ("point_gap", self.point_gap(dtype), lim["point_gap"],
                 "<=")]

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
        ref = reference_inst.point_costs(r[:, :8], r[:, 8], r[:, 9],
                                         r[:, 10], dtype).astype(np.float64)
        got = out[pick].astype(np.float64)
        return float(np.max(np.abs(got - ref)
                            / np.maximum(np.abs(ref), 1e-30)))


DRIVER = ServiceClosedInst

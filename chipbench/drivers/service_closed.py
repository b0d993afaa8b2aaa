"""Closed loop through ``SearchService.submit``.

``clients`` threads each submit their next search when the previous one
ends, from window open to window close.  Searches still running at the
close run on to their end (at most ``drain_s`` past it, else they count as
failed) and are checked like the rest; the rate counts only the work done
inside the window.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from chipbench import reference, traffic
from chipbench.drivers import Driver, Outcome, work_in_window

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


class ServiceClosed(Driver):

    def setup(self):
        from repro.serving import SearchService
        from repro.serving import batcher as batcher_lib
        mix = self.mix
        warm = mix.get("warmup", {})
        # Every padded row count the window can hand the cost kernel, on
        # points of another configuration's DNN; these calls bypass the
        # batcher, so no memo cache sees their points.
        if warm.get("kernel_rows_max"):
            with open(os.path.join(CONFIGS, warm["kernel_rows_from"]
                                   + ".json")) as f:
                layers = np.asarray(json.load(f)["layers"], np.float32)
            rng = np.random.default_rng(0)
            for m in range(128, warm["kernel_rows_max"] + 128, 128):
                rows = np.ones((m, 11), np.float32)
                rows[:, :8] = layers[rng.integers(0, len(layers), m)]
                rows[:, 8] = rng.choice(reference.PE_LEVELS[12], m)
                rows[:, 9] = rng.integers(1, 13, m)
                rows[:, 10] = rng.integers(0, 3, m)
                batcher_lib.eval_point_rows(rows, True)
        self.ctx.log(f"kernel shapes warm at "
                     f"{time.perf_counter() - self.ctx.t_process:.3f} s")
        # One search of every program shape the window runs.  A cold-cache
        # mix warms up in a throwaway service, so the window's memo cache
        # starts empty.
        specs = self.warm_specs()
        svc = SearchService(self.service_config())
        self.record_fresh(svc.batcher)
        tickets = [svc.submit(self.request(s, on_progress=lambda t: None))
                   for s in specs]
        for t in tickets:
            t.result()
        if warm.get("fresh_service"):
            svc.close()
            self.fresh.clear()
            svc = SearchService(self.service_config())
            self.record_fresh(svc.batcher)
        self.svc = svc

    def warm_specs(self):
        mix = self.mix
        seen, out = set(), []
        for c in range(mix["clients"]):
            # Six consecutive requests of a client cover every dataflow and
            # objective it will ask for.
            for k in range(6):
                s = traffic.closed_request(mix, self.config, 10 ** 6, c, k)
                key = (s["method"], s["dataflow"], s["objective"])
                if key in seen:
                    continue
                seen.add(key)
                s["eps"] = mix["warmup"]["eps"][s["method"]]
                out.append(s)
        return out

    def window(self, seconds: float):
        mix = self.mix
        lock = threading.Lock()
        t_open = time.perf_counter()
        t_close = t_open + seconds
        deadline = t_close + mix.get("drain_s", 60)
        marks, tickets, answered = [], [], []

        def client(c):
            k = 0
            while time.perf_counter() < t_close:
                spec = traffic.closed_request(mix, self.config,
                                              self.ctx.seed, c, k)
                mark = [(time.perf_counter(), 0)]

                def on_progress(tr, mark=mark):
                    mark.append((time.perf_counter(), tr.step))

                t = self.svc.submit(self.request(spec, on_progress))
                with lock:
                    marks.append(mark)
                    tickets.append(t)
                    self.attempted += 1
                try:
                    out = t.result(
                        timeout=max(deadline - time.perf_counter(), 0.0))
                except Exception as e:  # noqa: BLE001 -- counted as failed
                    with lock:
                        self.failed += 1
                    self.ctx.log(f"search failed or unanswered by the "
                                 f"deadline: {e!r}")
                    return
                hist = out.extras.get("history")
                with lock:
                    answered.append(time.perf_counter())
                    self.outcomes.append(Outcome(
                        spec, out.best_value, out.pe, out.kt, out.df,
                        hist if isinstance(hist, dict) else None))
                k += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(mix["clients"])]
        for th in threads:
            th.start()
        time.sleep(max(t_close - time.perf_counter(), 0.0))
        self.ctx.closed()
        for th in threads:
            th.join(timeout=max(deadline - time.perf_counter(), 0.0) + 5.0)
        with lock:
            for t in tickets:
                if not t.done():
                    t.cancel()
        for th in threads:
            th.join(timeout=30.0)
        self.evals = work_in_window(marks, t_open, t_close)
        self.e2e["hard_evals_per_s"] = self.evals / seconds
        last = max(answered, default=t_close) - t_close
        first = [m[1][0] - m[0][0] for m in marks if len(m) > 1]
        self.ctx.log(f"window: {self.attempted} searches, "
                     f"{len(self.outcomes)} answered, the last "
                     f"{last:.3f} s after the close; "
                     f"{self.evals:.1f} evals inside; first progress "
                     f"{min(first, default=0):.3f}-{max(first, default=0):.3f}"
                     f" s after submission")

    def close(self):
        self.svc.close()
        self.svc = None


DRIVER = ServiceClosed

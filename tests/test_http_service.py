"""HTTP front door: wire parity, admission control, fairness, streaming.

The load-bearing guarantee carries over from the in-process service:
a search submitted over HTTP returns bit-identical history/assignment to
the same ``api.run_search`` call (JSON float round-tripping is exact).
The rest is what makes the front door operable -- bounded admission
(429 + Retry-After), per-tenant weighted round-robin so a backlog can't
starve an interactive probe, cancel over the wire for queued AND running
jobs, chunked JSONL progress, and per-tenant accounting in /v1/stats.
"""
import time

import numpy as np
import pytest

from repro import api
from repro.core import env as env_lib
from repro.serving import (HttpConfig, QueueFull, SearchClient,
                           SearchHTTPService, ServiceConfig)

ECFG = env_lib.EnvConfig(platform="cloud")


def _hub(max_workers=2, max_queue=8, max_running=None, weights=(),
         progress_every=200):
    return SearchHTTPService(
        service_cfg=ServiceConfig(max_workers=max_workers,
                                  default_progress_every=progress_every),
        http_cfg=HttpConfig(port=0, max_queue=max_queue,
                            max_running=max_running,
                            tenant_weights=weights,
                            progress_poll_s=0.01)).start()


def _wait(pred, timeout=120, step=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


# ---------------------------------------------------------------------------
# Wire parity.
# ---------------------------------------------------------------------------
def test_http_end_to_end_bit_identical_to_in_process():
    """Same fixed-seed search over the wire == api.run_search, bit for bit
    (history bytes, pe/kt assignment, best value)."""
    want = api.run_search(api.SearchRequest(
        workload="ncf", env=ECFG, eps=200, seed=3, method="random"))
    hub = _hub()
    try:
        client = SearchClient(port=hub.port)
        uid = client.submit({"workload": "ncf", "method": "random",
                             "eps": 200, "seed": 3})["uid"]
        out = client.result(uid, timeout=300)
        assert out["best_value"] == want.best_value
        got_hist = np.asarray(out["history"], want.history.dtype)
        assert got_hist.tobytes() == want.history.tobytes()
        np.testing.assert_array_equal(
            np.asarray(out["pe"], np.asarray(want.pe).dtype), want.pe)
        np.testing.assert_array_equal(
            np.asarray(out["kt"], np.asarray(want.kt).dtype), want.kt)
        assert out["method"] == "random" and out["seed"] == 3
    finally:
        hub.close()


def test_http_full_env_spec_and_options_pass_through():
    """objective/constraint/dataflow and leftover option keys survive the
    spec -> SearchRequest translation (same convention as serve_search)."""
    env2 = env_lib.EnvConfig(platform="cloud", objective="energy",
                             constraint="power")
    want = api.run_search(api.SearchRequest(
        workload="ncf", env=env2, eps=150, seed=2, method="ga",
        options={"population": 30}))
    hub = _hub()
    try:
        client = SearchClient(port=hub.port)
        uid = client.submit({"workload": "ncf", "method": "ga", "eps": 150,
                             "seed": 2, "objective": "energy",
                             "constraint": "power",
                             "population": 30})["uid"]
        out = client.result(uid, timeout=300)
        assert out["best_value"] == want.best_value
        got_hist = np.asarray(out["history"], want.history.dtype)
        assert got_hist.tobytes() == want.history.tobytes()
    finally:
        hub.close()


# ---------------------------------------------------------------------------
# Admission control.
# ---------------------------------------------------------------------------
def test_queue_full_returns_429_with_retry_after():
    hub = _hub(max_workers=1, max_queue=1, max_running=1)
    try:
        client = SearchClient(port=hub.port)
        running = client.submit({"workload": "ncf", "method": "reinforce",
                                 "eps": 10_000_000})
        # Wait until the scheduler moved it out of the admission queue.
        assert _wait(lambda: hub.front.stats()["running"] == 1
                     and hub.front.stats()["queued"] == 0)
        queued = client.submit({"workload": "ncf", "method": "random",
                                "eps": 100})
        assert hub.front.stats()["queued"] == 1      # queue now full
        status, headers, _ = client._request(
            "POST", "/v1/search",
            {"workload": "ncf", "method": "random", "eps": 100})
        assert status == 429
        assert float(headers["Retry-After"]) > 0
        with pytest.raises(QueueFull):               # client-side surface
            client.submit({"workload": "ncf", "method": "random",
                           "eps": 100})
        st = hub.front.stats()
        assert st["rejected"] == 2
        assert st["tenants"]["anon"]["rejected"] == 2
        client.cancel(queued["uid"])
        client.cancel(running["uid"])
    finally:
        hub.close()


def test_bad_request_body_is_400_not_500():
    hub = _hub()
    try:
        client = SearchClient(port=hub.port)
        status, _, data = client._request("POST", "/v1/search",
                                          {"method": "random"})  # no workload
        assert status == 400 and b"workload" in data
        status, _, _ = client._request("GET", "/v1/search/nope")
        assert status == 404
        status, _, _ = client._request("DELETE", "/v1/search/nope")
        assert status == 404
        status, _, _ = client._request("GET", "/no/such/route")
        assert status == 404
    finally:
        hub.close()


# ---------------------------------------------------------------------------
# Cancellation over the wire.
# ---------------------------------------------------------------------------
def test_cancel_over_wire_running_and_queued():
    hub = _hub(max_workers=1, max_queue=8, max_running=1,
               progress_every=50)
    try:
        client = SearchClient(port=hub.port)
        running = client.submit({"workload": "ncf", "method": "reinforce",
                                 "eps": 10_000_000})["uid"]
        assert _wait(lambda: client.status(running)["status"] == "running")
        queued = client.submit({"workload": "ncf", "method": "random",
                                "eps": 100})["uid"]
        # Queued cancel resolves while the worker is still busy.
        client.cancel(queued)
        assert _wait(lambda: client.status(queued)["status"] == "cancelled",
                     timeout=5)
        assert client.status(running)["status"] == "running"
        client.cancel(running)
        assert _wait(lambda: client.status(running)["status"] == "cancelled")
        with pytest.raises(RuntimeError, match="cancelled"):
            client.result(queued, timeout=5)
        st = client.stats()["front_door"]["tenants"]["anon"]
        assert st["cancelled"] == 2 and st["completed"] == 0
    finally:
        hub.close()


# ---------------------------------------------------------------------------
# Progress streaming.
# ---------------------------------------------------------------------------
def test_progress_stream_is_incremental_jsonl():
    hub = _hub(max_workers=1, progress_every=25)
    try:
        client = SearchClient(port=hub.port)
        uid = client.submit({"workload": "ncf", "method": "reinforce",
                             "eps": 100})["uid"]
        recs = list(client.progress(uid))
        assert recs[-1]["done"] is True
        assert recs[-1]["status"] == "done"
        trials = recs[:-1]
        assert len(trials) >= 3                      # 25-step cadence
        steps = [r["step"] for r in trials]
        assert steps == sorted(steps) and steps[-1] == 100
        assert all(np.isfinite(r["best_value"]) or r["best_value"] == float(
            "inf") for r in trials)
    finally:
        hub.close()


# ---------------------------------------------------------------------------
# Tenant fairness + accounting.
# ---------------------------------------------------------------------------
def test_wrr_interactive_tenant_not_starved_by_backlog():
    """One running slot, tenant A floods 4 jobs, tenant B submits 1: WRR
    must schedule B's single job ahead of A's backlog tail."""
    hub = _hub(max_workers=1, max_queue=16, max_running=1)
    try:
        client = SearchClient(port=hub.port)
        a = [client.submit({"workload": "ncf", "method": "random",
                            "eps": 600, "seed": s, "tenant": "batch"})["uid"]
             for s in range(4)]
        b = client.submit({"workload": "ncf", "method": "random",
                           "eps": 300, "seed": 9,
                           "tenant": "interactive"})["uid"]
        for uid in a + [b]:
            client.result(uid, timeout=300)
        jobs = {uid: hub.front.get(uid) for uid in a + [b]}
        # B entered the rotation after at most one A job from the backlog:
        # it must have finished before A's last two.
        assert jobs[b].finished_at < jobs[a[2]].finished_at
        assert jobs[b].finished_at < jobs[a[3]].finished_at

        tenants = client.stats()["front_door"]["tenants"]
        assert tenants["batch"]["submitted"] == 4
        assert tenants["batch"]["completed"] == 4
        assert tenants["batch"]["eps_requested"] == 4 * 600
        assert tenants["batch"]["eps_finished"] == 4 * 600
        assert tenants["interactive"]["completed"] == 1
        assert tenants["interactive"]["eps_finished"] == 300
    finally:
        hub.close()


def test_stats_and_metrics_endpoints():
    hub = _hub()
    try:
        client = SearchClient(port=hub.port)
        uid = client.submit({"workload": "ncf", "method": "random",
                             "eps": 60, "tenant": "t0"})["uid"]
        client.result(uid, timeout=300)
        st = client.stats()
        assert st["service"]["completed"] == 1
        assert st["front_door"]["tenants"]["t0"]["completed"] == 1
        assert st["front_door"]["max_queue"] == 8
        text = client.metrics_text()
        # The registry's exposition is served whole -- the front-door
        # metrics are registered (samples only accrue while obs is on).
        assert "# TYPE repro_http_requests counter" in text
        assert "# TYPE repro_service_requests counter" in text
    finally:
        hub.close()


def test_http_metrics_accrue_when_telemetry_enabled():
    from repro import obs
    from repro.obs import instrument

    obs.enable()
    try:
        hub = _hub()
        try:
            client = SearchClient(port=hub.port)
            before = instrument.HTTP_REQUESTS.value(route="/v1/stats",
                                                    code="200")
            client.stats()
            client.stats()
            # The handler counts a request after its response is written,
            # so the client can read the counter before the server thread
            # has counted the second call: wait for it, then compare.
            deadline = time.monotonic() + 5.0
            while (instrument.HTTP_REQUESTS.value(route="/v1/stats",
                                                  code="200") < before + 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert instrument.HTTP_REQUESTS.value(
                route="/v1/stats", code="200") == before + 2
            text = client.metrics_text()
            assert "repro_http_requests_total{" in text
            assert 'route="/v1/stats"' in text
        finally:
            hub.close()
    finally:
        obs.disable()

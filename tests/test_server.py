"""HTTP front door (`repro.service.server`) end-to-end tests.

Everything except the OS-process fleet test runs against an in-process
ephemeral-port server backed by stub engines, so the protocol paths (spec
round-trip, SSE ordering, remote store read-through, error handling,
graceful shutdown) are exercised without JAX work and cannot flake on
compile timing.  The `slow`-marked fleet test is the acceptance check:
separate OS processes against one `repro-service serve`, with the warm
repeat answered from the shared store and asserted via `/v1/stats`.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from test_service import SMALL, CountingStubEngine, _job

from repro.core import ExploreJob, bert_large_workload, job_key
from repro.core.macro import TPDCIM_MACRO
from repro.service import (
    ResultStore,
    ServiceClient,
    job_from_spec,
    job_to_spec,
    settings_from_spec,
)
from repro.service.client import _read_sse
from repro.service.server import DSEServer, ServerConfig
from repro.service.streams import as_completed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _server(tmp_path, engine=None, store="unset", **cfg) -> DSEServer:
    if store == "unset":
        store = ResultStore(str(tmp_path / "server-store"))
    config = ServerConfig(port=0, stream_ping_s=0.2, **cfg)
    return DSEServer(engine=engine or CountingStubEngine(),
                     store=store, config=config).start()


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.loads(resp.read().decode())


def _post_json(url: str, payload) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read().decode())


# ------------------------------------------------------------------ #
# spec round-trip + status endpoints
# ------------------------------------------------------------------ #
def test_failed_profile_warmup_makes_healthz_unhealthy(tmp_path,
                                                       monkeypatch):
    from repro import obs

    def broken(*a, **kw):
        raise RuntimeError("kernel refused")

    srv = _server(tmp_path)
    try:
        assert _get_json(f"{srv.url}/healthz")["ok"] is True
        monkeypatch.setattr(obs.profile, "run_microbench", broken)
        srv._profile_warmup()
        with pytest.raises(urllib.error.HTTPError) as err:
            _get_json(f"{srv.url}/healthz")
        assert err.value.code == 503
        body = json.loads(err.value.read().decode())
        assert body["ok"] is False and "kernel refused" in body["error"]
    finally:
        srv.shutdown()


def test_post_jobs_roundtrip_including_portfolio(tmp_path):
    srv = _server(tmp_path)
    try:
        specs = [
            {"macro": "tpdcim-macro", "workload": "bert-large",
             "area_budget_mm2": 2.23, "objective": "ee",
             "search": "exhaustive",
             "space": {"mr": [1, 2], "mc": [1, 2], "scr": [1, 4],
                       "is_kb": [2, 16], "os_kb": [2, 16]}},
            {"macro": "tpdcim-macro", "workload": "bert-large",
             "area_budget_mm2": 2.23, "objective": "th",
             "search": "portfolio",
             "space": {"mr": [1, 2], "mc": [1, 2], "scr": [1, 4],
                       "is_kb": [2, 16], "os_kb": [2, 16]}},
        ]
        out = _post_json(f"{srv.url}/v1/jobs?wait=30", specs)
        assert [s["status"] for s in out["jobs"]] == ["done", "done"]
        # the server's canonical keys must equal a client's local
        # computation -- cross-host store sharing hinges on this parity
        for spec, state in zip(specs, out["jobs"]):
            job, method = job_from_spec(spec)
            from repro.service.queue import resolve_settings
            assert state["key"] == job_key(
                job, method, resolve_settings(method))
            assert state["result"]["workload"] == "bert-large"
        # status endpoint serves the same record
        key = out["jobs"][0]["key"]
        state = _get_json(f"{srv.url}/v1/jobs/{key}")
        assert state["status"] == "done"
        assert state["result"]["objective"] == "ee"
    finally:
        srv.shutdown()


def test_inline_job_spec_roundtrip_preserves_key():
    """job_to_spec -> JSON -> job_from_spec keeps the canonical job_key
    bit-for-bit for arbitrary in-memory jobs (custom space, workload)."""
    job = ExploreJob(TPDCIM_MACRO, bert_large_workload(384), 1.75,
                     objective="th", strategy_set="so", bw=128, space=SMALL,
                     merge_ops=False, search_method="genetic")
    wire = json.loads(json.dumps(job_to_spec(job)))
    back, method = job_from_spec(wire)
    assert method == "genetic"
    from repro.service.queue import resolve_settings
    assert job_key(back, method, resolve_settings(method)) == \
        job_key(job, "genetic", resolve_settings("genetic"))


def test_spec_settings_parse_and_reject_unknown_fields():
    from repro.search.genetic import GASettings
    got = settings_from_spec("genetic", {"pop": 8, "generations": 5})
    assert got == GASettings(pop=8, generations=5)
    with pytest.raises(ValueError, match="unknown GASettings fields"):
        settings_from_spec("genetic", {"population": 8})
    assert settings_from_spec("exhaustive", {"x": 1}) is None


# ------------------------------------------------------------------ #
# SSE streaming: per-bucket completion order mirrors as_completed
# ------------------------------------------------------------------ #
def test_sse_stream_order_matches_as_completed(tmp_path):
    from repro.configs import get_arch
    fast_wl = bert_large_workload()
    slow_wl = get_arch("whisper-small").workload(seq=512)
    eng = CountingStubEngine()
    eng.block_buckets = {eng.bucket_key(
        ExploreJob(TPDCIM_MACRO, slow_wl, 2.23, space=SMALL), "exhaustive")}
    srv = _server(tmp_path, engine=eng)
    try:
        # fast bucket first: the queue dispatches groups in (priority,
        # arrival) order and the stub holds the slow bucket open
        specs = [job_to_spec(_job(wl=fast_wl), "exhaustive"),
                 job_to_spec(_job(wl=slow_wl), "exhaustive")]
        out = _post_json(f"{srv.url}/v1/jobs", specs)
        fast_key, slow_key = (s["key"] for s in out["jobs"])
        url = f"{srv.url}/v1/stream?keys={slow_key},{fast_key}&timeout=30"
        events = []
        with urllib.request.urlopen(url, timeout=60) as resp:
            it = _read_sse(resp)
            event, obj = next(it)
            events.append((event, obj))
            # fast bucket streamed while the slow bucket is still held
            assert obj["key"] == fast_key
            eng.release.set()
            for event, obj in it:
                events.append((event, obj))
        assert [e for e, _ in events] == ["result", "result", "end"]
        assert events[1][1]["key"] == slow_key
        assert events[1][1]["status"] == "done"
    finally:
        eng.release.set()
        srv.shutdown()


def test_remote_client_streams_in_completion_order(tmp_path):
    from repro.configs import get_arch
    fast_wl = bert_large_workload()
    slow_wl = get_arch("whisper-small").workload(seq=512)
    eng = CountingStubEngine()
    eng.block_buckets = {eng.bucket_key(
        ExploreJob(TPDCIM_MACRO, slow_wl, 2.23, space=SMALL), "exhaustive")}
    srv = _server(tmp_path, engine=eng)
    cli = ServiceClient(base_url=srv.url, store=None)
    try:
        futs = cli.submit_many([_job(wl=fast_wl), _job(wl=slow_wl)],
                               method="exhaustive", metas=["fast", "slow"])
        stream = as_completed(futs, timeout=30)
        first = next(stream)
        assert first.meta == "fast"
        assert not futs[1].done()
        eng.release.set()
        assert next(stream).meta == "slow"
        assert futs[1].result(timeout=30).workload == slow_wl.name
    finally:
        eng.release.set()
        cli.close()
        srv.shutdown()


# ------------------------------------------------------------------ #
# shared-store semantics (the acceptance criterion, stub-engine tier)
# ------------------------------------------------------------------ #
def test_identical_resubmission_answered_from_shared_store(tmp_path):
    """Client A computes; client B (separate ServiceClient, cold local
    tier) resubmits the identical job and must be answered from the
    server's store with zero additional engine runs -- asserted via
    /v1/stats like the CI fleet job."""
    eng = CountingStubEngine()
    srv = _server(tmp_path, engine=eng)
    try:
        a = ServiceClient(base_url=srv.url, store=None)
        cold = a.explore([_job()], method="exhaustive")[0]
        assert eng.runs == 1
        a.close()

        b = ServiceClient(base_url=srv.url, store=None)
        warm = b.explore([_job()], method="exhaustive")[0]
        b.close()
        assert eng.runs == 1, "repeat must not reach the engine"
        assert warm.config.as_tuple() == cold.config.as_tuple()
        assert warm.search["cache"] == "remote-store"

        stats = _get_json(f"{srv.url}/v1/stats")
        assert stats["server"]["store_get_hits"] >= 1
        assert stats["store"]["hits"] >= 1
        assert stats["queue"]["dispatches"] == 1
    finally:
        srv.shutdown()


def test_remote_store_read_through_warms_local_tier(tmp_path):
    eng = CountingStubEngine()
    srv = _server(tmp_path, engine=eng)
    local = ResultStore(str(tmp_path / "client-store"))
    try:
        seed = ServiceClient(base_url=srv.url, store=None)
        seed.explore([_job()], method="exhaustive")
        seed.close()

        cli = ServiceClient(base_url=srv.url, store=local)
        got = cli.explore([_job()], method="exhaustive")[0]
        assert got.search["cache"] == "remote-store"
        assert cli.queue.store.stats["remote_hits"] == 1
        # the read-through wrote the record locally: a second query is
        # answered without any HTTP traffic at all
        before = srv.http_stats["requests"]
        again = cli.explore([_job()], method="exhaustive")[0]
        assert again.search["cache"] == "store"
        assert cli.queue.store.stats["local_hits"] == 1
        assert srv.http_stats["requests"] == before
        cli.close()
    finally:
        srv.shutdown()


def test_remote_values_submission(tmp_path):
    srv = _server(tmp_path)
    cli = ServiceClient(base_url=srv.url, store=None)
    try:
        rows = np.tile(np.asarray([1, 1, 1, 2, 2, 256], np.float64), (5, 1))
        fut = cli.submit_values(_job(), rows)
        vals = fut.result(timeout=30)
        np.testing.assert_allclose(vals, np.arange(5, dtype=float) + 1.0)
    finally:
        cli.close()
        srv.shutdown()


def test_stream_timeout_fails_pending_futures_instead_of_hanging(tmp_path):
    """When the server's stream ends (timeout event) before a bucket
    resolves, the remote client must fail the futures -- tagged with
    their job keys -- not leave callers blocked forever."""
    from repro.service.client import RemoteQueue
    eng = CountingStubEngine()
    eng.block_buckets = {eng.bucket_key(_job(), "exhaustive")}
    srv = _server(tmp_path, engine=eng)
    q = RemoteQueue(srv.url, store=None, timeout_s=0.5)
    try:
        fut = q.submit(_job(), method="exhaustive")
        exc = fut.exception(timeout=30)
        assert exc is not None
        assert fut.key[:16] in str(exc)
        assert exc.job_key == fut.key
    finally:
        eng.release.set()
        q.close()
        srv.shutdown()


def test_registry_eviction_never_drops_pending_futures(tmp_path):
    """With a tiny registry cap and every entry still running, eviction
    must overrun rather than make running work unreachable -- /v1/stream
    on both keys must succeed once released."""
    from repro.configs import get_arch
    eng = CountingStubEngine()
    eng.block_buckets = {
        eng.bucket_key(_job(), "exhaustive"),
        eng.bucket_key(_job(wl=get_arch("whisper-small").workload(seq=512)),
                       "exhaustive")}
    srv = _server(tmp_path, engine=eng, registry_cap=1)
    try:
        specs = [job_to_spec(_job(), "exhaustive"),
                 job_to_spec(_job(wl=get_arch("whisper-small")
                                  .workload(seq=512)), "exhaustive")]
        out = _post_json(f"{srv.url}/v1/jobs", specs)
        keys = [s["key"] for s in out["jobs"]]
        eng.release.set()
        url = f"{srv.url}/v1/stream?keys={','.join(keys)}&timeout=30"
        with urllib.request.urlopen(url, timeout=60) as resp:
            got = {obj.get("key") for event, obj in _read_sse(resp)
                   if event == "result"}
        assert got == set(keys)
    finally:
        eng.release.set()
        srv.shutdown()


# ------------------------------------------------------------------ #
# malformed requests
# ------------------------------------------------------------------ #
def _status_of(url: str, payload=None) -> int:
    try:
        if payload is None:
            urllib.request.urlopen(url, timeout=30)
        else:
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json"},
                method="POST")
            urllib.request.urlopen(req, timeout=30)
    except urllib.error.HTTPError as exc:
        return exc.code
    return 200


def test_malformed_requests_get_400s(tmp_path):
    srv = _server(tmp_path)
    try:
        jobs = f"{srv.url}/v1/jobs"
        assert _status_of(jobs, b"{not json") == 400
        assert _status_of(jobs, b"[]") == 400
        assert _status_of(jobs, b'["not-a-spec"]') == 400
        assert _status_of(jobs, json.dumps(
            [{"workload": "bert-large", "area_budget_mm2": 1}]
        ).encode()) == 400                              # missing macro
        assert _status_of(jobs, json.dumps(
            [{"macro": "tpdcim-macro", "workload": "bert-large",
              "area_budget_mm2": 1, "search": "nope"}]).encode()) == 400
        bad_cands = {"macro": "tpdcim-macro", "workload": "bert-large",
                     "area_budget_mm2": 1, "candidates": [[1, 2, 3]]}
        assert _status_of(jobs, json.dumps([bad_cands]).encode()) == 400
        # one bad spec poisons nothing: the whole batch is rejected and
        # nothing was admitted
        assert _get_json(f"{srv.url}/v1/stats")["queue"]["submitted"] == 0
        assert _status_of(f"{srv.url}/v1/stream") == 400
        assert _status_of(f"{srv.url}/v1/stream?keys=deadbeef") == 404
        assert _status_of(f"{srv.url}/v1/jobs/deadbeef") == 404
        assert _status_of(f"{srv.url}/v1/store/deadbeef") == 404
        assert _status_of(f"{srv.url}/nope") == 404
        assert _get_json(f"{srv.url}/v1/stats")["server"]["bad_requests"] > 0
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ #
# graceful shutdown
# ------------------------------------------------------------------ #
def test_graceful_shutdown_drains_inflight_buckets(tmp_path):
    eng = CountingStubEngine()
    eng.block_buckets = {eng.bucket_key(_job(), "exhaustive")}
    store = ResultStore(str(tmp_path / "server-store"))
    srv = _server(tmp_path, engine=eng, store=store)
    out = _post_json(f"{srv.url}/v1/jobs", [job_to_spec(_job(),
                                                        "exhaustive")])
    key = out["jobs"][0]["key"]
    assert out["jobs"][0]["status"] == "pending"

    done = threading.Event()
    threading.Thread(target=lambda: (srv.shutdown(drain=True),
                                     done.set()), daemon=True).start()
    time.sleep(0.1)
    assert not done.is_set(), "shutdown must wait for the held bucket"
    eng.release.set()
    assert done.wait(30), "drain never completed"
    # the accepted job's result was evaluated and persisted on the way out
    assert store.get(key) is not None


# ------------------------------------------------------------------ #
# pareto SSE endpoint (stub candidate sweep)
# ------------------------------------------------------------------ #
def test_pareto_endpoint_streams_frontiers(tmp_path):
    srv = _server(tmp_path)
    try:
        url = (f"{srv.url}/v1/pareto?macro=tpdcim-macro"
               f"&workloads=bert-large&area_budget_mm2=2.23&timeout=30")
        events = []
        with urllib.request.urlopen(url, timeout=60) as resp:
            for event, obj in _read_sse(resp):
                events.append((event, obj))
        assert [e for e, _ in events] == ["frontier", "end"]
        front = events[0][1]
        assert front["workload"] == "bert-large"
        assert front["frontier"], "stub sweep must yield frontier points"
        assert {"config", "gops", "tops_w"} <= set(front["frontier"][0])
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ #
# acceptance: separate OS processes sharing one serve instance
# ------------------------------------------------------------------ #
@pytest.mark.slow
def test_fleet_of_processes_shares_one_server(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["CIM_TUNER_RESULT_STORE"] = str(tmp_path / "server-store")
    env.pop("CIM_TUNER_SERVICE_URL", None)

    specs = [
        {"macro": "tpdcim-macro", "workload": "bert-large",
         "area_budget_mm2": 2.23, "objective": obj, "search": "exhaustive",
         "space": {"mr": [1, 2], "mc": [1, 2], "scr": [1, 4],
                   "is_kb": [16, 128], "os_kb": [16, 64]}}
        for obj in ("ee", "th")
    ]
    jobs_file = tmp_path / "jobs.json"
    jobs_file.write_text(json.dumps(specs))
    port_file = tmp_path / "port.txt"

    server = subprocess.Popen(
        [sys.executable, "-m", "repro.service", "serve", "--port", "0",
         "--port-file", str(port_file)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO)
    try:
        deadline = time.monotonic() + 120
        while not port_file.exists():
            assert server.poll() is None, \
                f"server died early:\n{server.stdout.read()}"
            assert time.monotonic() < deadline, "server never bound a port"
            time.sleep(0.2)
        url = f"http://127.0.0.1:{port_file.read_text().strip()}"
        assert _get_json(f"{url}/healthz")["ok"] is True

        def client(tag: str, extra: list[str]) -> subprocess.Popen:
            cenv = dict(env)
            cenv["CIM_TUNER_RESULT_STORE"] = str(tmp_path / f"{tag}-store")
            cenv["CIM_TUNER_SERVICE_URL"] = url
            return subprocess.Popen(
                [sys.executable, "-m", "repro.service", "explore",
                 str(jobs_file), *extra],
                env=cenv, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True, cwd=REPO)

        # two concurrent client processes against the one server
        a = client("client-a", ["--stream"])
        b = client("client-b", [])
        out_a, _ = a.communicate(timeout=600)
        out_b, _ = b.communicate(timeout=600)
        assert a.returncode == 0, f"client A failed:\n{out_a}"
        assert b.returncode == 0, f"client B failed:\n{out_b}"
        assert out_a.count("bert-large") >= 2, out_a

        # third process resubmits the identical file: answered from the
        # shared store without another engine run
        before = _get_json(f"{url}/v1/stats")
        c = client("client-c", [])
        out_c, _ = c.communicate(timeout=600)
        assert c.returncode == 0, f"client C failed:\n{out_c}"
        after = _get_json(f"{url}/v1/stats")
        assert after["store"]["hits"] > before["store"]["hits"], \
            "warm repeat must be served by the shared store"
        assert after["queue"]["dispatches"] == before["queue"]["dispatches"], \
            "warm repeat must not dispatch new engine work"

        server.terminate()                              # SIGTERM: graceful
        out_s, _ = server.communicate(timeout=60)
        assert server.returncode == 0, f"server exit nonzero:\n{out_s}"
        assert "draining" in out_s
    finally:
        if server.poll() is None:
            server.kill()

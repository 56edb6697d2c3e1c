"""Telemetry subsystem (`repro.obs`) tests.

Registry semantics, the Prometheus text contract (round-tripped through
``tools/check_metrics.py`` -- the same parser the CI fleet smoke uses),
the ``StatCounters`` migration facade, span tracing + the Chrome
trace_event export (including the ``repro-service trace`` CLI), the
logging selectors, the progress bus, SSE ``progress`` interleaving, and
the HTTP surface under concurrent load.  The unit tests build their own
``Registry`` / ``Tracer`` / ``ProgressBus`` instances; only the
server-level tests touch the process-wide registry, and those assert
deltas / monotonicity, never absolute values.
"""
from __future__ import annotations

import importlib.util
import json
import logging
import os
import subprocess
import sys
import threading
import urllib.request

import pytest
from test_server import _get_json, _post_json, _server
from test_service import SMALL, CountingStubEngine, _job

from repro import obs
from repro.obs.events import ProgressBus
from repro.obs.log import _parse_spec, configure_logging
from repro.obs.metrics import Registry, StatCounters
from repro.obs.trace import Tracer
from repro.service import job_to_spec
from repro.service.client import _read_sse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name: str):
    """Import a script from tools/ (not a package) by file path."""
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_metrics = _load_tool("check_metrics")


# ------------------------------------------------------------------ #
# registry: instrument semantics
# ------------------------------------------------------------------ #
def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("t_jobs_total", "jobs", ("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3
    assert c.value(kind="b") == 1
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, kind="a")
    with pytest.raises(ValueError, match="expects labels"):
        c.inc(wrong="a")

    g = reg.gauge("t_depth", "depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6

    h = reg.histogram("t_latency_seconds", "latency",
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    child = h.labels()
    assert child.snapshot() == (55.55, 4)
    # cumulative over (0.1, 1.0, 10.0, +Inf): one value per band
    assert child.cumulative() == [1, 2, 3, 4]


def test_registry_registration_idempotent_and_type_checked():
    reg = Registry()
    a = reg.counter("t_total", "help", ("x",))
    assert reg.counter("t_total", "other help", ("x",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t_total", "help", ("x",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("t_total", "help", ("y",))
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("0bad", "help")
    with pytest.raises(ValueError, match="bad label name"):
        reg.counter("t_ok_total", "help", ("le gume",))


def test_snapshot_flattens_histograms_to_sum_and_count():
    reg = Registry()
    reg.counter("t_a_total", "a").inc(3)
    reg.histogram("t_h_seconds", "h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["t_a_total"] == 3
    assert snap["t_h_seconds_sum"] == 0.5
    assert snap["t_h_seconds_count"] == 1
    assert not any("_bucket" in k for k in snap)


# ------------------------------------------------------------------ #
# the Prometheus text contract, via the CI gate's own parser
# ------------------------------------------------------------------ #
def test_render_roundtrips_through_check_metrics():
    reg = Registry()
    reg.counter("t_reqs_total", "requests", ("route", "method")) \
       .inc(4, route="/v1/jobs/{key}", method="GET")
    reg.gauge("t_depth", "queue depth", ("state",)).set(7, state="pending")
    reg.histogram("t_wait_seconds", "wait", buckets=(0.01, 0.1)) \
       .observe(0.05)
    # label values with every escaped character must survive the wire
    reg.counter("t_esc_total", "escaping", ("v",)) \
       .inc(v='quote " back \\ newline \n done')

    families = check_metrics.parse(reg.render())
    assert set(families) == {"t_reqs_total", "t_depth", "t_wait_seconds",
                             "t_esc_total"}
    assert families["t_reqs_total"]["type"] == "counter"
    assert families["t_depth"]["type"] == "gauge"
    assert families["t_wait_seconds"]["type"] == "histogram"
    assert check_metrics.family_total(families, "t_reqs_total") == 4
    assert check_metrics.family_total(families, "t_wait_seconds") == 1
    # the histogram emitted the full _bucket/_sum/_count series incl +Inf
    names = set(families["t_wait_seconds"]["samples"])
    assert any(name.startswith("t_wait_seconds_bucket") and "+Inf" in name
               for name in names)
    assert any(name.startswith("t_wait_seconds_sum") for name in names)


def test_check_metrics_rejects_malformed_exposition():
    with pytest.raises(ValueError):
        check_metrics.parse("t_x_total 1\n")       # sample without a TYPE
    with pytest.raises(ValueError):
        check_metrics.parse("# TYPE t_x_total counter\nt_x_total one\n")


def test_check_metrics_validates_histogram_self_consistency():
    good = (
        "# TYPE t_h histogram\n"
        't_h_bucket{k="a",le="1"} 1\n'
        't_h_bucket{k="a",le="+Inf"} 2\n'
        't_h_sum{k="a"} 3.5\n'
        't_h_count{k="a"} 2\n')
    assert check_metrics.histogram_errors(check_metrics.parse(good)) == []
    # +Inf bucket disagreeing with _count
    bad = good.replace('t_h_count{k="a"} 2', 't_h_count{k="a"} 3')
    errs = check_metrics.histogram_errors(check_metrics.parse(bad))
    assert any("+Inf bucket" in e and "_count" in e for e in errs), errs
    # cumulative counts must be monotone non-decreasing in le
    bad = good.replace('le="+Inf"} 2', 'le="+Inf"} 0')
    errs = check_metrics.histogram_errors(check_metrics.parse(bad))
    assert any("monotone" in e for e in errs), errs
    # a bucket series with no +Inf at all
    errs = check_metrics.histogram_errors(check_metrics.parse(
        "# TYPE t_h histogram\n"
        't_h_bucket{k="a",le="1"} 1\n'
        't_h_sum{k="a"} 1\nt_h_count{k="a"} 1\n'))
    assert any("+Inf" in e for e in errs), errs


def test_exemplars_render_gated_and_parse_with_span_ids(monkeypatch):
    monkeypatch.setenv("CIM_TUNER_EXEMPLARS", "1")
    reg = Registry()
    h = reg.histogram("t_ex_seconds", "x", ("k",), buckets=(0.1, 1.0))
    tr = Tracer(capacity=8)
    with tr.span("unit.ex", histogram=h.labels(k="a")):
        pass
    text = reg.render()
    assert " # {span_id=" in text
    families = check_metrics.parse(text)
    assert check_metrics.histogram_errors(families) == []
    span_ids = check_metrics.exemplar_span_ids(families)
    ev = tr.events()[-1]
    assert span_ids == {ev["id"]}, "exemplar must link the span's id"
    # the trace-json cross-check accepts the matching export...
    ex = families["t_ex_seconds"]["exemplars"]
    assert list(ex.values())[0]["value"] == pytest.approx(
        ev["dur"] / 1e6, rel=1e-2)
    # ...and the env gate strips the suffixes entirely
    monkeypatch.setenv("CIM_TUNER_EXEMPLARS", "0")
    off = reg.render()
    assert "span_id" not in off
    assert not any(rec["exemplars"]
                   for rec in check_metrics.parse(off).values())


def test_span_ids_are_unique_and_foreign_histograms_still_observe():
    tr = Tracer(capacity=8)
    h = Registry().histogram("t_plain_seconds", "x", buckets=(1.0,))

    class _Plain:                 # a histogram without exemplar support
        calls = 0

        def observe(self, value, exemplar=None):
            if exemplar is not None:
                raise TypeError("no exemplars here")
            _Plain.calls += 1

    with tr.span("unit.a", histogram=h.labels()):
        pass
    with tr.span("unit.b", histogram=_Plain()):
        pass
    ids = [e["id"] for e in tr.events()]
    assert len(set(ids)) == 2, ids
    assert _Plain.calls == 1, "TypeError fallback must re-observe"


def test_check_metrics_catalog_drift_both_directions(tmp_path):
    md = ("| family | type |\n|---|---|\n"
          "| `cim_present_total` | counter |\n"
          "| `cim_ghost_total` | counter |\n")
    text = ("# TYPE cim_present_total counter\ncim_present_total 1\n"
            "# TYPE cim_extra_total counter\ncim_extra_total 1\n")
    errs = check_metrics.catalog_drift(check_metrics.parse(text), md)
    assert any("cim_extra_total" in e and "missing from the docs" in e
               for e in errs)
    assert any("cim_ghost_total" in e and "absent from the scrape" in e
               for e in errs)
    # the CLI wires it all together, including the trace cross-check
    prom = tmp_path / "m.prom"
    prom.write_text(
        "# TYPE cim_present_total counter\ncim_present_total 1\n"
        "# TYPE t_h histogram\n"
        't_h_bucket{le="+Inf"} 1 # {span_id="77-1"} 0.5 1.0\n'
        "t_h_sum 0.5\nt_h_count 1\n")
    cat = tmp_path / "cat.md"
    cat.write_text("| `cim_present_total` | counter |\n")
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [{"id": "77-1"}]}))
    rc = check_metrics.main([str(prom), "--require-exemplars", "t_h",
                             "--catalog", str(cat),
                             "--trace-json", str(trace)])
    assert rc == 0
    trace.write_text(json.dumps({"traceEvents": [{"id": "other"}]}))
    assert check_metrics.main([str(prom), "--trace-json",
                               str(trace)]) == 1
    assert check_metrics.main([str(prom), "--require-exemplars",
                               "cim_present_total"]) == 1


def test_check_dashboard_catches_undocumented_metrics(tmp_path):
    check_dashboard = _load_tool("check_dashboard")
    # the shipped dashboard must pass against the shipped catalog
    assert check_dashboard.main([]) == 0
    board = {"panels": [
        {"id": 1, "title": "outer", "targets": [
            {"expr": "rate(cim_real_total[5m])"}],
         "panels": [{"id": 2, "title": "nested", "targets": [
             {"expr": "histogram_quantile(0.9, cim_fake_seconds_bucket)"
              }]}]}]}
    path = tmp_path / "board.json"
    path.write_text(json.dumps(board))
    cat = tmp_path / "cat.md"
    cat.write_text("| `cim_real_total` | counter |\n")
    refs = check_dashboard.dashboard_families(board)
    assert set(refs) == {"cim_real_total", "cim_fake_seconds"}
    assert check_dashboard.main(["--dashboard", str(path),
                                 "--catalog", str(cat)]) == 1
    cat.write_text("| `cim_real_total` | counter |\n"
                   "| `cim_fake_seconds` | histogram |\n")
    assert check_dashboard.main(["--dashboard", str(path),
                                 "--catalog", str(cat)]) == 0


# ------------------------------------------------------------------ #
# kernel profiling hooks
# ------------------------------------------------------------------ #
def test_profile_gate_roofline_and_instrument(monkeypatch):
    from repro.obs import profile

    monkeypatch.delenv("CIM_TUNER_PROFILE", raising=False)
    assert not profile.profiling_enabled()
    monkeypatch.setenv("CIM_TUNER_PROFILE", "1")
    assert profile.profiling_enabled()

    # roofline: attainable is min(peak compute, bw * intensity)
    peaks = profile.DevicePeaks(flops=100.0, bw=10.0, source="test")
    # intensity 1 flop/byte -> bw-bound at 10 FLOP/s; achieving 5 = 50%
    assert profile.roofline_utilization(5, 5, 1.0, peaks) \
        == pytest.approx(0.5)
    # huge intensity -> compute-bound at 100 FLOP/s
    assert profile.roofline_utilization(100, 0.001, 1.0, peaks) \
        == pytest.approx(1.0)
    assert profile.roofline_utilization(0, 0, 1.0, peaks) == 0.0
    assert profile.roofline_utilization(1, 1, 0.0, peaks) == 0.0

    calls = []
    wrapped = profile.instrument(
        "t_kernel", lambda x: calls.append(x) or x * 2,
        lambda x: f"b{x}")
    monkeypatch.delenv("CIM_TUNER_PROFILE", raising=False)
    assert wrapped(3) == 6                 # off: plain passthrough
    monkeypatch.setenv("CIM_TUNER_PROFILE", "1")
    before = profile._M_US.labels(kernel="t_kernel", bucket="b4") \
        .snapshot()[1]
    assert wrapped(4) == 8                 # on: observed into cim_kernel_us
    after = profile._M_US.labels(kernel="t_kernel", bucket="b4") \
        .snapshot()[1]
    assert after == before + 1
    assert calls == [3, 4]
    rows = [r for r in profile.summary() if r["kernel"] == "t_kernel"]
    assert rows and rows[0]["bucket"] == "b4" \
        and rows[0]["us_per_call"] > 0


def test_device_peaks_table_rejects_unknown_kinds():
    from repro.obs import profile

    v5e = profile.device_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.bw) == (197e12, 819e9) and v5e.source
    with pytest.raises(profile.UnknownDeviceError, match="cpu"):
        profile.device_peaks("cpu")
    # the CPU the tests run on has no entry: no roofline gauge, and a
    # records summary reports the utilization as unknown
    with pytest.raises(profile.UnknownDeviceError):
        profile.device_peaks()
    rows = profile.summary([{"kernel": "k", "bucket": "b", "tiling": "t",
                             "us": 1.0, "flops": 1.0, "bytes": 1.0,
                             "seed": 0}])
    assert rows[0]["roofline_utilization"] is None


# ------------------------------------------------------------------ #
# StatCounters: the legacy-dict facade
# ------------------------------------------------------------------ #
def test_statcounters_reads_like_the_legacy_dict():
    reg = Registry()
    fam = reg.counter("t_events_total", "events", ("event",))
    stats = StatCounters({"hits": fam.labels(event="hits"),
                          "misses": fam.labels(event="misses"),
                          "local_only": None})
    stats.bump("hits")
    stats.bump("hits", 2)
    stats.bump("misses")
    stats.bump("local_only", 5)
    # exact legacy read surface
    assert stats["hits"] == 3
    assert dict(stats) == {"hits": 3, "misses": 1, "local_only": 5}
    assert stats.snapshot() == dict(stats)
    assert len(stats) == 3 and set(stats) == set(dict(stats))
    assert "3" in repr(stats)
    # mirrored children saw the same increments; None stayed local
    assert fam.value(event="hits") == 3
    assert fam.value(event="misses") == 1


def test_statcounters_negative_corrections_stay_local():
    reg = Registry()
    fam = reg.counter("t_corr_total", "corrections", ("event",))
    stats = StatCounters({"hits": fam.labels(event="hits")})
    stats.bump("hits", 2)
    stats.bump("hits", -1)          # legacy correction pattern
    assert stats["hits"] == 1
    assert fam.value(event="hits") == 2, \
        "registry counters are monotonic; corrections must not decrement"


# ------------------------------------------------------------------ #
# span tracer + Chrome export
# ------------------------------------------------------------------ #
def test_tracer_records_spans_and_exports_chrome_shape(tmp_path):
    jsonl = tmp_path / "spans.jsonl"
    tr = Tracer(capacity=16, jsonl_path=str(jsonl))
    with tr.span("unit.outer", widget="a"):
        with tr.span("unit.inner"):
            pass
    events = tr.events()
    assert [e["name"] for e in events] == ["unit.inner", "unit.outer"]
    for e in events:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    assert events[1]["args"]["widget"] == "a"
    # the JSONL sink mirrors the ring buffer line-for-line
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["unit.inner", "unit.outer"]

    doc = obs.chrome_trace(events)
    assert isinstance(doc["traceEvents"], list) and len(
        doc["traceEvents"]) == 2
    json.dumps(doc)                       # Perfetto wants plain JSON

    tr.clear()
    assert tr.events() == []


def test_spans_carry_parent_and_inherit_batch_and_job():
    tr = Tracer(capacity=16)
    with tr.span("unit.dispatch", batch=4) as outer:
        with tr.span("unit.job", job="k1") as mid:
            with tr.span("unit.leaf"):
                pass
            tr.record("unit.after", mid.t0, 0.0)
        with tr.span("unit.other", job="k2"):
            pass
    with tr.span("unit.root"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    assert "parent" not in ev["unit.dispatch"]["args"]
    assert "parent" not in ev["unit.root"]["args"]
    assert ev["unit.job"]["args"] == {"job": "k1", "parent": outer.span_id,
                                      "batch": 4}
    assert ev["unit.leaf"]["args"] == {"parent": mid.span_id, "batch": 4,
                                       "job": "k1"}
    assert ev["unit.after"]["args"]["parent"] == mid.span_id
    assert ev["unit.other"]["args"]["job"] == "k2"
    assert obs.current_span() is None


def test_spans_nest_per_thread():
    tr = Tracer(capacity=16)
    seen = {}

    def worker():
        with tr.span("unit.thread") as sp:
            seen["args"] = dict(sp.args)

    with tr.span("unit.main", batch=1):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["args"] == {}, "a thread starts with no enclosing span"


def test_span_lands_in_a_jax_profile_on_the_trace_clock(tmp_path):
    """While a JAX profile runs, a span also shows in its ``/host:CPU``
    plane; its ring-buffer ``ts``, mapped through one annotation whose
    wall time is read while it is open, starts where the plane's does."""
    import glob
    import time

    import jax
    tr = Tracer(capacity=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("unit.clock"):
            sync_ns = time.time_ns()
        with tr.span("unit.profiled"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("unit."):
                        host[e.name] = (e.start_ns, e.duration_ns)
    assert set(host) == {"unit.clock", "unit.profiled"}
    offset_ns = sync_ns - host["unit.clock"][0]
    (ev,) = tr.events()
    mapped_ns = ev["ts"] * 1e3 - offset_ns
    assert abs(mapped_ns - host["unit.profiled"][0]) < 100e3
    assert host["unit.profiled"][1] >= 2e6


def test_queue_spans_join_one_dispatch_from_submit_to_store(tmp_path):
    from repro.service import JobQueue, ResultStore

    obs.tracer().clear()
    jobs = [_job(budget=b) for b in (2.0, 2.1, 2.2)]
    before = obs.registry().snapshot()
    with JobQueue(engine=CountingStubEngine(),
                  store=ResultStore(str(tmp_path))) as q:
        futures = q.submit_many(jobs)
        for f in futures:
            f.result(timeout=30)
    after = obs.registry().snapshot()
    events = obs.tracer().events()
    submits = [e for e in events if e["name"] == "queue.submit"]
    assert [e["args"]["jobs"] for e in submits] == [3], \
        "submit_many is one submit phase, not one per job"
    (dispatch,) = [e for e in events if e["name"] == "queue.dispatch"]
    seq = dispatch["args"]["batch"]
    (resolve,) = [e for e in events if e["name"] == "queue.resolve"]
    assert resolve["args"]["parent"] == dispatch["id"]
    assert resolve["args"]["batch"] == seq
    puts = [e for e in events if e["name"] == "store.put"]
    assert sorted(e["args"]["job"] for e in puts) == sorted(
        f.key for f in futures)
    assert all(e["args"]["parent"] == resolve["id"] and
               e["args"]["batch"] == seq for e in puts)
    for phase, n in (("submit", 1), ("resolve", 1)):
        key = f'cim_queue_phase_seconds_count{{phase="{phase}"}}'
        assert after[key] - before.get(key, 0.0) == n


def test_tracer_ring_buffer_caps_and_histogram_observes():
    reg = Registry()
    h = reg.histogram("t_span_seconds", "span time", buckets=(60.0,))
    tr = Tracer(capacity=3)
    for i in range(5):
        with tr.span("unit.loop", histogram=h.labels(), i=i):
            pass
    events = tr.events()
    assert len(events) == 3, "ring buffer must cap at capacity"
    assert [e["args"]["i"] for e in events] == [2, 3, 4]
    assert h.labels().snapshot()[1] == 5


def test_trace_cli_exports_perfetto_loadable_file(tmp_path):
    spans = tmp_path / "spans.jsonl"
    tr = Tracer(capacity=8, jsonl_path=str(spans))
    with tr.span("cli.work", rows=3):
        pass
    out = tmp_path / "trace.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.service", "trace",
         "--input", str(spans), "--export", "chrome", "-o", str(out)],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    ev = doc["traceEvents"][0]
    assert ev["name"] == "cli.work" and ev["ph"] == "X"
    assert {"ts", "dur", "pid", "tid"} <= set(ev)


# ------------------------------------------------------------------ #
# logging selectors
# ------------------------------------------------------------------ #
def test_log_spec_parsing():
    assert _parse_spec("server") == {"server": logging.DEBUG}
    assert _parse_spec("engine,queue=INFO") == {
        "engine": logging.DEBUG, "queue": logging.INFO}
    assert _parse_spec("all=WARNING") == {"all": logging.WARNING}
    assert _parse_spec(" Server = info ") == {"server": logging.INFO}
    assert _parse_spec("") == {}
    assert _parse_spec("x=bogus") == {"x": logging.DEBUG}


def test_configure_logging_applies_selectors_idempotently():
    root = configure_logging("engine=INFO,queue", force=True)
    try:
        assert root.level == logging.WARNING
        assert logging.getLogger("repro.engine").level == logging.INFO
        assert logging.getLogger("repro.queue").level == logging.DEBUG
        assert obs.get_logger("engine").getEffectiveLevel() == logging.INFO
        # one tagged handler no matter how often we configure
        configure_logging("all=INFO", force=True)
        assert root.level == logging.INFO
        tagged = [h for h in root.handlers
                  if getattr(h, "_repro_obs", False)]
        assert len(tagged) == 1
        assert root.propagate is False
    finally:
        configure_logging("", force=True)
        logging.getLogger("repro.engine").setLevel(logging.NOTSET)
        logging.getLogger("repro.queue").setLevel(logging.NOTSET)


# ------------------------------------------------------------------ #
# progress bus
# ------------------------------------------------------------------ #
def test_progress_bus_replays_history_then_delivers_live():
    bus = ProgressBus(history_per_key=4)
    bus.publish("k1", phase="race", rung=0)
    bus.publish("k1", phase="race", rung=1)
    bus.publish("other", phase="race", rung=0)

    got: list[dict] = []
    history = bus.subscribe(["k1"], lambda key, ev: got.append(ev))
    assert [ev["seq"] for ev in history] == [0, 1]
    assert all(ev["key"] == "k1" for ev in history)
    live = bus.publish("k1", phase="final")
    bus.publish("other", phase="final")      # not subscribed: not seen
    assert got == [live]
    assert live["seq"] == 2, "seq must stay monotonic across the boundary"

    bus.unsubscribe(lambda key, ev: None)    # unknown sink: no-op
    bus.unsubscribe(got.append)


def test_progress_bus_bounds_history_and_keys():
    bus = ProgressBus(history_per_key=2, max_keys=2)
    for rung in range(5):
        bus.publish("k1", rung=rung)
    assert [ev["rung"] for ev in bus.subscribe(["k1"], lambda *a: None)] \
        == [3, 4]
    bus.publish("k2")
    bus.publish("k3")                        # evicts the LRU key (k1)
    assert bus.subscribe(["k1"], lambda *a: None) == []
    assert bus.publish("k1")["seq"] == 0, "evicted key restarts its seq"


def test_progress_bus_survives_broken_sinks():
    bus = ProgressBus()

    def broken(key, ev):
        raise RuntimeError("dead subscriber")

    got = []
    bus.subscribe(["k"], broken)
    bus.subscribe(["k"], lambda key, ev: got.append(ev))
    bus.publish("k", rung=0)
    assert len(got) == 1, "one broken sink must not stall the others"


# ------------------------------------------------------------------ #
# HTTP surface: /v1/metrics, /v1/stats shape, concurrent load
# ------------------------------------------------------------------ #
def test_metrics_endpoint_serves_parseable_prometheus(tmp_path):
    srv = _server(tmp_path)
    try:
        _post_json(f"{srv.url}/v1/jobs?wait=30",
                   [job_to_spec(_job(), "exhaustive")])
        req = urllib.request.urlopen(f"{srv.url}/v1/metrics", timeout=30)
        with req as resp:
            ctype = resp.headers.get("Content-Type", "")
            body = resp.read().decode()
        assert ctype.startswith("text/plain") and "version=0.0.4" in ctype
        families = check_metrics.parse(body)
        assert len(families) >= 12
        for fam in ("cim_queue_submitted_total", "cim_queue_depth",
                    "cim_queue_wait_seconds", "cim_store_ops_total",
                    "cim_http_requests_total", "cim_http_request_seconds",
                    "cim_engine_jobs_total", "cim_search_pulls_total"):
            assert fam in families, f"missing family {fam}"
        assert check_metrics.family_total(
            families, "cim_queue_submitted_total") >= 1
        assert check_metrics.family_total(
            families, "cim_http_requests_total") >= 1
        # /v1/stats keeps its legacy JSON shape on the same numbers
        stats = _get_json(f"{srv.url}/v1/stats")
        assert {"queue", "server", "store"} <= set(stats)
        assert {"submitted", "store_hits", "inflight_dedup", "dispatches",
                "completed", "failed"} <= set(stats["queue"])
        assert stats["queue"]["submitted"] >= 1
    finally:
        srv.shutdown()


def test_stats_and_metrics_consistent_under_concurrent_load(tmp_path):
    """N reader threads hammer /v1/stats + /v1/metrics while a blocked
    batch is in flight and further jobs stream in: every stats snapshot
    must be internally consistent (no torn reads) and every counter
    monotonic across samples; every metrics scrape must stay parseable."""
    from repro.configs import get_arch
    eng = CountingStubEngine()
    from repro.core import ExploreJob
    from repro.core.macro import TPDCIM_MACRO
    slow_wl = get_arch("whisper-small").workload(seq=512)
    eng.block_buckets = {eng.bucket_key(
        ExploreJob(TPDCIM_MACRO, slow_wl, 2.23, space=SMALL), "exhaustive")}
    srv = _server(tmp_path, engine=eng)
    errors: list[str] = []
    stop = threading.Event()

    def reader():
        last: dict[str, float] = {}
        while not stop.is_set():
            try:
                stats = _get_json(f"{srv.url}/v1/stats")
                flat = {f"{sec}.{k}": v
                        for sec in ("queue", "server", "store")
                        for k, v in stats[sec].items()
                        if isinstance(v, (int, float))}
                for k in ("queue.submitted", "queue.dispatches",
                          "queue.completed", "server.requests"):
                    if flat[k] < last.get(k, 0):
                        errors.append(
                            f"{k} went backwards: {last[k]} -> {flat[k]}")
                    last[k] = flat[k]
                if flat["queue.completed"] > flat["queue.submitted"]:
                    errors.append(f"torn read: {flat}")
                with urllib.request.urlopen(f"{srv.url}/v1/metrics",
                                            timeout=30) as resp:
                    check_metrics.parse(resp.read().decode())
            except Exception as exc:      # noqa: BLE001 -- collected
                errors.append(f"reader died: {exc!r}")
                return

    threads = [threading.Thread(target=reader) for _ in range(4)]
    try:
        # hold one bucket open (the single queue worker blocks on it),
        # then pile further submissions on top: admission-side counters
        # (submitted, depth, store misses, http requests) keep moving on
        # the handler threads while the batch is active
        out = _post_json(f"{srv.url}/v1/jobs",
                         [job_to_spec(_job(wl=slow_wl), "exhaustive")])
        keys = [out["jobs"][0]["key"]]
        for t in threads:
            t.start()
        for budget in (2.23, 3.0, 4.0, 5.0):
            out = _post_json(f"{srv.url}/v1/jobs",
                             [job_to_spec(_job(budget=budget),
                                          "exhaustive")])
            keys.append(out["jobs"][0]["key"])
        eng.release.set()
        url = f"{srv.url}/v1/stream?keys={','.join(keys)}&timeout=30"
        with urllib.request.urlopen(url, timeout=60) as resp:
            done = {obj["key"] for event, obj in _read_sse(resp)
                    if event == "result"}
        assert done == set(keys)
    finally:
        eng.release.set()
        stop.set()
        for t in threads:
            t.join(timeout=30)
        srv.shutdown()
    assert not errors, errors[:5]


# ------------------------------------------------------------------ #
# SSE progress events
# ------------------------------------------------------------------ #
def test_stream_interleaves_progress_before_result(tmp_path):
    """A subscriber must see per-rung ``progress`` events -- including
    ones published before the stream attached (history replay) -- ahead
    of the final ``result`` for the same key."""
    # a budget no other test uses: the progress bus is process-global and
    # keyed by canonical job_key, so publishing against a shared job would
    # leak replayed history into other tests streaming the same key
    job = _job(budget=7.77)
    eng = CountingStubEngine()
    eng.block_buckets = {eng.bucket_key(job, "exhaustive")}
    srv = _server(tmp_path, engine=eng)
    try:
        out = _post_json(f"{srv.url}/v1/jobs",
                         [job_to_spec(job, "exhaustive")])
        key = out["jobs"][0]["key"]
        # rung events fire while the job computes, BEFORE the client
        # attaches its stream -- exactly the POST-then-stream race
        bus = obs.progress_bus()
        bus.publish(key, phase="race", allocator="bandit", rung=0,
                    best=2.0, pulls={"sa": 1})
        bus.publish(key, phase="race", allocator="bandit", rung=1,
                    best=1.0, pulls={"sa": 2})
        url = f"{srv.url}/v1/stream?keys={key}&timeout=30"
        events = []
        with urllib.request.urlopen(url, timeout=60) as resp:
            it = _read_sse(resp)
            for event, obj in it:
                events.append((event, obj))
                if event == "progress" and obj.get("rung") == 1:
                    # live event after the replay, then let it finish
                    bus.publish(key, phase="final", best=1.0)
                    eng.release.set()
                if event == "end":
                    break
        kinds = [e for e, _ in events]
        assert kinds.index("progress") < kinds.index("result")
        progress = [obj for e, obj in events if e == "progress"]
        assert [p["seq"] for p in progress] == [0, 1, 2]
        assert [p["phase"] for p in progress] == ["race", "race", "final"]
        assert progress[0]["rung"] == 0 and progress[0]["key"] == key
        assert kinds[-2:] == ["result", "end"]
    finally:
        eng.release.set()
        srv.shutdown()


# Runs in a child interpreter: one more real XLA engine run inside the
# suite process shifts native allocator state enough that a later jitted
# test aborts with glibc heap corruption ("corrupted double-linked
# list"); the bus/engine wiring under test is identical either way.
_PORTFOLIO_PROGRESS_CHILD = """
import json, sys
from test_service import _job
from repro import obs
from repro.core import ExplorationEngine, job_key
from repro.search import PortfolioSettings
from repro.service.queue import resolve_settings

settings = resolve_settings(
    "portfolio", PortfolioSettings(backends=("sobol", "sa"),
                                   total_evals=512, rungs=2))
job = _job(budget=7.91)
key = job_key(job, "portfolio", settings)
got = []
bus = obs.progress_bus()
bus.subscribe([key], lambda k, ev: got.append(ev))
res = ExplorationEngine().run([job], method="portfolio",
                              settings=settings)[0]
json.dump({"key": key, "winner": res.search["portfolio"]["winner"],
           "events": got}, sys.stdout)
"""


@pytest.mark.slow
def test_portfolio_run_publishes_per_rung_progress():
    """The real engine's portfolio path publishes >= 1 per-rung race
    event and a final event for each job's key."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), os.path.join(REPO, "tests")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-c", _PORTFOLIO_PROGRESS_CHILD],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    key, got = out["key"], out["events"]
    assert out["winner"] in ("sobol", "sa")
    phases = [ev["phase"] for ev in got]
    assert phases.count("race") >= 1, got
    assert phases[-1] == "final"
    assert all(ev["key"] == key for ev in got)
    race = [ev for ev in got if ev["phase"] == "race"]
    assert {"allocator", "rung", "best", "pulls"} <= set(race[0])

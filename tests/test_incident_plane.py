"""The incident plane (ISSUE 20): alert rule grammar, firing/resolved
state machines, event routing from the drift/fleet/watchdog latches,
black-box incident capture (rate limit, retention, atomicity), deep
profiling fallbacks, report/endpoint surfaces, and the zero-overhead
contract."""

import json
import os
import threading
import time

import numpy as np
import pytest

from dask_ml_tpu import config
from dask_ml_tpu.observability import alerts, incidents, live
from dask_ml_tpu.observability._counters import (
    counter_add,
    counters_reset,
)


@pytest.fixture(autouse=True)
def _clean_plane():
    yield
    alerts.reset()
    incidents.reset()
    counters_reset()
    live.metrics_reset()


# -- rule grammar ------------------------------------------------------------

def test_parse_rules_grammar():
    rules = alerts.parse_rules(
        "serving_slo_violations:rate>5/60s, drift_score_max:gauge>0.2;"
        "fit_eta_seconds:gauge>1800, recompiles:counter>=10"
    )
    assert [r.kind for r in rules] == ["rate", "gauge", "gauge",
                                       "counter"]
    r = rules[0]
    assert (r.metric, r.op, r.threshold, r.window_s) == \
        ("serving_slo_violations", ">", 5.0, 60.0)
    assert rules[1].window_s is None
    assert rules[3].op == ">="


def test_parse_rules_empty_and_builtin_are_no_rules():
    assert alerts.parse_rules("") == []
    assert alerts.parse_rules("builtin") == []
    assert alerts.parse_rules(" builtin , ") == []


@pytest.mark.parametrize("bad", [
    "nocolon",
    "x:bogus>1",
    "x:rate>1",          # rate needs a window
    "x:gauge>1/30s",     # windows are rate-only
    "x:rate>1/0s",       # window must be positive
    "x:gauge!1",
    "x:gauge>abc",
])
def test_parse_rules_typed_rejection_lists_accepted_forms(bad):
    with pytest.raises(alerts.AlertRuleError) as ei:
        alerts.parse_rules(bad)
    msg = str(ei.value)
    # the rejection is self-documenting: the full accepted-forms
    # vocabulary rides every error
    assert "accepted forms" in msg
    assert "rate" in msg and "gauge" in msg and "builtin" in msg
    assert isinstance(ei.value, ValueError)


# -- state machines (driven tick-by-tick, no ticker thread) ------------------

def _engine(spec, interval=1.0):
    rules = alerts.parse_rules(spec)
    return alerts.AlertEngine(rules, interval)


def test_gauge_rule_fires_and_resolves_with_hysteresis():
    eng = _engine("my_gauge:gauge>0.5")
    now = time.time()
    live.gauge_set("my_gauge", 0.9)
    out = eng.tick(now)
    assert [(r.name.split(":")[0], tr) for r, tr in out] == \
        [("my_gauge", "firing")]
    assert eng.rows()[0]["state"] == "firing"
    # one clean tick is NOT enough (hysteresis) ...
    live.gauge_set("my_gauge", 0.1)
    assert eng.tick(now + 1) == []
    assert eng.rows()[0]["state"] == "firing"
    # ... the second clean tick resolves
    out = eng.tick(now + 2)
    assert [tr for _, tr in out] == ["resolved"]
    assert eng.rows()[0]["state"] == "ok"
    assert eng.rows()[0]["fired"] == 1


def test_gauge_rule_worst_series_and_no_data():
    eng = _engine("g:gauge>1.0, h:gauge<0.0")
    now = time.time()
    # absent families = no data = no firing
    assert eng.tick(now) == []
    # worst series for the op direction: any one series breaching fires
    live.gauge_set("g", 0.5, (("shard", "a"),))
    live.gauge_set("g", 2.0, (("shard", "b"),))
    live.gauge_set("h", 0.5)
    out = eng.tick(now + 1)
    assert [r.metric for r, _ in out] == ["g"]


def test_rate_rule_first_sample_is_baseline():
    """Counter totals from BEFORE the engine armed can never fire a
    rate rule — the post-warmup-recompiles semantics."""
    counter_add("ev_total", 100)   # pre-arm history
    eng = _engine("ev_total:rate>2/10s")
    now = time.time()
    assert eng.tick(now) == []      # baseline sample, no verdict
    assert eng.tick(now + 1) == []  # no delta
    counter_add("ev_total", 5)
    out = eng.tick(now + 2)
    assert [tr for _, tr in out] == ["firing"]
    # the window slides: once the bump ages out, two clean ticks resolve
    assert eng.tick(now + 14) == []
    out = eng.tick(now + 15)
    assert [tr for _, tr in out] == ["resolved"]


def test_counter_rule_absolute_total():
    eng = _engine("boom:counter>=3")
    now = time.time()
    counter_add("boom", 2)
    assert eng.tick(now) == []
    counter_add("boom", 1)
    assert [tr for _, tr in eng.tick(now + 1)] == ["firing"]


def test_event_rule_fires_on_note_event_and_ages_out(tmp_path):
    with config.set(obs_alert_rules="builtin", obs_alert_interval_s=60):
        eng = alerts.ensure_engine()
        assert eng is not None
        rec = alerts.note_event("watchdog_stall", value=4.2,
                                meta={"span": "fit"})
        assert rec["event"] == "watchdog_stall"
        data = alerts.alerts_data()
        assert data["armed"] and \
            "builtin:watchdog_stall" in data["firing"]
        assert data["transitions"][-1]["state"] == "firing"
        # firing transitions increment the counter + set the gauge
        from dask_ml_tpu.observability._counters import counters_snapshot

        assert counters_snapshot().get("alerts_fired") == 1
        key = ("alerts_firing", (("rule", "builtin:watchdog_stall"),))
        assert live.gauges_snapshot()[key] == 1.0
        # a fresh event while firing refreshes the clock, no re-fire
        alerts.note_event("watchdog_stall", value=5.0)
        assert counters_snapshot().get("alerts_fired") == 1
        # age-based auto-resolve: EVENT_RESOLVE_TICKS intervals without
        # a fresh event
        out = eng.tick(now=time.time() + 60 * 10)
        assert [tr for _, tr in out] == ["resolved"]
        assert live.gauges_snapshot()[key] == 0.0


def test_events_ledger_records_without_engine():
    """The crossing ledger is always on — drift/fleet/watchdog events
    land even with no engine armed (the old private-deque role)."""
    assert alerts.engine() is None
    rec = alerts.note_event("drift", value=0.4, meta={"model": "m"})
    assert alerts.events("drift")[-1] is rec
    assert alerts.events("fleet_slo_burn") == []


def test_note_error_is_inert_by_default_and_routes_when_armed():
    alerts.note_error(ValueError("x"), "serving_execute")
    assert alerts.events("typed_error") == []   # disarmed: no ledger spam
    with config.set(obs_alert_rules="builtin", obs_alert_interval_s=60):
        alerts.ensure_engine()
        alerts.note_error(ValueError("boom"), "serving_execute")
        evs = alerts.events("typed_error")
        assert evs and evs[-1]["error"] == "ValueError"
        assert "builtin:typed_error" in alerts.alerts_data()["firing"]


def test_engine_transitions_emit_jsonl_and_capture(tmp_path):
    trace = str(tmp_path / "tr")
    idir = str(tmp_path / "inc")
    with config.set(trace_dir=trace, incident_dir=idir,
                    obs_alert_interval_s=60):
        eng = alerts.ensure_engine()   # incident_dir alone arms built-ins
        assert eng is not None
        alerts.note_event("fleet_slo_burn", value=2.5,
                          meta={"burn_rate": 2.5})
        recs = [json.loads(line)
                for line in open(os.path.join(trace, "trace.jsonl"))]
        al = [r for r in recs if r.get("alert")]
        assert al and al[-1]["rule"] == "builtin:fleet_slo_burn" \
            and al[-1]["state"] == "firing"
        # the firing transition captured one bundle
        files = [n for n in os.listdir(idir)
                 if n.startswith("incident_") and n.endswith(".json")]
        assert len(files) == 1
        inc = [r for r in [json.loads(line) for line in
                           open(os.path.join(trace, "trace.jsonl"))]
               if r.get("incident")]
        assert inc and inc[-1]["reason"] == "alert:builtin:fleet_slo_burn"


# -- source wiring (dedupe: one crossing = one event) ------------------------

def test_drift_canary_crossing_routes_through_ledger():
    from dask_ml_tpu.observability import drift

    rng = np.random.RandomState(0)
    old = rng.randn(400)
    new = old + 10.0   # wildly disagreeing versions
    with config.set(obs_drift_threshold=0.05):
        verdict = drift.record_canary("m", 1, 2, "predict", old, new)
    assert verdict["disagreement"] > 0.05
    evs = alerts.events("drift")
    assert len(evs) == 1 and evs[0]["pair"] == "canary"
    drift.reset()


def test_fleet_burn_latch_routes_through_ledger_same_record():
    from dask_ml_tpu.observability.fleet import MetricsFederator

    fed = MetricsFederator("f")
    doc1 = {"counters": {"serving_slo_violations": 0,
                         "serving_requests": 100}}
    doc2 = {"counters": {"serving_slo_violations": 50,
                         "serving_requests": 200}}
    fed.ingest([("p0", doc1)])
    fed.ingest([("p0", doc2)])       # 50/100 violations >> 1% budget
    assert len(fed._alerts) == 1
    evs = alerts.events("fleet_slo_burn")
    assert len(evs) == 1
    # the SAME object serves both surfaces — one crossing, one record
    assert fed._alerts[0] is evs[0]
    assert fed._alerts[0]["burn_rate"] > 1.0


def test_watchdog_stall_feeds_the_ledger():
    from dask_ml_tpu.observability import span
    from dask_ml_tpu.observability._watchdog import Watchdog

    wd = Watchdog(timeout_s=0.05, poll_s=0.02)
    with wd:
        with span("stalling"):
            deadline = time.time() + 5
            while not alerts.events("watchdog_stall"):
                assert time.time() < deadline, "no stall event"
                time.sleep(0.02)
    evs = alerts.events("watchdog_stall")
    assert evs and evs[-1]["span"] == "stalling"


# -- incident capture --------------------------------------------------------

def _arm(tmp_path, **kw):
    return config.set(incident_dir=str(tmp_path / "inc"), **kw)


def test_capture_bundle_contents_and_rate_limit(tmp_path):
    with _arm(tmp_path):
        path = incidents.capture_incident("test", rule="r1",
                                          meta={"k": "v"})
        assert path and os.path.exists(path)
        bundle = json.load(open(path))
        for key in ("open_spans", "recent_spans", "traces", "counters",
                    "gauges", "histograms", "programs",
                    "device_memory", "fault_plan", "alerts",
                    "watchdog_stalls", "config"):
            assert key in bundle, key
        assert bundle["reason"] == "test" and bundle["rule"] == "r1"
        assert bundle["meta"] == {"k": "v"}
        assert len(bundle["config"]["fingerprint"]) == 64
        assert bundle["config"]["values"]["incident_keep"] == 16
        # second capture inside the window: refused, counted
        assert incidents.capture_incident("again") is None
        from dask_ml_tpu.observability._counters import counters_snapshot

        snap = counters_snapshot()
        assert snap.get("incidents_captured") == 1
        assert snap.get("incidents_rate_limited") == 1
        # force bypasses the limit
        p2 = incidents.capture_incident("forced", force=True)
        assert p2 and p2 != path
        data = incidents.incidents_data()
        assert [c["reason"] for c in data["captured"]] == ["test",
                                                           "forced"]


def test_capture_disabled_without_dir(tmp_path):
    assert incidents.capture_incident("x") is None
    assert incidents.incidents_data()["captured"] == []


def test_retention_evicts_oldest(tmp_path):
    with _arm(tmp_path, incident_keep=2):
        paths = [incidents.capture_incident(f"r{i}", force=True)
                 for i in range(4)]
        idir = str(tmp_path / "inc")
        left = sorted(n for n in os.listdir(idir)
                      if n.startswith("incident_")
                      and n.endswith(".json"))
        assert len(left) == 2
        # the SURVIVORS are the newest two
        assert os.path.basename(paths[-1]) in left
        assert os.path.basename(paths[0]) not in left


def test_load_bundles_skips_unparseable(tmp_path):
    with _arm(tmp_path):
        incidents.capture_incident("good", force=True)
        idir = str(tmp_path / "inc")
        with open(os.path.join(idir, "incident_9999_bad.json"),
                  "w") as f:
            f.write("{truncated")
        rows = incidents.load_bundles(idir)
        assert len(rows) == 2
        assert rows[0].get("reason") == "good"
        assert "error" in rows[1]
    assert "error" in incidents.load_bundles("/nonexistent/dir")[0]


def test_config_fingerprint_tracks_knobs():
    fp1, _ = incidents.config_fingerprint()
    with config.set(incident_keep=3):
        fp2, values = incidents.config_fingerprint()
    assert fp1 != fp2 and values["incident_keep"] == 3
    fp3, _ = incidents.config_fingerprint()
    assert fp3 == fp1


# -- deep profiling ----------------------------------------------------------

def test_deep_profile_noop_with_reason_off_tpu(tmp_path):
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("asserts the off-TPU fallback")
    with _arm(tmp_path):
        out = incidents.deep_profile(1)
    assert out["profiled"] is False
    assert "TPU" in out["reason"]
    assert out["backend"] == jax.default_backend()


def test_deep_profile_rejects_bad_seconds(tmp_path):
    with _arm(tmp_path):
        assert incidents.deep_profile(0)["profiled"] is False
        assert incidents.deep_profile("nan-ish")["profiled"] is False
        assert incidents.deep_profile(-3)["profiled"] is False


# -- report / endpoint surfaces ----------------------------------------------

def test_report_summaries_from_transition_records():
    from dask_ml_tpu.observability.report import (
        render_report,
        report_data,
    )

    records = [
        {"alert": True, "rule": "r1", "kind": "rate", "metric": "m",
         "state": "firing", "value": 7, "t_unix": 100.0},
        {"alert": True, "rule": "r1", "kind": "rate", "metric": "m",
         "state": "resolved", "value": 0, "t_unix": 160.0},
        {"alert": True, "rule": "r2", "kind": "gauge", "metric": "g",
         "state": "firing", "value": 0.9, "t_unix": 200.0},
        {"incident": True, "path": "/tmp/i.json", "reason": "alert:r1",
         "rule": "r1", "t_unix": 101.0},
    ]
    data = report_data(records)
    al = data["alerts"]
    assert al["firing"] == ["r2"]
    by_rule = {r["rule"]: r for r in al["rules"]}
    assert by_rule["r1"]["state"] == "ok" and by_rule["r1"]["fired"] == 1
    assert by_rule["r2"]["state"] == "firing"
    assert data["incidents"][0]["reason"] == "alert:r1"
    text = render_report(data)
    assert "alerts (rules engine)" in text
    assert "incidents (black-box bundles)" in text
    assert "r2" in text and "alert:r1" in text


def test_report_prefers_status_snapshot_blocks():
    from dask_ml_tpu.observability.report import (
        summarize_alerts,
        summarize_incidents,
    )

    snap = {"armed": True, "rules": [{"rule": "x", "state": "firing"}],
            "firing": ["x"], "transitions": []}
    records = [
        {"alert": True, "rule": "old", "state": "firing", "t_unix": 1},
        {"alerts": snap},
        {"incidents": [{"path": "p", "reason": "r", "rule": None,
                        "t_unix": 2}]},
    ]
    assert summarize_alerts(records) is snap
    assert summarize_incidents(records)[0]["path"] == "p"


def test_report_cli_incidents_flag(tmp_path, capsys):
    from dask_ml_tpu.observability.report import main

    with _arm(tmp_path):
        incidents.capture_incident("cli-test", force=True)
    idir = str(tmp_path / "inc")
    assert main(["--incidents", idir]) == 0
    out = capsys.readouterr().out
    assert "incident bundles" in out and "cli-test" in out
    # --json rides the same object
    assert main(["--incidents", idir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["incident_bundles"][0]["reason"] == "cli-test"
    assert doc["incident_bundles"][0]["counters"] is not None


def test_status_and_alerts_endpoint(tmp_path):
    import urllib.request

    with config.set(obs_alert_rules="builtin", obs_alert_interval_s=60,
                    incident_dir=str(tmp_path / "inc")):
        alerts.ensure_engine()
        alerts.note_event("watchdog_stall", value=1.0)
        doc = live.status_data()
        assert doc["alerts"]["armed"]
        assert "builtin:watchdog_stall" in doc["alerts"]["firing"]
        assert doc["incidents"]["captured"], "capture-on-firing missing"
        # the same blocks ride report_data as synthetic records — no
        # second serialization path
        assert doc["report"]["alerts"] is not None
        assert doc["report"]["alerts"]["firing"] == \
            doc["alerts"]["firing"]
        assert doc["report"]["incidents"] == \
            doc["incidents"]["captured"]
        with live.TelemetryServer(port=0) as srv:
            with urllib.request.urlopen(srv.url + "/alerts",
                                        timeout=5) as resp:
                adoc = json.loads(resp.read().decode())
        assert adoc["armed"] and adoc["rules"]
        assert adoc["events"][-1]["event"] == "watchdog_stall"


def test_export_lanes_alert_and_incident_instants():
    from dask_ml_tpu.observability.export import to_chrome_trace

    records = [
        {"span": "fit", "span_id": 1, "parent_id": None, "depth": 0,
         "time": 1.0, "t_unix": 101.0, "wall_s": 0.5,
         "thread": "MainThread"},
        {"alert": True, "rule": "r1", "state": "firing", "value": 3,
         "time": 1.2, "t_unix": 101.2, "thread": "MainThread"},
        {"alert": True, "rule": "r1", "state": "resolved", "value": 0,
         "time": 1.3, "t_unix": 101.3, "thread": "MainThread"},
        {"incident": True, "reason": "alert:r1", "path": "/tmp/x.json",
         "time": 1.25, "t_unix": 101.25, "thread": "MainThread"},
    ]
    trace = to_chrome_trace(records)
    names = [e["name"] for e in trace["traceEvents"]
             if e.get("ph") == "i"]
    assert "alert firing: r1" in names
    assert "incident: alert:r1" in names
    # resolved transitions stay off the timeline
    assert not any("resolved" in n for n in names)


# -- zero-overhead contract --------------------------------------------------

def test_incident_plane_adds_nothing_when_disabled():
    """Default config: no engine object, no ticker thread, no capture
    ring growth — and the streamed-SGD scan kernel's jaxpr stays
    byte-identical across an arm/disarm cycle of the full plane (the
    engine is host dicts + one thread; nothing of it exists inside
    jit)."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.models.sgd import _sgd_stream_program
    from dask_ml_tpu.observability._programs import unwrap

    def scan_jaxpr():
        body = unwrap(_sgd_stream_program(None, "xla", "hinge", False))
        K, S, d = 2, 8, 3
        return str(jax.make_jaxpr(
            lambda W, Xs, ys, c, lrs: body(
                W, (Xs,), ys, c, lrs, 1e-4, 1.0, 0.0, 1.0
            )
        )(jnp.zeros(d + 1), jnp.zeros((K, S, d)), jnp.zeros((K, S)),
          jnp.zeros(K, jnp.int32), jnp.zeros(K)))

    assert alerts.engine() is None
    assert alerts.ensure_engine() is None      # "" knobs: stays None
    assert not [t for t in threading.enumerate()
                if t.name == "dask-ml-tpu-alerts"]
    baseline = scan_jaxpr()
    with config.set(obs_alert_rules="builtin", obs_alert_interval_s=60):
        eng = alerts.ensure_engine()
        assert eng is not None and eng._thread.is_alive()
        assert scan_jaxpr() == baseline
    alerts.stop_engine()
    assert not [t for t in threading.enumerate()
                if t.name == "dask-ml-tpu-alerts"]
    assert scan_jaxpr() == baseline


def test_bad_rule_spec_raises_into_the_arming_caller():
    with config.set(obs_alert_rules="totally:wrong>"):
        with pytest.raises(alerts.AlertRuleError):
            live.ensure_telemetry()
    assert alerts.engine() is None

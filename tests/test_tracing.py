"""ISSUE 6 — request-scoped tracing + SLO plane (nakama_tpu/tracing.py).

Covers: the Ledger refactor (bounded deque + monotonic total), W3C
traceparent parse/format, span parent linkage + status + events, the
tail-based sampler (error/slow kept 100%, deterministic p-sample,
hold/release deferral, bounded active buffer), the matchmaker cohort
error trace under an injected `device.dispatch` fault, the SLO
burn-rate recorder + its overload signal, and the named
`trace_overhead_regression` bench gate.
"""

from __future__ import annotations

import time

import pytest

from nakama_tpu import tracing as trace_api
from nakama_tpu.tracing import (
    TRACES,
    Ledger,
    SloRecorder,
    TraceStore,
    Tracing,
    format_traceparent,
    parse_traceparent,
)


@pytest.fixture(autouse=True)
def _clean_traces():
    """The store is process-global (faults.PLANE precedent): every test
    here starts from a known posture and restores the shipped default
    afterwards so suite order can never leak sampling config."""
    TRACES.reset()
    TRACES.configure(
        enabled=True, sample_rate=1.0, slow_ms=1000.0,
        max_active=512, max_spans=64,
    )
    yield
    TRACES.reset()
    TRACES.configure(enabled=True, sample_rate=0.01, slow_ms=1000.0)


# ------------------------------------------------------------- Ledger


def test_ledger_bounded_with_monotonic_total():
    led = Ledger(4)
    for i in range(10):
        led.append({"i": i})
    assert len(led) == 4  # bounded
    assert led.total == 10  # ...but "how many ever" is exact
    assert [d["i"] for d in led] == [6, 7, 8, 9]
    assert led[-1]["i"] == 9  # indexing (breadcrumb update path)
    assert [d["i"] for d in reversed(led)] == [9, 8, 7, 6]
    assert bool(led) and not bool(Ledger(4))
    assert led.recent(2) == [led[-2], led[-1]]
    assert "ts" in led[-1]  # stamped on append


def test_tracing_ledgers_all_answer_how_many_ever():
    t = Tracing()
    for i in range(300):  # past the 256 cap
        t.record({"i": i})
        t.record_delivery(i=i)
        t.record_db_drain(i=i)
        t.record_breaker(i=i)
        t.record_overload(i=i)
    totals = t.ledger_totals()
    assert set(totals) == {
        "breadcrumbs", "deliveries", "db_drains",
        "breaker_events", "overload_events",
    }
    assert all(v == 300 for v in totals.values()), totals
    # the deliveries_total compat property reads the Ledger counter
    assert t.deliveries_total == 300
    assert len(t.deliveries) == 256


def test_interval_crumb_takes_the_add_stages_and_a_seq():
    """`record(interval=True)` folds the add stages summed since the
    last interval crumb and starts them again; every crumb has a seq
    and both perf_counter stamps (mid-gap crumbs take no add stages)."""
    t = Tracing()
    t.add_stages.add(1.0, 1.25, 1.5, 1.75, 2.0)
    session = object()
    t.add_stages.enveloped(session)
    t.add_stages.envelope_done(session, 1.5)
    t.add_stages.envelope_done(object(), 9.0)  # refused: no add of its own
    t.add_stages.add(3.0, 3.125, 3.25, 3.375, 3.5)
    gap = t.open_crumb(midgap_collect=True)
    t.record(gap)
    assert "adds" not in gap
    crumb = t.open_crumb(actives=7)
    t.record(crumb, interval=True)
    assert crumb["seq"] == gap["seq"] + 1
    assert crumb["_pc_start"] <= crumb["_pc_end"]
    assert (crumb["adds"], crumb["adds_enveloped"]) == (2, 1)
    assert crumb["add_pipeline_s"] == 0.5  # 1.5 s envelope - 1.0 s add
    assert crumb["add_parse_s"] == crumb["add_trace_s"] == 0.375
    nxt = t.open_crumb(actives=0)
    t.record(nxt, interval=True)
    assert (nxt["adds"], nxt["add_parse_s"], nxt["add_pipeline_s"]) == (0, 0, 0)


# -------------------------------------------------------- traceparent


def test_traceparent_roundtrip():
    tid, sid = "ab" * 16, "cd" * 8
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)


@pytest.mark.parametrize(
    "bad",
    [
        "",
        "00-short-1234567812345678-01",
        "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # all-zero trace id
        "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
        "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
        "no-dashes-here",
    ],
)
def test_traceparent_malformed(bad):
    with pytest.raises(ValueError):
        parse_traceparent(bad)


def test_root_span_ingests_traceparent_and_bad_header_starts_fresh():
    with trace_api.root_span(
        "r", traceparent=format_traceparent("ab" * 16, "cd" * 8)
    ) as sp:
        assert sp.trace_id == "ab" * 16
        assert sp.parent_id == "cd" * 8
    with trace_api.root_span("r", traceparent="garbage") as sp:
        assert len(sp.trace_id) == 32 and sp.trace_id != "ab" * 16


# ---------------------------------------------------------------- spans


def test_span_parent_linkage_attrs_events_status():
    with trace_api.root_span("root", kind="test") as root:
        assert trace_api.current_span() is root
        assert trace_api.current_trace_ids() == (
            root.trace_id, root.span_id,
        )
        with trace_api.span("child", step=1) as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            trace_api.add_event("thing", detail="x")
            child.set_status("error", "boom")
        assert trace_api.current_span() is root  # restored
    assert trace_api.current_span() is None
    trace = TRACES.get(root.trace_id)
    spans = trace["resourceSpans"][0]["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["child"]["parentSpanId"] == root.span_id
    assert by_name["child"]["events"][0]["name"] == "thing"
    assert by_name["child"]["status"]["code"] == "ERROR"
    assert by_name["root"]["attributes"]["kind"] == "test"
    assert trace["status"] == "error"  # child error marks the trace


def test_span_without_active_trace_is_noop():
    with trace_api.span("orphan") as sp:
        assert sp is None
    assert TRACES.stats()["finished_total"] == 0


def test_disabled_store_is_noop():
    TRACES.configure(enabled=False)
    with trace_api.root_span("r") as sp:
        assert sp is None
    TRACES.configure(enabled=True)
    assert TRACES.stats()["finished_total"] == 0


# ------------------------------------------------------- tail sampling


def test_tail_sampling_keeps_errors_and_slow_at_rate_zero():
    TRACES.configure(sample_rate=0.0, slow_ms=50.0)
    with trace_api.root_span("fine"):
        pass
    with pytest.raises(RuntimeError):
        with trace_api.root_span("broken"):
            raise RuntimeError("x")
    with trace_api.root_span("slow") as sp:
        sp.start_ts -= 10.0  # fake a 10s root without sleeping
        sp._pc0 -= 10.0
    st = TRACES.stats()
    assert st["finished_total"] == 3
    assert st["kept_by"] == {"error": 1, "slow": 1}
    roots = {r["root"]: r["reason"] for r in TRACES.list(10)}
    assert roots == {"broken": "error", "slow": "slow"}


def test_p_sampling_deterministic_salted_and_rate_shaped():
    assert TraceStore._p_sample("ff" * 16, 1.0)
    assert not TraceStore._p_sample("00" * 16, 0.0)
    # Deterministic within the process: same id, same decision.
    tid = trace_api.new_trace_id()
    assert TraceStore._p_sample(tid, 0.5) == TraceStore._p_sample(
        tid, 0.5
    )
    # Salted: a client-minted low/high prefix must NOT force the
    # decision — over many ids the keep fraction tracks the rate.
    ids = [trace_api.new_trace_id() for _ in range(2000)]
    kept = sum(TraceStore._p_sample(t, 0.1) for t in ids)
    assert 100 <= kept <= 320, kept  # ~200 expected
    hostile = ["00000001" + t[8:] for t in ids[:500]]
    hostile_kept = sum(TraceStore._p_sample(t, 0.01) for t in hostile)
    assert hostile_kept < 50, hostile_kept  # prefix buys nothing


def test_hold_defers_sampling_until_release():
    with trace_api.root_span("ws.matchmaker_add") as root:
        TRACES.hold(root.trace_id)
    assert TRACES.stats()["finished_total"] == 0  # held open
    trace_api.emit_span(
        root.trace_id, root.span_id, "matchmaker.published",
        start_ts=time.time(), end_ts=time.time(),
    )
    TRACES.release(root.trace_id)
    st = TRACES.stats()
    assert st["finished_total"] == 1 and st["kept_total"] == 1
    spans = TRACES.get(root.trace_id)["resourceSpans"][0][
        "scopeSpans"
    ][0]["spans"]
    assert {s["name"] for s in spans} == {
        "ws.matchmaker_add", "matchmaker.published",
    }


def test_active_buffer_bounded_evicts_oldest_held():
    TRACES.configure(max_active=8)
    ids = []
    for i in range(20):
        with trace_api.root_span(f"r{i}") as sp:
            TRACES.hold(sp.trace_id)  # never released
            ids.append(sp.trace_id)
    st = TRACES.stats()
    assert st["active"] <= 8
    assert st["finished_total"] >= 12  # evicted ones were finalized


def test_release_after_eviction_never_orphans_or_double_finalizes():
    """A trace evicted by the active-buffer bound is tombstoned: its
    deferred spans arriving later are counted as late (never
    resurrecting an entry), the paired release is a no-op, and the
    trace is finalized exactly once."""
    TRACES.configure(max_active=4, sample_rate=0.0)
    ids = []
    for i in range(8):
        with trace_api.root_span(f"r{i}") as sp:
            TRACES.hold(sp.trace_id)
            ids.append(sp.trace_id)
    assert TRACES.stats()["active"] <= 4  # oldest evicted + finalized
    for tid in ids:  # deferred spans + release for every ticket
        trace_api.emit_span(
            tid, "p", "matchmaker.published",
            start_ts=time.time(), end_ts=time.time(),
        )
        TRACES.release(tid)
    st = TRACES.stats()
    assert st["active"] == 0, st
    assert st["finished_total"] == 8, st  # exactly once per trace
    assert st["late_spans"] == 4, st  # the evicted four, counted


def test_slow_judged_on_full_span_extent_not_root_duration():
    """A held trace's duration lives in post-hoc spans (the cohort's
    dispatch→published), not the ms-long root: slow-keep must judge
    the full extent or production matched-ticket traces are never
    tail-kept as slow."""
    TRACES.configure(sample_rate=0.0, slow_ms=1000.0)
    with trace_api.root_span("ws.matchmaker_add") as root:  # fast root
        TRACES.hold(root.trace_id)
    now = time.time()
    trace_api.emit_span(
        root.trace_id, root.span_id, "matchmaker.matched",
        start_ts=now - 5.0, end_ts=now,
    )
    TRACES.release(root.trace_id)
    kept = TRACES.list(5)
    assert kept and kept[0]["reason"] == "slow", TRACES.stats()
    assert kept[0]["duration_ms"] >= 5000


def test_max_spans_per_trace_bounded():
    TRACES.configure(max_spans=4)
    with trace_api.root_span("root") as root:
        for i in range(50):
            with trace_api.span(f"c{i}"):
                pass
    rec = TRACES.get(root.trace_id)
    assert len(
        rec["resourceSpans"][0]["scopeSpans"][0]["spans"]
    ) == 4
    # Loss is flagged, never silent: a missing stage span must read as
    # truncation, not as the stage never having happened.
    assert rec["truncated"] is True
    assert rec["spans_dropped"] == 47  # 51 spans recorded, 4 stored


def test_emit_matched_spans_builds_stage_chain_and_links_cohort():
    with trace_api.root_span("ws.matchmaker_add") as root:
        TRACES.hold(root.trace_id)
    entry = {
        "dispatched_ts": time.time() - 2.0,
        "ready_lag_s": 0.5,
        "collect_lag_s": 1.0,
        "publish_lag_s": 1.5,
        "trace_id": "ee" * 16,
    }
    trace_api.emit_matched_spans((root.trace_id, root.span_id), entry)
    rec = TRACES.get(root.trace_id)
    spans = rec["resourceSpans"][0]["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert {
        "matchmaker.matched", "matchmaker.dispatch_to_ready",
        "matchmaker.collected", "matchmaker.published",
    } <= set(by_name)
    assert by_name["matchmaker.matched"]["links"][0]["trace_id"] == (
        "ee" * 16
    )
    assert (
        by_name["matchmaker.published"]["durationMs"]
        > by_name["matchmaker.dispatch_to_ready"]["durationMs"]
    )
    assert TRACES.stats()["active"] == 0  # hold released


def test_jsonl_export_writes_kept_traces(tmp_path):
    import json as _json

    path = tmp_path / "traces.jsonl"
    TRACES.configure(export_path=str(path))
    with trace_api.root_span("exported"):
        pass
    TRACES.configure(export_path="")
    lines = path.read_text().splitlines()
    assert len(lines) == 1
    rec = _json.loads(lines[0])
    assert rec["root"] == "exported" and rec["spans"]


# -------------------------------------------- matchmaker fault tracing


def test_dispatch_fault_yields_tail_kept_error_trace_with_breaker():
    """Acceptance: an injected `device.dispatch` fault produces a
    tail-sampled error trace (kept at sample_rate=0) whose cohort span
    carries the breaker event."""
    from nakama_tpu import faults
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    TRACES.configure(sample_rate=0.0)
    cfg = MatchmakerConfig(
        pool_capacity=64, candidates_per_ticket=16, numeric_fields=4,
        string_fields=4, max_constraints=4, max_intervals=50,
    )
    backend = TpuBackend(cfg, test_logger(), row_block=8, col_block=16)
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=backend, on_matched=lambda b: None
    )
    try:
        for i in range(2):
            p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
            mm.add([p], p.session_id, "", "*", 2, 2, 1, {}, {})
        faults.arm("device.dispatch", "raise", count=1)
        mm.process()
    finally:
        mm.stop()
    kept = TRACES.list(10)
    assert [k["root"] for k in kept] == ["matchmaker.cohort"], kept
    assert kept[0]["reason"] == "error"
    rec = TRACES.get(kept[0]["trace_id"])
    root = rec["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
    assert root["status"]["code"] == "ERROR"
    events = {e["name"]: e for e in root.get("events", ())}
    assert events["breaker"]["stage"] == "dispatch"


def test_matched_ticket_trace_covers_add_to_publish():
    """Acceptance: an add that matches produces ONE trace id whose
    spans cover the envelope root, the add, and the cohort's
    dispatch→ready→collected→published stages."""
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=64, candidates_per_ticket=16, numeric_fields=4,
        string_fields=4, max_constraints=4, max_intervals=50,
    )
    backend = TpuBackend(cfg, test_logger(), row_block=8, col_block=16)
    got = []
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=backend, on_matched=got.append
    )
    try:
        tids = []
        for i in range(2):
            p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
            with trace_api.root_span("ws.matchmaker_add") as root:
                mm.add([p], p.session_id, "", "*", 2, 2, 1, {}, {})
                tids.append(root.trace_id)
        deadline = time.perf_counter() + 60
        while (
            sum(b.entry_count for b in got) < 2
            and time.perf_counter() < deadline
        ):
            mm.process()
            backend.wait_idle(timeout=30)
            mm.collect_pipelined()
    finally:
        mm.stop()
    assert sum(b.entry_count for b in got) == 2
    for tid in tids:
        rec = TRACES.get(tid)
        assert rec is not None, TRACES.stats()
        names = {
            s["name"]
            for s in rec["resourceSpans"][0]["scopeSpans"][0]["spans"]
        }
        assert {
            "ws.matchmaker_add", "matchmaker.add", "matchmaker.matched",
            "matchmaker.published",
        } <= names, names
    assert not mm._ticket_traces  # holds all released


def test_removed_ticket_releases_its_trace_hold():
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=64, candidates_per_ticket=16, numeric_fields=4,
        string_fields=4, max_constraints=4,
    )
    backend = TpuBackend(cfg, test_logger(), row_block=8, col_block=16)
    mm = LocalMatchmaker(test_logger(), cfg, backend=backend)
    try:
        p = MatchmakerPresence(user_id="u", session_id="s")
        with trace_api.root_span("ws.matchmaker_add"):
            ticket, _ = mm.add([p], "s", "", "*", 2, 2, 1, {}, {})
        assert mm._ticket_traces
        assert TRACES.stats()["active"] == 1  # held open
        mm.remove_session("s", ticket)
        assert not mm._ticket_traces
        assert TRACES.stats()["active"] == 0  # finalized on removal
    finally:
        mm.stop()


# ------------------------------------------------------------ SLO plane


def test_slo_recorder_burn_rates_and_windows():
    rec = SloRecorder(
        {"api_latency": {"target": 0.99, "threshold_ms": 100}}
    )
    for _ in range(98):
        rec.observe("api_latency", 10.0)
    rec.observe("api_latency", 10.0)
    rec.observe("api_latency", 5000.0)  # 1 bad in 100 → burn 1.0
    rates = rec.burn_rates()
    assert rates["api_latency"]["5m"] == pytest.approx(1.0, abs=0.01)
    assert rates["api_latency"]["1h"] == pytest.approx(1.0, abs=0.01)
    # all-bad → burn = 1/budget = 100x
    rec2 = SloRecorder(
        {"publish": {"target": 0.99, "threshold_ms": 1}}
    )
    for _ in range(10):
        rec2.observe("publish", 99.0)
    assert rec2.burn_rate("publish", 300) == pytest.approx(100.0)
    assert rec2.max_burn("5m") == pytest.approx(100.0)
    # no data / unknown slo → 0, never a crash
    assert rec2.burn_rate("nope", 300) == 0.0
    rec2.observe("nope", 1.0)  # ignored


def test_slo_recorder_publishes_gauges():
    from nakama_tpu.metrics import Metrics

    m = Metrics()
    rec = SloRecorder(
        {"api_latency": {"target": 0.9, "threshold_ms": 100}},
        metrics=m,
    )
    rec.observe("api_latency", 500.0)
    rec.sample()
    snap = m.snapshot()
    assert snap.get(
        "nakama_slo_burn_rate{slo=api_latency,window=5m}"
    ) == pytest.approx(10.0)


def test_slo_burn_signal_escalates_only_when_asked():
    from nakama_tpu import overload

    rec = SloRecorder({"x": {"target": 0.99, "threshold_ms": 1}})
    for _ in range(10):
        rec.observe("x", 99.0)  # burn 100
    watch = overload.slo_burn_signal(rec, 14.0, 99.0, escalate=False)
    assert watch() == overload.OK  # publish-only posture
    sig = overload.slo_burn_signal(rec, 14.0, 99.0, escalate=True)
    assert sig() == overload.SHED
    sig2 = overload.slo_burn_signal(rec, 200.0, 500.0, escalate=True)
    assert sig2() == overload.OK
    rec2 = SloRecorder({"x": {"target": 0.99, "threshold_ms": 1}})
    for _ in range(100):
        rec2.observe("x", 99.0 if _ % 2 else 0.5)  # burn ~50
    sig3 = overload.slo_burn_signal(rec2, 14.0, 100.0, escalate=True)
    assert sig3() == overload.WARN


# -------------------------------------------------------- bench gate


def test_trace_overhead_regression_gate():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_gate",
        os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py"),
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    reasons, regression = bench.trace_overhead_regression(0.2)
    assert not regression and reasons == []
    reasons, regression = bench.trace_overhead_regression(1.0)
    assert regression and "1%" in reasons[0]
    reasons, regression = bench.trace_overhead_regression(7.3)
    assert regression


# ------------------------------------------------- console endpoints


def test_console_traces_endpoints():
    """/v2/console/traces list + single-trace drill-down serve the
    kept store (auth-gated like every console route)."""
    import asyncio

    from aiohttp import web as _web  # noqa: F401 (aiohttp presence)

    from nakama_tpu.config import Config
    from nakama_tpu.console.server import ConsoleServer
    from nakama_tpu.logger import test_logger

    class _Srv:
        pass

    async def run():
        import aiohttp

        with trace_api.root_span("http GET /demo") as root:
            with trace_api.span("admission"):
                pass
        srv = _Srv()
        srv.config = Config()
        srv.logger = test_logger()
        srv.slo = SloRecorder(
            {"api_latency": {"target": 0.99, "threshold_ms": 100}}
        )
        console = ConsoleServer(srv)
        port = await console.start("127.0.0.1", 0)
        try:
            from nakama_tpu.api import session_token

            token, _ = session_token.generate(
                srv.config.console.signing_key, "admin", "admin",
                3600, vars={"role": "1"},
            )
            async with aiohttp.ClientSession() as http:
                headers = {"Authorization": f"Bearer {token}"}
                async with http.get(
                    f"http://127.0.0.1:{port}/v2/console/traces",
                    headers=headers,
                ) as resp:
                    assert resp.status == 200
                    body = await resp.json()
                async with http.get(
                    f"http://127.0.0.1:{port}/v2/console/traces/"
                    f"{root.trace_id}",
                    headers=headers,
                ) as resp:
                    assert resp.status == 200
                    one = await resp.json()
                async with http.get(
                    f"http://127.0.0.1:{port}/v2/console/traces/"
                    f"{'0' * 32}",
                    headers=headers,
                ) as resp:
                    missing = resp.status
                async with http.get(
                    f"http://127.0.0.1:{port}/v2/console/traces"
                ) as resp:
                    unauth = resp.status
        finally:
            await console.stop()
        return body, one, missing, unauth

    body, one, missing, unauth = asyncio.run(run())
    assert body["traces"] and body["traces"][0]["root"] == "http GET /demo"
    assert body["kept_total"] == 1
    assert "api_latency" in body["slo"]["burn_rates"]
    names = [
        s["name"]
        for s in one["resourceSpans"][0]["scopeSpans"][0]["spans"]
    ]
    assert set(names) == {"http GET /demo", "admission"}
    assert missing == 404
    assert unauth == 401

"""Tier-1 tracing smoke: one booted server, end-to-end trace trees.

Boots the full NakamaServer (HTTP front door, overload plane, device
matchmaker backend, 1s intervals), runs ONE HTTP request with an
ingested W3C traceparent and ONE matchmaker add→matched cycle through
the realtime pipeline, and asserts each yields a single complete trace:
the HTTP trace continues the client's trace id and spans admission; the
matchmaker trace's span tree covers admission → pipeline → matchmaker
add → cohort stages → publish, is retrievable from
`/v2/console/traces`, and its trace id appears on correlated log lines.

Subprocess-isolated per the perf-ratio-test convention
(test_storage_writeload / test_fault_smoke): the trace store is
process-global and the server spins device worker threads — a fresh
interpreter guarantees no sampling config, armed fault, or thread
leaks into (or from) the rest of the suite.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def _smoke() -> dict:
    import asyncio
    import base64
    import tempfile
    import time

    from nakama_tpu import tracing as trace_api
    from nakama_tpu.config import Config
    from nakama_tpu.server import NakamaServer

    tmp = tempfile.mkdtemp(prefix="trace-smoke-")
    logpath = f"{tmp}/server.log"
    cfg = Config()
    cfg.socket.port = 0
    cfg.socket.grpc_port = -1
    cfg.logger.stdout = False
    cfg.logger.file = logpath
    cfg.logger.level = "debug"
    mc = cfg.matchmaker
    mc.pool_capacity = 64
    mc.candidates_per_ticket = 16
    mc.numeric_fields = 4
    mc.string_fields = 4
    mc.max_constraints = 4
    mc.interval_sec = 1
    mc.max_intervals = 50
    cfg.tracing.sample_rate = 1.0  # the smoke wants every trace kept

    out: dict = {}

    async def run():
        import aiohttp

        # backend="tpu" in config is refused off-TPU; the CPU rig hands
        # the server its own (interpreting) device backend instead.
        from nakama_tpu.logger import setup_logging
        from nakama_tpu.matchmaker.tpu import TpuBackend

        log = setup_logging(cfg.logger)
        server = NakamaServer(
            cfg, log, matchmaker_backend=TpuBackend(cfg.matchmaker, log)
        )
        await server.start()
        base = f"http://{'127.0.0.1'}:{server.port}"
        console = f"http://127.0.0.1:{server.console_port}"
        tp_in = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        try:
            async with aiohttp.ClientSession() as http:
                # --- one HTTP request, client traceparent ingested
                auth = "Basic " + base64.b64encode(
                    b"defaultkey:"
                ).decode()
                async with http.post(
                    f"{base}/v2/account/authenticate/device",
                    json={"account": {"id": "trace-smoke-device-0001"}},
                    headers={
                        "Authorization": auth, "traceparent": tp_in
                    },
                ) as resp:
                    out["http_status"] = resp.status
                    out["tp_out"] = resp.headers.get("traceparent", "")

                # --- one matchmaker add→matched cycle via the pipeline
                class Stub:
                    def __init__(self, i):
                        self.id = f"sess-{i}"
                        self.user_id = f"user-{i}"
                        self.username = f"u{i}"
                        self.format = "json"
                        self.vars = {}

                    def send(self, env):
                        pass

                for i in range(2):
                    await server.pipeline.process(
                        Stub(i),
                        {
                            "matchmaker_add": {
                                "query": "*",
                                "min_count": 2,
                                "max_count": 2,
                            },
                            "cid": str(i),
                        },
                    )
                mm_traces = []
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    mm_traces = [
                        k
                        for k in trace_api.TRACES.list(100)
                        if k["root"] == "ws.matchmaker_add"
                    ]
                    if len(mm_traces) >= 2:
                        break
                    await asyncio.sleep(0.2)
                out["mm_traces"] = len(mm_traces)
                kept = trace_api.TRACES.list(100)
                http_traces = [
                    k for k in kept if k["root"].startswith("http POST")
                ]
                out["http_traces"] = len(http_traces)

                def names(trace_id):
                    rec = trace_api.TRACES.get(trace_id)
                    return sorted(
                        {
                            s["name"]
                            for rs in rec["resourceSpans"]
                            for ss in rs["scopeSpans"]
                            for s in ss["spans"]
                        }
                    )

                if http_traces:
                    out["http_trace_id"] = http_traces[0]["trace_id"]
                    out["http_span_names"] = names(out["http_trace_id"])
                if mm_traces:
                    out["mm_trace_id"] = mm_traces[0]["trace_id"]
                    out["mm_span_names"] = names(out["mm_trace_id"])

                # --- retrievable from the console
                async with http.post(
                    f"{console}/v2/console/authenticate",
                    json={"username": "admin", "password": "password"},
                ) as resp:
                    ctoken = (await resp.json())["token"]
                headers = {"Authorization": f"Bearer {ctoken}"}
                async with http.get(
                    f"{console}/v2/console/traces?n=100", headers=headers
                ) as resp:
                    body = await resp.json()
                    out["console_trace_ids"] = [
                        t["trace_id"] for t in body["traces"]
                    ]
                    out["console_slo"] = sorted(
                        body.get("slo", {}).get("burn_rates", {})
                    )
                async with http.get(
                    f"{console}/v2/console/traces/"
                    + out.get("mm_trace_id", "0" * 32),
                    headers=headers,
                ) as resp:
                    out["console_single_status"] = resp.status
        finally:
            await server.stop()

    asyncio.run(run())

    # --- logs↔traces correlation by grep, as an operator would
    correlated = []
    with open(logpath) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("msg") == "matchmaker ticket added":
                correlated.append(rec.get("trace_id"))
    out["log_trace_ids"] = correlated
    return out


_CHILD = """
import importlib.util, json, sys
sys.path.insert(0, {repo!r})
spec = importlib.util.spec_from_file_location("trace_smoke", {path!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print(json.dumps(mod._smoke()))
"""


def test_trace_smoke_subprocess_isolated():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            _CHILD.format(repo=repo, path=os.path.abspath(__file__)),
        ],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])

    # HTTP: the response continues the client's trace id, and the kept
    # trace spans ingress + admission.
    assert out["http_status"] == 200, out
    assert out["tp_out"].startswith("00-" + "ab" * 16 + "-"), out
    assert out["http_traces"] == 1, out
    assert out["http_trace_id"] == "ab" * 16
    assert "admission" in out["http_span_names"], out
    assert any(
        n.startswith("http POST /v2/account/authenticate")
        for n in out["http_span_names"]
    ), out

    # Matchmaker: ONE trace id covering socket envelope ingress →
    # admission → pipeline dispatch → matchmaker add → cohort stages →
    # publish (the acceptance tree).
    assert out["mm_traces"] == 2, out  # one per added ticket
    assert {
        "ws.matchmaker_add",
        "admission",
        "pipeline.matchmaker_add",
        "matchmaker.add",
        "matchmaker.matched",
        "matchmaker.dispatch_to_ready",
        "matchmaker.collected",
        "matchmaker.published",
    } <= set(out["mm_span_names"]), out["mm_span_names"]

    # Retrievable from /v2/console/traces (list + single), with the
    # SLO burn snapshot alongside.
    assert out["mm_trace_id"] in out["console_trace_ids"], out
    assert out["http_trace_id"] in out["console_trace_ids"], out
    assert out["console_single_status"] == 200
    assert out["console_slo"] == [
        "api_latency", "delivery_publish", "matchmaker_interval",
    ], out

    # Correlated log lines carry the same trace id.
    assert out["mm_trace_id"] in out["log_trace_ids"], out

#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: set-up (load, warm-up, every shape the
window uses; counted as `setup_s`), a measured window of `--seconds`,
then the comparison that decides `correct`. The last line of standard
output is one JSON object: `correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` `breakdown`, and last `checks`. README.md
says what each earlier line holds and how to add a cell as files.

No accelerator, or fewer chips than the cell asks for: a non-zero exit
and no result. `--rehearse 1` is the one CPU route (tiny sizes, the
interpreting backend): it prints `"platform": "cpu"` and reports no
device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

from lib import harness  # noqa: E402
from lib.harness import say  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0,
                    help="CPU rehearsal at the configuration's tiny size")
    return ap.parse_args(argv)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    return cells[name]


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics this run reports: the cell's `end_to_end` ones with
    `--trace 0`, its `per_layer` ones with `--trace 1`."""
    return [
        m for m in bench["per_layer" if traced else "end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    ]


def device_or_exit(args, chips: int) -> dict:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    want = "cpu" if args.rehearse else "tpu"
    if d0.platform != want or (not args.rehearse and len(devices) < chips):
        sys.stderr.write(
            f"needs {chips} {want} device(s); JAX reports {len(devices)} x"
            f" {d0.platform}. No CPU carry-on (see --rehearse).\n"
        )
        raise SystemExit(3)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


async def run_cell(ctx, kind, trace_dir, sabotage=None):
    import jax

    scratch = os.path.join(ROOT, ".bench_run")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(os.path.join(scratch, "data"))
    harness.build_server(
        ctx, os.path.join(scratch, "data"),
        os.path.join(scratch, "server.log"),
    )
    say("server", overrides=ctx.overrides,
        interpret=bool(getattr(ctx.backend, "_interpret", False)))
    if sabotage is not None:
        sabotage(ctx)  # tests/test_faults.py breaks the timed path here
    harness.time_process(ctx)
    async with harness.running(ctx):
        await kind.setup(ctx)
        if trace_dir:
            # The device's lines and the host's TraceMe spans; not the
            # Python call tracer, which would slow the host it measures.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation("bench.sync"):
                ctx.notes["sync_pc"] = time.perf_counter()
        try:
            await kind.window(ctx, ctx.args.seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        ctx.memory_peak = harness.memory_peak_bytes()
        from lib import durable

        ctx.journal = await durable.acked_not_durable(
            os.path.join(scratch, "data", "bench.db"),
            [s.ticket for s in ctx.sessions if s.ticket is not None],
        )
        tracing = ctx.backend.tracing
        ctx.crumbs = [c for c in tracing.recent(4096) if "actives" in c]
        ctx.deliveries = tracing.recent_deliveries(4096)
        ctx.path_faults = list(ctx.backend.device_path_faults())
    shutil.rmtree(os.path.join(scratch, "data"), ignore_errors=True)


def window_rows(ctx):
    """Ledger rows and breadcrumbs of cohorts dispatched in the window."""
    lo, hi = ctx.t0_wall - 0.05, ctx.t0_wall + (ctx.t1 - ctx.t0) + 60.0
    rows = [d for d in ctx.deliveries
            if d.get("dispatched_ts") and lo <= d["dispatched_ts"] <= hi]
    # One breadcrumb with "actives" per process() call, none stamped:
    # the window's are the last as many as it had ticks.
    n = sum(1 for t, _ in ctx.ticks if t >= ctx.t0)
    return rows, ctx.crumbs[-n:] if n else []


def path_checks(ctx, crumbs) -> list[dict]:
    """The harness's own refusals: a run that left the device path or
    dispatched another program than the cell names is not a measurement
    of the cell. Compiles inside the window are counted and printed,
    and decide nothing: a host that stands still piles a tick's
    arrivals into a shape no warm-up reached, and `correct` is for the
    program's answers."""
    expect = ctx.config["expect"]
    off = []
    for c in ctx.crumbs:
        bad = {k: c[k] for k in (
            "backend_state", "host_actives", "host_deferred",
            "dispatch_failed", "collect_failed", "collect_reclaimed",
        ) if c.get(k)}
        if bad:
            off.append(bad)
    kernels = [c["kernel"] for c in crumbs if "kernel" in c]
    wrong = []
    for i, k in enumerate(kernels):
        widths = {w: k.get(w) for w in expect["widths"]}
        if (
            k["kernel"] != expect["kernel"]
            or (not ctx.rehearse and widths != expect["widths"])
            or k["interpret"] is not ctx.rehearse
            or k["rev"] != ctx.mm.config.rev_precision
        ):
            wrong.append(k)
        first = expect.get("first_dispatch")
        if i == 0 and first and not ctx.rehearse and any(
            k[key] != first[key] for key in first
        ):
            wrong.append(k)
    c0, c1 = ctx.compiles_at_t0, ctx.compiles_at_t1
    compiled = (c1["backend"] - c0["backend"]) + (
        c1["cache_requests"] - c0["cache_requests"])
    say("dispatched", kernels=[
        dict(kernel=k["kernel"], a_pad=k["a_pad"], n_cols=k["n_cols"])
        for k in kernels
    ], actives=[c["actives"] for c in crumbs],
        before_window=sorted({
            (c["kernel"]["a_pad"], c["kernel"]["n_cols"])
            for c in ctx.crumbs[:len(ctx.crumbs) - len(crumbs)]
            if "kernel" in c}),
        wrong=wrong[:2], off_device=off[:2], faults=ctx.path_faults)
    say("compiles", at_window_start=c0, at_window_end=c1,
        inside_window=compiled)
    if compiled:
        sys.stderr.write(
            f"note: {compiled} compile event(s) inside the window; the"
            " run times them (not part of correct)\n")

    def row(name, value):
        return dict(name=name, value=value, limit=0, ok=value == 0)

    say("journal", **ctx.journal)
    return [
        row("acked_not_durable", ctx.journal["missing"]),
        row("off_device_path", len(off) + len(ctx.path_faults)
            + (0 if kernels else 1)),
        row("wrong_program", len(wrong)),
    ]


def main(argv=None, sabotage=None) -> int:
    args = parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    kind = harness.load_module("traffic_kinds", traffic["kind"])
    wanted = metrics_for(bench, cell["name"], bool(args.trace))
    readers = []
    for m in wanted:
        folder = "layer_metrics" if args.trace else "end_to_end"
        spec = harness.load_json(folder, f"{m['name']}.json")
        readers.append((m, spec, harness.load_module("readers", spec["reader"])))

    from nakama_tpu.jaxenv import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_or_exit(args, cell["chips"])
    say("run", workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, cache_dir=cache_dir,
        **({"platform": "cpu", "rehearsal": True} if args.rehearse else {}))
    from nakama_tpu import native

    native.load()  # builds libnakama_native.so only where it is missing

    ctx = harness.Ctx(args, cell, config, traffic, device, T_PROCESS)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    asyncio.run(run_cell(ctx, kind, trace_dir, sabotage))

    rows, crumbs = window_rows(ctx)
    ctx.window_rows, ctx.window_crumbs = rows, crumbs
    say("ledger", rows=[{
        k: d.get(k) for k in (
            "ready_lag_s", "fetch_lag_s", "collect_lag_s", "accept_lag_s",
            "publish_lag_s", "slipped")
    } | {"at_s": round(d["dispatched_ts"] - ctx.t0_wall, 3)} for d in rows])
    say("ticks", at_s=[round(t - ctx.t0, 3) for t, _ in ctx.ticks
                       if t >= ctx.t0],
        host_ms=[round(d * 1e3, 1) for t, d in ctx.ticks if t >= ctx.t0],
        pool=[p for (t, _), p in zip(ctx.ticks, ctx.pool_at_tick)
              if t >= ctx.t0])
    if ctx.late:
        from lib.stats import percentile

        say("generator", adds=len(ctx.late),
            generator_late_p95_ms=percentile(ctx.late, 95) * 1e3,
            generator_late_max_ms=max(ctx.late) * 1e3)

    ctx.trace = None
    if trace_dir:
        from lib import trace as trace_lib

        ctx.trace = trace_lib.reduce(trace_dir, ctx)
        say("trace", **trace_lib.summary(ctx.trace))
        if ctx.trace.get("busy_s") is None:
            ctx.trace = None
    shutil.rmtree(trace_dir or "", ignore_errors=True)

    metrics = {}
    for m, spec, reader in readers:
        if args.rehearse and m["source"] == "device_trace":
            continue
        value = reader.read(ctx, spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    say("notes", **ctx.notes)

    # The program's state is freed before the reference runs on the chip.
    checks = path_checks(ctx, crumbs)
    sessions, eligible = ctx.sessions, ctx.eligible
    rev = bool(ctx.mm.config.rev_precision)
    ticks = [t for t, _ in ctx.ticks]
    ctx.server = ctx.mm = ctx.backend = None
    gc.collect()
    t = time.perf_counter()
    from lib.judge import judge

    limits = (traffic["rehearse"] if args.rehearse else traffic)["limits"]
    verdict = judge(sessions, rev, eligible, limits, ticks,
                    config["candidates_per_ticket"], config["max_intervals"])
    checks = verdict.pop("checks") + checks
    say("judge", seconds=round(time.perf_counter() - t, 2), **verdict)

    correct = all(c["ok"] for c in checks)
    if device["platform"] != "cpu":
        device["memory_peak_bytes"] = ctx.memory_peak
    if ctx.trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    result = {
        "correct": correct, "attempted": ctx.attempted,
        "failed": ctx.failed, "metrics": metrics, "device": device,
    }
    if ctx.trace:
        result["breakdown"] = ctx.trace["breakdown"]
    result["checks"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks
    }
    for c in checks:
        sys.stderr.write(
            f"check {c['name']}: {c['value']} (limit {c['limit']})"
            f" {'ok' if c['ok'] else 'FAILED'}\n"
        )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

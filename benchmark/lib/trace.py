"""Profiler trace -> device busy time, per-program device time, the
operations that took most time and the idle gaps by what the host did.

Reads the `.xplane.pb` the JAX profiler wrote with
`jax.profiler.ProfileData` and nothing else. A device plane is one
whose name starts with `/device:TPU:`; on it the line `XLA Ops` holds
one event per operation run and `XLA Modules` one per program run
(tests/test_trace.py reduces a small hand-made trace of that form).
The host's spans are the benchmark's own, taken on `perf_counter` and
brought onto the trace's clock by the `bench.sync` annotation written
when the trace starts.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of (start, end) intervals, and the
    merged intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] that `merged` busy ones leave."""
    out, at = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def label_gap(gap, host_spans) -> str:
    """What the host was doing for most of an idle gap: the host span
    that overlaps it longest, else `idle-until-tick`."""
    best, best_s = "idle-until-tick", 0.0
    a, b = gap
    covered = {}
    for name, s, e in host_spans:
        o = min(b, e) - max(a, s)
        if o > 0:
            covered[name] = covered.get(name, 0.0) + o
    for name, o in covered.items():
        if o > best_s:
            best, best_s = name, o
    return best if best_s >= 0.25 * (b - a) else "idle-until-tick"


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO text
    (`%fusion.59 = s32[...] fusion(...)`): keep the name before ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def read_planes(trace_dir: str) -> list[dict]:
    """[{"name", "lines": {line name: [(event name, start_s, end_s)]}}]."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (short_name(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events
            )
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_planes(planes, window, host_spans) -> dict | None:
    """`window` = (start, end) and `host_spans` = [(name, start, end)],
    all on the trace's clock. None when no operation ran on a device."""
    lo, hi = window
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    busy, merged0 = [], None
    op_s: dict[str, float] = {}
    programs: list[tuple[str, float, float]] = []
    ops0: list[tuple[str, float, float]] = []
    for p in devices:
        ops = [(a, b) for _, a, b in p["lines"].get(OPS_LINE, [])
               if b > lo and a < hi]
        total, merged = union_seconds(
            (max(a, lo), min(b, hi)) for a, b in ops
        )
        busy.append(total)
        if merged0 is None:
            merged0 = merged
            ops0 = [(n, a, b) for n, a, b in p["lines"].get(OPS_LINE, [])
                    if b > lo and a < hi]
        for name, a, b in p["lines"].get(OPS_LINE, []):
            if b > lo and a < hi:
                op_s[name] = op_s.get(name, 0.0) + (b - a)
        if not programs:
            programs = [(n, a, b) for n, a, b in
                        p["lines"].get(MODULES_LINE, []) if b > lo and a < hi]
    if not devices or not any(busy):
        return None
    n = len(devices)
    # Gaps under a millisecond are the seams between operations.
    all_gaps = [g for g in gaps(merged0, lo, hi) if g[1] - g[0] >= 1e-3]
    idle = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    by_label: dict[str, float] = {}
    for g in all_gaps:
        lab = label_gap(g, host_spans)
        by_label[lab] = by_label.get(lab, 0.0) + (g[1] - g[0])
    return dict(
        devices=n,
        window_s=hi - lo,
        busy_s=sum(busy) / n,
        programs=programs,  # of the first device, as are `ops`
        ops=ops0,
        op_s={k: v / n for k, v in op_s.items()},
        breakdown=dict(
            device_ops=[[k, v / n] for k, v in sorted(
                op_s.items(), key=lambda kv: -kv[1])[:10]],
            idle_gaps=[[f"{label_gap(g, host_spans)}@{g[0] - lo:.3f}s",
                        g[1] - g[0]] for g in idle],
        ),
        idle_by_label=by_label,
    )


def program_runs(tr: dict | None, match: str) -> list[tuple[float, float]]:
    """(start, end) of every run of the programs whose name holds
    `match`, in time order."""
    if not tr:
        return []
    return sorted((a, b) for name, a, b in tr["programs"] if match in name)


def find_event(planes, name: str):
    for p in planes:
        for events in p["lines"].values():
            for n, a, b in events:
                if n == name:
                    return a
    return None


def host_spans_of(ctx) -> list[tuple[str, float, float]]:
    """The benchmark's host spans on `perf_counter`: process() calls,
    adds, and each window cohort's fetch / assemble / publish stages
    from the delivery ledger's lags since dispatch."""
    spans = [("process", t, t + d) for t, d in ctx.ticks]
    spans += [("add", t, t + d) for t, d in ctx.add_spans]
    for r in ctx.window_rows:
        t = r.get("_pc_dispatch")
        if t is None:
            continue
        f, rd = r.get("fetch_lag_s"), r.get("ready_lag_s")
        c, pb = r.get("collect_lag_s"), r.get("publish_lag_s")
        if f is not None:
            spans.append(("fetch", t, t + f))
        if f is not None and rd is not None and rd > f:
            spans.append(("assemble", t + f, t + rd))
        if c is not None and pb is not None and pb > c:
            spans.append(("publish", t + c, t + pb))
    return spans


def reduce(trace_dir: str, ctx) -> dict | None:
    planes = read_planes(trace_dir)
    sync = find_event(planes, "bench.sync")
    if sync is None:
        raise RuntimeError("the trace holds no bench.sync annotation")
    shift = sync - ctx.notes["sync_pc"]  # perf_counter -> trace clock
    host = [(n, a + shift, b + shift) for n, a, b in host_spans_of(ctx)]
    out = reduce_planes(planes, (ctx.t0 + shift, ctx.t1 + shift), host)
    described = [
        {"plane": p["name"], "lines": {
            k: len(v) for k, v in p["lines"].items()}}
        for p in planes
    ]
    if out is None:
        return {"planes": described, "busy_s": None}
    out["planes"] = described
    return out


def summary(tr: dict) -> dict:
    if tr.get("busy_s") is None:
        return {"planes": tr["planes"], "device_ops": "none ran"}
    names = sorted({n for n, _, _ in tr["programs"]})
    return dict(
        planes=tr["planes"], devices=tr["devices"],
        busy_s=tr["busy_s"], window_s=tr["window_s"],
        programs={n: [sum(1 for m, _, _ in tr["programs"] if m == n),
                      sum(b - a for m, a, b in tr["programs"] if m == n)]
                  for n in names},
        idle_by_label=tr["idle_by_label"],
    )

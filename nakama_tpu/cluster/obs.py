"""Fleet observability plane: cross-node trace stitching, metrics/SLO
federation, and a live health-rule engine over the cluster.

PRs 10-12 made the system a real multi-node fleet, but every
observability surface stayed node-local: a trace id crosses the bus
(`bus.py` stamps/continues W3C traceparent per frame) yet its spans
land in each node's private `TRACES` store, the console answers only
for its own process, and the only fleet-wide SLO view
(`loadgen.judge.merge_tables`) lived inside the bench driver. This
module is the read-side counterpart to the PR 10-12 write-side planes
— ONE pane of glass, assembled on a config-designated collector node
(``cluster.obs_collector``, default the device-owner / first shard
owner), following the Dapper model of collector-assembled cross-
process traces and the Monarch/Prometheus-federation model of
hierarchical metric aggregation:

1. **Trace stitching** — every node ships its tail-sampled kept-trace
   fragments (summaries + spans, bounded batches off the kept-ring
   cursor) as ``obs.frag`` frames; the collector groups fragments by
   trace id into one fleet trace (frontend admission → `mm.add`
   forward → owner pool/cohort → publish-back `route` → delivery),
   annotating each span with its origin node and a per-peer
   clock-offset estimate from pull-RTT midpoints, so cross-node
   ordering is honest: skew is shown, never hidden. Per-hop bus
   latency comes from the send-side wall stamp the bus now carries on
   every frame.

2. **Metrics + SLO federation** — a BusRpc ``obs.pull`` (riding the
   PR 12 correlated request/response layer) fetches every node's
   metric families, SLO burn tables, shard/lease map, replication
   lag, device-telemetry summary and live loadgen counts on the
   collector's cadence; `/v2/console/fleet` serves the merged view
   (scenario SLO tables merged with the judge's `merge_tables`, now
   live in the product instead of bench-only), with per-node
   staleness marked when a peer is DOWN or a pull failed.

3. **Health-rule engine** — a small declarative rule table (burn rate
   over threshold, replication lag past the checkpoint interval,
   lease in GRACE/EXPIRED, unexpected XLA recompiles, breaker open,
   peer DOWN, stale node) evaluated on the pull cadence, emitting a
   bounded alert ledger + ``fleet_alerts{rule,severity}`` gauges and
   an OK/WARN/CRITICAL fleet-status roll-up. Alerts are events with
   first-seen / last-seen / heal timestamps — one log line on raise,
   one on heal, never log spam. Thresholds are config-tunable
   (``cluster.obs_rules``).

Everything ships/pulls OFF the hot path: the exporter and collector
run their own cadence tasks, the node-side cost with no collector
configured is one None check, and the `obs.frag`/`obs.pull` fault
points let chaos prove that armed drops degrade to stale-marked views
and never wedge a node (`fleet_obs_overhead_regression` in bench.py
gates the disarmed cost under 1% of the interval headline).
"""

from __future__ import annotations

import asyncio
import time
from collections import OrderedDict

from .. import faults
from ..config import OBS_RULE_KEYS
from ..logger import Logger
from ..tracing import TRACES, Ledger
from .ops import BusRpc, ClusterOpError

# Severity encoding (fleet_status gauge; alert severities).
OK, WARN, CRITICAL = 0, 1, 2
STATUS_NAMES = {OK: "ok", WARN: "warn", CRITICAL: "critical"}

# Tunable rule thresholds (cluster.obs_rules overrides; the key list
# is shared with config.check() so typos fail loudly at boot).
DEFAULT_RULES = {
    # Per-SLO 1h error-budget burn (SloRecorder windows) over this →
    # WARN. 1.0 = budget spent exactly at its sustainable pace.
    "burn_1h_max": 1.0,
    # Per-scenario 1h burn on the MERGED soak table over this → WARN.
    "scenario_burn_1h_max": 1.0,
    # Owner→standby replication backlog age over this → WARN. 0 =
    # derive from the node's own checkpoint interval (the PR 11 bound:
    # a standby more than one checkpoint behind is not warm).
    "replication_lag_max_s": 0.0,
    # Unexpected post-warmup XLA recompiles over this → WARN (the
    # devobs "shape churn became a p99 spike" alarm, fleet-wide).
    "recompiles_max": 0.0,
    # A pull/fragment feed older than this marks the node STALE in
    # every federated view (and raises node_stale while it lasts).
    "stale_after_ms": 10_000.0,
    # Reshard planner triggers (0 = the trigger is off; the planner
    # itself only runs when cluster.reshard.enabled). Skew: hottest
    # owner's ticket count over the owner mean; HBM: per-owner devobs
    # ledger bytes; burn: merged-scenario 1h budget burn.
    "reshard_skew_max": 0.0,
    "reshard_hbm_max_bytes": 0.0,
    "reshard_burn_1h_max": 0.0,
}
assert set(DEFAULT_RULES) == set(OBS_RULE_KEYS)


def parse_rules(specs) -> dict:
    """``name=value`` entries (config.cluster.obs_rules, already
    validated by config.check()) → threshold overrides."""
    out = {}
    for spec in specs or ():
        key, sep, value = spec.partition("=")
        if sep and key in DEFAULT_RULES:
            try:
                out[key] = float(value)
            except ValueError:
                continue
    return out


# ---------------------------------------------------------- trace export


class TraceFragmentExporter:
    """Node side: incremental reads of the process-wide kept-trace
    ring (`TRACES.kept_since`), shipped to the collector as bounded
    ``obs.frag`` frames. The collector's own fragments take the same
    path minus the bus (``local_sink``). Costs nothing on the hot path
    — the exporter runs on the obs cadence task, and with no target at
    all `maybe_ship` is one None check (the posture bench.py's
    `fleet_obs_overhead_regression` budgets)."""

    def __init__(self, bus, node: str, collector: str,
                 logger: Logger, metrics=None, *, max_batch: int = 64,
                 local_sink: "FleetTraceStore | None" = None):
        self.bus = bus
        self.node = node
        # Ship target: None when this node IS the collector (fragments
        # land in local_sink) — and both None when obs is unwired.
        self.target = collector if collector != node else None
        self.local_sink = local_sink
        self.logger = logger.with_fields(subsystem="cluster.obs")
        self.metrics = metrics
        self.max_batch = max(1, int(max_batch))
        self._cursor = 0
        self.shipped = 0
        self.dropped = 0
        self.evicted = 0

    def maybe_ship(self) -> int:
        """Ship newly-kept trace fragments; returns how many. The
        armed ``obs.frag`` point costs the BATCH (drop and raise modes
        both advance the cursor — frame-loss posture: the collector's
        view goes stale-marked, the node never wedges, and fresh
        traces heal the feed after disarm)."""
        if self.target is None and self.local_sink is None:
            return 0  # the disarmed one-None-check posture
        cursor, records, evicted = TRACES.kept_since(
            self._cursor, self.max_batch
        )
        self._cursor = cursor
        if evicted:
            self.evicted += evicted
        if not records:
            return 0
        try:
            if faults.fire("obs.frag"):
                self._count("dropped", len(records))
                return 0
        except Exception as e:
            self._count("dropped", len(records))
            self.logger.warn(
                "trace fragment ship failed", error=str(e),
                fragments=len(records),
            )
            return 0
        frags = [self._fragment(rec) for rec in records]
        if self.local_sink is not None:
            for frag in frags:
                self.local_sink.ingest(self.node, frag)
            self.local_sink.note_batch(self.node, evicted)
            self._count("shipped", len(frags))
            return len(frags)
        sent = self.bus.send(
            self.target,
            "obs.frag",
            {"frags": frags, "evicted": evicted, "t": time.time()},
        )
        self._count("shipped" if sent else "dropped", len(frags))
        return len(frags) if sent else 0

    def _count(self, outcome: str, n: int) -> None:
        if outcome == "shipped":
            self.shipped += n
        else:
            self.dropped += n
        if self.metrics is not None:
            try:
                self.metrics.obs_fragments.labels(outcome=outcome).inc(n)
            except Exception:
                pass

    @staticmethod
    def _fragment(rec: dict) -> dict:
        """One kept-trace record → the wire fragment (summary fields +
        span bodies; the store's per-trace span cap already bounds
        it)."""
        return {
            "trace_id": rec.get("trace_id", ""),
            "root": rec.get("root", ""),
            "status": rec.get("status", "ok"),
            "reason": rec.get("reason", ""),
            "duration_ms": rec.get("duration_ms"),
            "truncated": bool(rec.get("truncated")),
            "n_spans": rec.get("n_spans", 0),
            "ts": rec.get("ts"),
            "spans": list(rec.get("spans") or ()),
        }

    def stats(self) -> dict:
        return {
            "target": self.target or ("local" if self.local_sink else None),
            "cursor": self._cursor,
            "shipped": self.shipped,
            "dropped": self.dropped,
            "evicted": self.evicted,
        }


# -------------------------------------------------------- trace stitching


class FleetTraceStore:
    """Collector side: fragments grouped by trace id into one fleet
    trace. Bounded (`capacity` traces, `max_spans` spans each —
    truncation flagged, never silent); per-node fragment-feed ages
    drive the staleness marks on the console."""

    def __init__(self, capacity: int = 256, max_spans: int = 512):
        self.capacity = max(1, int(capacity))
        self.max_spans = max(8, int(max_spans))
        self._traces: OrderedDict[str, dict] = OrderedDict()
        self.frag_at: dict[str, float] = {}  # node -> last batch wall
        self.fragments = 0
        self.span_drops = 0
        self.evicted_reported = 0  # node-side kept-ring losses, surfaced

    def note_batch(self, node: str, evicted: int = 0) -> None:
        self.frag_at[node] = time.time()
        self.evicted_reported += max(0, int(evicted))

    def ingest(self, node: str, frag: dict) -> None:
        tid = frag.get("trace_id") or ""
        if not tid:
            return
        entry = self._traces.get(tid)
        if entry is None:
            entry = {
                "trace_id": tid,
                "ts": frag.get("ts") or time.time(),
                "status": "ok",
                "nodes": {},
                "roots": {},
                "spans": [],  # (origin_node, span dict)
                "truncated": False,
            }
            self._traces[tid] = entry
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)
        self._traces.move_to_end(tid)
        entry["nodes"][node] = {
            "reason": frag.get("reason", ""),
            "n_spans": frag.get("n_spans", 0),
            "duration_ms": frag.get("duration_ms"),
            "truncated": bool(frag.get("truncated")),
        }
        if frag.get("status") == "error":
            entry["status"] = "error"
        if frag.get("truncated"):
            entry["truncated"] = True
        if frag.get("root"):
            entry["roots"][node] = frag["root"]
        for sp in frag.get("spans") or ():
            if len(entry["spans"]) >= self.max_spans:
                self.span_drops += 1
                entry["truncated"] = True
                break
            entry["spans"].append((node, sp))
        self.fragments += 1

    def __len__(self) -> int:
        return len(self._traces)

    def frag_ages_ms(self) -> dict[str, float]:
        now = time.time()
        return {
            node: round((now - at) * 1000.0, 1)
            for node, at in self.frag_at.items()
        }

    def summaries(self, n: int = 32) -> list[dict]:
        """Newest-first stitched-trace summaries (no span bodies)."""
        out = []
        for entry in reversed(self._traces.values()):
            if len(out) >= n:
                break
            spans = entry["spans"]
            t0 = t1 = None
            for _, sp in spans:
                s = sp.get("startTimeUnixNano", 0) / 1e9
                e = sp.get("endTimeUnixNano", 0) / 1e9
                t0 = s if t0 is None else min(t0, s)
                t1 = e if t1 is None else max(t1, e)
            out.append(
                {
                    "trace_id": entry["trace_id"],
                    "root": self._root_name(entry),
                    "status": entry["status"],
                    "nodes": sorted(entry["nodes"]),
                    "stitched": len(entry["nodes"]) > 1,
                    "n_spans": len(spans),
                    "extent_ms": (
                        round((t1 - t0) * 1000.0, 3)
                        if t0 is not None
                        else None
                    ),
                    "truncated": entry["truncated"],
                    "ts": entry["ts"],
                }
            )
        return out

    @staticmethod
    def _root_name(entry: dict) -> str:
        """The fleet trace's display root: the span no other fragment
        parents (the frontend's envelope root), else the earliest."""
        spans = entry["spans"]
        ids = {sp.get("spanId") for _, sp in spans}
        orphans = [
            sp for _, sp in spans
            if not sp.get("parentSpanId")
            or sp.get("parentSpanId") not in ids
        ]
        pool = orphans or [sp for _, sp in spans]
        if not pool:
            return next(iter(entry["roots"].values()), "")
        pool.sort(key=lambda sp: sp.get("startTimeUnixNano", 0))
        return pool[0].get("name", "")

    def stitched(self, trace_id: str,
                 offsets_s: dict[str, float] | None = None) -> dict | None:
        """One fleet trace as a stitched tree: every span annotated
        with its origin node and that node's clock-offset estimate
        (skew SHOWN, not hidden — adjusted timestamps are additional
        fields, the raw ones stay), plus the cross-node hops with
        per-hop bus latency from the frame's send-side wall stamp."""
        entry = self._traces.get(trace_id)
        if entry is None:
            return None
        offsets_s = offsets_s or {}
        by_id: dict[str, tuple[str, dict]] = {}
        spans = []
        for node, sp in entry["spans"]:
            off = float(offsets_s.get(node, 0.0))
            annotated = {
                **sp,
                "originNode": node,
                "clockOffsetMs": round(off * 1000.0, 3),
                "adjStartUnixNano": int(
                    sp.get("startTimeUnixNano", 0) + off * 1e9
                ),
            }
            spans.append(annotated)
            sid = sp.get("spanId")
            if sid:
                by_id[sid] = (node, annotated)
        spans.sort(key=lambda s: s["adjStartUnixNano"])
        hops = []
        for sp in spans:
            parent = by_id.get(sp.get("parentSpanId") or "")
            if parent is None or parent[0] == sp["originNode"]:
                continue
            from_node, parent_sp = parent
            start_adj = sp["adjStartUnixNano"] / 1e9
            sent_at = (sp.get("attributes") or {}).get("bus_sent_at")
            if sent_at is not None:
                # True bus latency: receiver dispatch start (receiver
                # clock, offset-adjusted) minus the frame's send wall
                # stamp (sender clock, offset-adjusted).
                base = float(sent_at) + float(
                    offsets_s.get(from_node, 0.0)
                )
                basis = "frame_sent"
            else:
                base = parent_sp["adjStartUnixNano"] / 1e9
                basis = "parent_start"
            hops.append(
                {
                    "from": from_node,
                    "to": sp["originNode"],
                    "span": sp.get("name", ""),
                    "latency_ms": round((start_adj - base) * 1000.0, 3),
                    "basis": basis,
                }
            )
        return {
            "trace_id": trace_id,
            "status": entry["status"],
            "stitched": len(entry["nodes"]) > 1,
            "root": self._root_name(entry),
            "nodes": {
                node: {
                    **info,
                    "clock_offset_ms": round(
                        float(offsets_s.get(node, 0.0)) * 1000.0, 3
                    ),
                }
                for node, info in entry["nodes"].items()
            },
            "truncated": entry["truncated"],
            "hops": hops,
            "spans": spans,
        }

    def delivery_chain(self, trace_id: str,
                       offsets_s: dict[str, float] | None = None
                       ) -> list[str]:
        """The stitched trace as a printable chain: one line per span
        in adjusted time order, hops annotated with their bus latency."""
        tree = self.stitched(trace_id, offsets_s)
        if tree is None:
            return []
        hop_by_span = {
            (h["to"], h["span"]): h for h in tree["hops"]
        }
        lines = []
        for sp in tree["spans"]:
            hop = hop_by_span.get((sp["originNode"], sp.get("name", "")))
            hop_txt = (
                f"  [hop {hop['from']}->{hop['to']}"
                f" {hop['latency_ms']}ms ({hop['basis']})]"
                if hop
                else ""
            )
            lines.append(
                f"{sp['originNode']:>12s}  {sp.get('name', ''):<32s}"
                f" {sp.get('durationMs', 0):>9.3f}ms"
                f" off={sp['clockOffsetMs']}ms{hop_txt}"
            )
        return lines

    def stats(self) -> dict:
        return {
            "traces": len(self._traces),
            "fragments": self.fragments,
            "span_drops": self.span_drops,
            "evicted_reported": self.evicted_reported,
            "frag_age_ms": self.frag_ages_ms(),
        }


# ------------------------------------------------------------ health rules


class HealthRuleEngine:
    """Declarative fleet health rules over the federated view.

    `evaluate` diffs the desired alert set against the active one:
    new conditions raise (one WARN log line + ledger event), persisting
    ones update last_seen, vanished ones heal (one log line + ledger
    event with the heal timestamp). The active set and the bounded
    event ledger are the console surface; `fleet_alerts{rule,severity}`
    and `fleet_status` are the scrapeable one."""

    def __init__(self, thresholds: dict | None, logger: Logger,
                 metrics=None):
        self.thresholds = {**DEFAULT_RULES, **(thresholds or {})}
        self.logger = logger.with_fields(subsystem="cluster.obs.rules")
        self.metrics = metrics
        self.active: dict[tuple[str, str], dict] = {}
        self.ledger = Ledger(256)
        self.evaluations = 0
        self._published: set[tuple[str, str]] = set()
        # Extra condition sources: callables yielding the same
        # (rule, subject, severity, detail) tuples as `_desired` —
        # subsystems with state the view doesn't carry (the reshard
        # planner's active plan) get first-class raise→heal alerts.
        self.extra_sources: list = []

    # -------------------------------------------------------- rule table

    def _desired(self, view: dict):
        """Yield (rule, subject, severity, detail) for every condition
        the current view violates."""
        th = self.thresholds
        nodes = view.get("nodes") or {}
        for name, info in nodes.items():
            if info.get("state") == "down":
                yield (
                    "peer_down", name, CRITICAL,
                    "peer DOWN (membership); views serve last-known"
                    " data marked stale",
                )
                continue  # down subsumes staleness and data rules
            if info.get("stale"):
                yield (
                    "node_stale", name, WARN,
                    f"no successful pull for {info.get('age_ms')}ms",
                )
            data = info.get("data") or {}
            burn = (data.get("slo") or {}).get("burn_rates") or {}
            for slo, windows in burn.items():
                b1h = float((windows or {}).get("1h", 0.0))
                if b1h > th["burn_1h_max"]:
                    yield (
                        "burn_rate", f"{name}:{slo}", WARN,
                        f"1h burn {b1h} > {th['burn_1h_max']}",
                    )
            repl = (data.get("cluster") or {}).get("replication") or {}
            lag_s = float(repl.get("lag_sec", 0.0) or 0.0)
            if repl and repl.get("standby"):
                lag_max = th["replication_lag_max_s"] or float(
                    data.get("checkpoint_interval_sec") or 60.0
                )
                if lag_s > lag_max:
                    yield (
                        "replication_lag", name, WARN,
                        f"backlog age {lag_s:.1f}s > {lag_max:.0f}s"
                        " (standby falling behind one checkpoint)",
                    )
            rec = float(
                (data.get("devobs") or {}).get("recompiles_total", 0)
                or 0
            )
            if rec > th["recompiles_max"]:
                yield (
                    "recompiles", name, WARN,
                    f"{int(rec)} unexpected XLA recompiles past the"
                    " warmup window",
                )
            for bname, state in (data.get("breakers") or {}).items():
                if state == "open":
                    yield (
                        "breaker_open", f"{name}:{bname}", WARN,
                        f"{bname} circuit open (degraded fallback"
                        " serving)",
                    )
        for shard, info in (view.get("shards") or {}).items():
            lease = info.get("lease")
            if lease == "grace":
                yield (
                    "lease_grace", shard, WARN,
                    f"owner {info.get('node')} silent past lease_ms"
                    f" ({info.get('silent_s')}s)",
                )
            elif lease == "expired":
                yield (
                    "lease_expired", shard, CRITICAL,
                    f"owner {info.get('node')} lease expired past"
                    " grace — shard promotable/unserved",
                )
        for scenario, row in (view.get("slo_merged") or {}).items():
            b1h = float(row.get("burn_1h", 0.0) or 0.0)
            if b1h > th["scenario_burn_1h_max"]:
                yield (
                    "scenario_burn", scenario, WARN,
                    f"merged 1h burn {b1h} >"
                    f" {th['scenario_burn_1h_max']}",
                )
        for source in self.extra_sources:
            try:
                yield from source()
            except Exception as e:
                self.logger.warn(
                    "extra health-condition source error", error=str(e)
                )

    # -------------------------------------------------------- evaluation

    def evaluate(self, view: dict) -> int:
        self.evaluations += 1
        now = time.time()
        desired: dict[tuple[str, str], tuple[int, str]] = {}
        for rule, subject, severity, detail in self._desired(view):
            desired[(rule, subject)] = (severity, detail)
        for key, (severity, detail) in desired.items():
            alert = self.active.get(key)
            if alert is None:
                alert = {
                    "rule": key[0],
                    "subject": key[1],
                    "severity": STATUS_NAMES[severity],
                    "detail": detail,
                    "first_seen": now,
                    "last_seen": now,
                    "healed_at": None,
                    "rounds": 1,
                }
                self.active[key] = alert
                self.ledger.append(
                    {"event": "raised", **{k: alert[k] for k in (
                        "rule", "subject", "severity", "detail",
                    )}}
                )
                self.logger.warn(
                    "fleet health alert raised",
                    rule=key[0], subject=key[1],
                    severity=alert["severity"], detail=detail,
                )
            else:
                alert["last_seen"] = now
                alert["severity"] = STATUS_NAMES[severity]
                alert["detail"] = detail
                alert["rounds"] += 1
        for key in [k for k in self.active if k not in desired]:
            alert = self.active.pop(key)
            alert["healed_at"] = now
            self.ledger.append(
                {
                    "event": "healed",
                    "rule": alert["rule"],
                    "subject": alert["subject"],
                    "severity": alert["severity"],
                    "active_for_s": round(
                        now - alert["first_seen"], 1
                    ),
                }
            )
            self.logger.info(
                "fleet health alert healed",
                rule=alert["rule"], subject=alert["subject"],
                active_for_s=round(now - alert["first_seen"], 1),
            )
        self._publish()
        return self.status()

    def status(self) -> int:
        worst = OK
        for alert in self.active.values():
            sev = (
                CRITICAL if alert["severity"] == "critical" else WARN
            )
            worst = max(worst, sev)
        return worst

    def _publish(self) -> None:
        if self.metrics is None:
            return
        counts: dict[tuple[str, str], int] = {}
        for alert in self.active.values():
            key = (alert["rule"], alert["severity"])
            counts[key] = counts.get(key, 0) + 1
        try:
            for key in self._published - set(counts):
                self.metrics.fleet_alerts.labels(
                    rule=key[0], severity=key[1]
                ).set(0)
            for key, n in counts.items():
                self.metrics.fleet_alerts.labels(
                    rule=key[0], severity=key[1]
                ).set(n)
            self._published = set(counts)
            self.metrics.fleet_status.set(self.status())
        except Exception:
            pass

    def stats(self) -> dict:
        return {
            "status": STATUS_NAMES[self.status()],
            "thresholds": dict(self.thresholds),
            "active": sorted(
                self.active.values(),
                key=lambda a: (a["severity"], a["rule"], a["subject"]),
            ),
            "recent_events": self.ledger.recent(32),
            "evaluations": self.evaluations,
            "events_total": self.ledger.total,
        }


# --------------------------------------------------------------- collector


class FleetCollector:
    """Collector side: the ``obs.pull`` fan-out on its own cadence
    task, per-node last-known snapshots with staleness ages, per-peer
    clock-offset EMAs from pull-RTT midpoints, the merged scenario SLO
    table, and one rule-engine evaluation per round. A failed pull
    costs that round's freshness for that node — last-known data
    serves, marked stale; the loop never wedges."""

    OFFSET_EMA = 0.3

    def __init__(self, rpc: BusRpc, membership, directory, node: str,
                 snapshot_fn, engine: HealthRuleEngine,
                 store: FleetTraceStore, logger: Logger, metrics=None,
                 *, pull_ms: int = 2000):
        self.rpc = rpc
        self.membership = membership
        self.directory = directory
        self.node = node
        self.snapshot_fn = snapshot_fn
        self.engine = engine
        self.store = store
        self.logger = logger.with_fields(subsystem="cluster.obs")
        self.metrics = metrics
        self.pull_s = max(0.1, pull_ms / 1000.0)
        self.snapshots: dict[str, dict] = {}
        self.offsets_s: dict[str, float] = {node: 0.0}
        self.pulls_ok = 0
        self.pulls_failed = 0
        self.rounds = 0
        self.status = OK
        # ReshardPlanner (set by the plane when cluster.reshard is
        # enabled): ticked once per pull round, AFTER evaluation — the
        # planner's decisions read the same view the rules just judged.
        self.planner = None
        self._task: asyncio.Task | None = None

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._loop())

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None

    async def _loop(self) -> None:
        while True:
            try:
                await self.pull_round()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # The collector loop must survive anything a snapshot
                # section or a metrics sink throws.
                self.logger.error("fleet obs pull error", error=str(e))
            await asyncio.sleep(self.pull_s)

    async def pull_round(self) -> None:
        """One federation round: local snapshot + obs.pull every UP
        peer (concurrently), then rule evaluation + gauges."""
        self.rounds += 1
        try:
            self.snapshots[self.node] = {
                "data": self.snapshot_fn(),
                "at": time.monotonic(),
                "ok": True,
            }
            self.pulls_ok += 1
        except Exception as e:
            self.pulls_failed += 1
            self.logger.warn("local obs snapshot failed", error=str(e))
        peers = sorted(self.membership.state)
        if peers:
            await asyncio.gather(
                *(self._pull_one(p) for p in peers)
            )
        view = self.view()
        self.status = self.engine.evaluate(view)
        self._publish(view)
        if self.planner is not None:
            try:
                await self.planner.tick(view)
            except Exception as e:
                # A planner round must never cost the collector loop.
                self.logger.warn(
                    "reshard planner tick error", error=str(e)
                )

    async def _pull_one(self, peer: str) -> None:
        if not self.membership.is_up(peer):
            return  # down is membership's (and peer_down's) story
        t0 = time.time()
        try:
            data = await self.rpc.call(
                peer, "obs.pull", {},
                timeout=max(1.0, self.pull_s * 1.5),
            )
        except ClusterOpError as e:
            self.pulls_failed += 1
            if self.metrics is not None:
                try:
                    self.metrics.obs_pulls.labels(
                        outcome=e.kind or "error"
                    ).inc()
                except Exception:
                    pass
            return  # last-known snapshot keeps serving, marked stale
        t1 = time.time()
        self.pulls_ok += 1
        if self.metrics is not None:
            try:
                self.metrics.obs_pulls.labels(outcome="ok").inc()
            except Exception:
                pass
        # NTP-style offset estimate, in the COLLECTOR-minus-peer
        # convention stitched() consumes (adding the offset to a
        # peer's raw timestamp expresses it in collector time): the
        # RTT midpoint is when the peer read its wall clock, so
        # midpoint - peer_wall is the correction. EMA-smoothed; shown
        # on every stitched span from that node.
        wall = float(data.get("wall") or t1)
        sample = self._offset_sample(wall, t0, t1)
        prev = self.offsets_s.get(peer)
        self.offsets_s[peer] = (
            sample
            if prev is None
            else prev + self.OFFSET_EMA * (sample - prev)
        )
        self.snapshots[peer] = {
            "data": data,
            "at": time.monotonic(),
            "ok": True,
        }

    @staticmethod
    def _offset_sample(peer_wall: float, t0: float, t1: float) -> float:
        """One clock-offset observation, collector-minus-peer: a peer
        whose clock runs AHEAD yields a NEGATIVE offset, and
        `peer_timestamp + offset` is that moment on the collector's
        clock — the correction stitched() applies."""
        return (t0 + t1) / 2.0 - peer_wall

    # ------------------------------------------------------------- views

    def _stale_after_s(self) -> float:
        return self.engine.thresholds["stale_after_ms"] / 1000.0

    def view(self) -> dict:
        """The federated view the rules evaluate and the console
        serves: per-node state/age/staleness + last-known data, the
        collector's shard/lease map, and the merged scenario table."""
        now = time.monotonic()
        stale_after = self._stale_after_s()
        nodes: dict[str, dict] = {}
        names = set(self.membership.state) | {self.node} | set(
            self.snapshots
        )
        for name in sorted(names):
            snap = self.snapshots.get(name)
            age_ms = (
                round((now - snap["at"]) * 1000.0, 1)
                if snap is not None
                else None
            )
            if name == self.node:
                state = "self"
            elif self.membership.is_up(name):
                state = "up"
            elif name in self.membership.down_peers():
                state = "down"
            else:
                state = "unknown"
            nodes[name] = {
                "state": state,
                "age_ms": age_ms,
                "stale": (
                    age_ms is None or age_ms > stale_after * 1000.0
                ),
                "data": snap["data"] if snap is not None else None,
            }
        tables = []
        for info in nodes.values():
            table = (info["data"] or {}).get("scenario_table")
            if table:
                tables.append(table)
        merged = {}
        if tables:
            from ..loadgen.judge import merge_tables

            merged = merge_tables(tables)
        return {
            "nodes": nodes,
            "shards": self.directory.snapshot(),
            "slo_merged": merged,
        }

    def _publish(self, view: dict) -> None:
        if self.metrics is None:
            return
        try:
            view_nodes = view["nodes"]
            fresh = stale = down = 0
            for info in view_nodes.values():
                if info["state"] == "down":
                    down += 1
                elif info["stale"]:
                    stale += 1
                else:
                    fresh += 1
            self.metrics.fleet_nodes.labels(state="fresh").set(fresh)
            self.metrics.fleet_nodes.labels(state="stale").set(stale)
            self.metrics.fleet_nodes.labels(state="down").set(down)
            self.metrics.obs_stitched_traces.set(len(self.store))
            for node, off in self.offsets_s.items():
                self.metrics.fleet_clock_offset_ms.labels(
                    node=node
                ).set(round(off * 1000.0, 3))
        except Exception:
            pass

    def console(self) -> dict:
        """The `/v2/console/fleet` body."""
        view = self.view()
        nodes = {}
        for name, info in view["nodes"].items():
            nodes[name] = {
                "state": info["state"],
                "age_ms": info["age_ms"],
                "stale": info["stale"],
                "clock_offset_ms": round(
                    self.offsets_s.get(name, 0.0) * 1000.0, 3
                ),
                "data": info["data"],
            }
        out = {
            "status": STATUS_NAMES[self.status],
            "nodes": nodes,
            "shards": view["shards"],
            "generation": self.directory.generation,
            "slo_merged": view["slo_merged"],
            "alerts": self.engine.stats(),
            "pulls": {
                "ok": self.pulls_ok,
                "failed": self.pulls_failed,
                "rounds": self.rounds,
                "cadence_ms": int(self.pull_s * 1000),
            },
            "traces": self.store.stats(),
        }
        if self.planner is not None:
            out["reshard"] = self.planner.stats()
        return out


# ------------------------------------------------------------------ plane


def resolve_collector(config) -> str:
    """The collector node: explicit ``cluster.obs_collector``, else
    the device-owner / first shard owner — the node every ticket
    already flows through, so the stitched story needs no extra hop."""
    cc = config.cluster
    return (
        cc.obs_collector
        or (cc.shards[0] if cc.shards else "")
        or cc.device_owner
        or (config.name if cc.role == "device_owner" else "")
        or cc.standby_of
        or config.name
    )


class FleetObsPlane:
    """Server-facing assembly: the exporter on every node, the
    collector stack (trace store + pull loop + rule engine) on the
    designated node, and the ``obs.pull`` snapshot handler everywhere.
    """

    def __init__(self, server, rpc: BusRpc):
        self.server = server
        cluster = server.cluster
        config = server.config
        cc = config.cluster
        self.node = cluster.node
        self.logger = server.logger.with_fields(subsystem="cluster.obs")
        self.metrics = server.metrics
        self.collector_name = resolve_collector(config)
        self.is_collector = self.collector_name == self.node
        self.pull_ms = cc.obs_pull_ms
        rpc.register("obs.pull", self._on_pull)
        thresholds = parse_rules(cc.obs_rules)
        self.store: FleetTraceStore | None = None
        self.engine: HealthRuleEngine | None = None
        self.collector: FleetCollector | None = None
        self.planner = None  # ReshardPlanner, collector-only
        if self.is_collector:
            self.store = FleetTraceStore(
                capacity=cc.obs_trace_capacity
            )
            self.engine = HealthRuleEngine(
                thresholds, self.logger, self.metrics
            )
            self.collector = FleetCollector(
                rpc,
                cluster.membership,
                cluster.directory,
                self.node,
                self.node_snapshot,
                self.engine,
                self.store,
                self.logger,
                self.metrics,
                pull_ms=self.pull_ms,
            )
            cluster.bus.on("obs.frag", self._on_frag)
            if cc.reshard.enabled:
                import os

                from .reshard import ReshardPlanner

                self.planner = ReshardPlanner(
                    self.node,
                    cluster.directory,
                    rpc,
                    self.logger,
                    rules=self.engine.thresholds,
                    journal_path=os.path.join(
                        config.data_dir, "reshard_plan.json"
                    ),
                    local_migrator=cluster.migrator,
                    plan_timeout_s=max(
                        30.0, 4 * cc.reshard.handover_timeout_ms / 1000.0
                    ),
                )
                # One raise→heal ledger entry per executed plan.
                self.engine.extra_sources.append(self.planner.conditions)
                self.collector.planner = self.planner
        self.exporter = TraceFragmentExporter(
            cluster.bus,
            self.node,
            self.collector_name,
            self.logger,
            self.metrics,
            max_batch=cc.obs_frag_max,
            local_sink=self.store,
        )
        self._task: asyncio.Task | None = None

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(
            self._export_loop()
        )
        if self.collector is not None:
            self.collector.start()
        self.logger.info(
            "fleet observability enabled",
            collector=self.collector_name,
            is_collector=self.is_collector,
            pull_ms=self.pull_ms,
            rules=(
                self.engine.thresholds
                if self.engine is not None
                else None
            ),
        )

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
        if self.collector is not None:
            self.collector.stop()

    async def _export_loop(self) -> None:
        # Fragment export rides the SAME cadence as the collector's
        # pull loop: freshness within one pull round is all the
        # console promises, and a tighter loop just burns the one-core
        # lab's CPU on JSON it could batch.
        cadence = self.pull_ms / 1000.0
        while True:
            try:
                self.exporter.maybe_ship()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.logger.error(
                    "trace fragment export error", error=str(e)
                )
            await asyncio.sleep(cadence)

    # ---------------------------------------------------------- handlers

    def _on_frag(self, src: str, d: dict) -> None:
        for frag in d.get("frags") or ():
            self.store.ingest(src, frag)
        self.store.note_batch(src, int(d.get("evicted", 0) or 0))

    def _on_pull(self, src: str, body: dict) -> dict:
        if faults.fire("obs.pull"):
            raise faults.InjectedFault("obs.pull")
        return self.node_snapshot()

    # ----------------------------------------------------- node snapshot

    def node_snapshot(self) -> dict:
        """Everything the collector federates from this node, built
        best-effort: a broken section names itself in
        ``section_errors`` instead of costing the whole snapshot."""
        s = self.server
        out: dict = {
            "node": self.node,
            "role": s.config.cluster.role,
            "wall": time.time(),
            "checkpoint_interval_sec": (
                s.config.recovery.checkpoint_interval_sec
            ),
            "section_errors": {},
        }

        def section(name, fn):
            try:
                out[name] = fn()
            except Exception as e:
                out["section_errors"][name] = str(e)

        section("metrics", lambda: s.metrics.snapshot())
        section(
            "slo",
            lambda: s.slo.snapshot() if s.slo is not None else {},
        )
        section("cluster", lambda: s.cluster.stats())
        section(
            "matchmaker_tickets", lambda: len(s.matchmaker)
        )
        section(
            "overload",
            lambda: (
                s.overload.stats()["level"]
                if s.overload is not None
                else "off"
            ),
        )
        section("devobs", self._devobs_summary)
        section("breakers", self._breaker_states)
        engine = getattr(s, "soak_engine", None)
        if engine is not None:
            section("scenario_table", lambda: engine.judge.table())
            section("loadgen", lambda: engine.stats())
        return out

    def _devobs_summary(self) -> dict:
        from ..devobs import DEVOBS

        st = DEVOBS.stats()
        return {
            "compiles_total": st["compiles"]["total"],
            "recompiles_total": st["compiles"]["recompiles_total"],
            "memory_total_bytes": st["memory"]["total_bytes"],
            "memory_high_water_bytes": (
                st["memory"]["high_water_bytes"]
            ),
        }

    def _breaker_states(self) -> dict:
        s = self.server
        out = {}
        breaker = s.matchmaker.backend.breaker
        if breaker is not None:
            out["matchmaker_backend"] = breaker.state
        device = getattr(s.leaderboards, "device", None)
        if device is not None and getattr(device, "breaker", None):
            out["leaderboard_device"] = device.breaker.state
        return out

    # ------------------------------------------------------------- views

    def console_fleet(self) -> dict:
        base = {
            "enabled": True,
            "collector": self.collector_name,
            "is_collector": self.is_collector,
            "exporter": self.exporter.stats(),
        }
        if self.collector is None:
            base["hint"] = (
                f"fleet views are assembled on {self.collector_name!r}"
                " — query its console"
            )
            return base
        return {**base, **self.collector.console()}

    def console_traces(self, n: int = 32) -> dict:
        base = {
            "enabled": True,
            "collector": self.collector_name,
            "is_collector": self.is_collector,
        }
        if self.store is None:
            base["hint"] = (
                f"stitched traces live on {self.collector_name!r}"
            )
            base["traces"] = []
            return base
        return {
            **base,
            "traces": self.store.summaries(n),
            "stats": self.store.stats(),
        }

    def console_trace_get(self, trace_id: str) -> dict | None:
        if self.store is None:
            return None
        offsets = (
            self.collector.offsets_s
            if self.collector is not None
            else {}
        )
        return self.store.stitched(trace_id, offsets)

    def stats(self) -> dict:
        out = {
            "collector": self.collector_name,
            "is_collector": self.is_collector,
            "exporter": self.exporter.stats(),
        }
        if self.store is not None:
            out["store"] = self.store.stats()
        if self.engine is not None:
            out["status"] = STATUS_NAMES[self.engine.status()]
            out["active_alerts"] = len(self.engine.active)
        return out

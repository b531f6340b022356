"""Tracing + profiling + the SLO plane.

The reference ships none (SURVEY §5: OpenCensus remnants commented out,
api.go:190) and the survey sets a higher bar for the TPU build. Three
layers live here:

1. **Breadcrumbs + ledgers** (`Tracing`): the aggregate, always-on
   layer, every stamp on `time.perf_counter()`. Three records of the
   matchmaker live here, tied by ids:

   - the **interval record**: one breadcrumb per `process_slots` call
     (`open_crumb` → `record`), with its `seq`, `_pc_start`/`_pc_end`,
     the stage sums inside it (`flush_s` … `callback_s`), the `seq` of
     the cohort it dispatched (`cohort_seq`) and the add stages of
     every `mm.add` since the previous interval (`AddStages`: `adds`,
     `add_parse_s`, `add_register_s`, …);
   - the **gap record**: one breadcrumb of `kind: "gap"` per pass of
     the interval loop's idle-gap maintenance (`local.py _gap_pass`),
     among the interval crumbs: when the loop woke for it and how late
     (`wake_late_s`), whether backpressure shed it, the cohorts in
     flight, its stages (`count_s`, `drain_s`, `gc_s`, `flush_s`,
     `checkpoint_s`) and the loop thread's CPU over it (`cpu_s`);
   - the **cohort record**: one delivery-ledger row per pipelined
     cohort, made by `matchmaker.tpu.Cohort.row()` from the stamps the
     cohort carried from dispatch to accept (`device_done_lag_s`,
     `fetch_lag_s`, `ready_lag_s`, `collect_lag_s`, `accept_lag_s`,
     each an unrounded lag since `_pc_dispatch`), with its `seq` and
     the `interval_seq` of the interval that dispatched it;
   - the **delivery call**: what the `process()` / `collect_pipelined()`
     call that shipped cohorts did after accept, stamped in place on
     the row of the oldest cohort it shipped (`deliver_remove_s`,
     the `publish_*_s` stages and counts of
     `on_matched`, `delivery_held_s`), beside each row's
     `publish_lag_s`.

   Wall time alone cannot tell a stage that ran slowly from one that
   stood waiting, so the delivery call's handler and the worker's
   assembly carry the thread's own CPU beside it (`cpu_stamp` /
   `cpu_split`: `publish_cpu_s`, `publish_offcpu_s`,
   `publish_other_cpu_s`, `publish_invol_switches`,
   `publish_minor_faults`; `assemble_cpu_s`, `assemble_offcpu_s`), and
   a cohort's row says how long gap passes held the loop while it was
   in flight (`gap_in_flight_s`).

   The coarse sites also open a `jax.profiler.TraceAnnotation`
   (`annotate`), so a captured profile shows them on the host lines
   beside the device's. The other ledgers (db drains, breaker and
   overload transitions) are bounded event lists. Every ledger is a
   `Ledger`: a bounded deque plus a monotonic `total` counter, so "how
   many ever" questions never read a saturated deque length.

2. **Request-scoped distributed traces** (module API + `TraceStore`):
   Dapper-style spans carried in a contextvar alongside overload.py's
   Deadline. The front doors ingest W3C `traceparent` and emit it on
   responses; `span()` / `root_span()` create real spans (parent
   linkage, status, attributes, events, links); completed traces land
   in the process-wide bounded `TRACES` store under **tail-based
   sampling** — error traces and slow-over-threshold traces are kept
   100%, the rest are p-sampled deterministically by trace id. The
   console serves them at `/v2/console/traces`; an optional JSONL
   export writes each kept trace as one line.

3. **SLO burn rates** (`SloRecorder`): multi-window (5m/1h) error-budget
   burn over api latency, matchmaker interval time, and delivery
   publish lag, published as `slo_burn_rate{slo,window}` gauges and
   optionally fed into the OverloadController ladder
   (overload.slo_burn_signal).

The disarmed posture (no ambient trace on the caller) costs one
contextvar read per instrumentation point; `bench.py --trace-overhead`
measures it against the <1% interval budget.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import random
import threading
import time
import zlib
from collections import Counter, OrderedDict, deque

try:  # the calling thread's own switches and faults: Linux
    from resource import RUSAGE_THREAD, getrusage
except ImportError:  # pragma: no cover - a platform without it
    RUSAGE_THREAD = None

# Per-boot salt for the p-sampling hash (see TraceStore._p_sample).
# Cluster deployments may override it with a fleet-shared secret
# (config.tracing.sample_salt) so every node keeps the SAME p-sampled
# trace ids — without that, a cross-node trace's fragments survive
# tail sampling independently per node and the fleet collector can
# only stitch the error/slow-kept ones.
_SAMPLE_SALT = os.urandom(8)

# --------------------------------------------------------------- ledgers


class Ledger:
    """Bounded event deque + monotonic `total` counter — the general
    form of the old `deliveries`/`deliveries_total` pair: once the
    bounded deque fills, its length stops moving, so "how many did this
    call add" and "how many ever" questions must read the counter, and
    every ledger now answers them correctly."""

    __slots__ = ("_items", "total")

    def __init__(self, capacity: int = 256):
        self._items: deque[dict] = deque(maxlen=capacity)
        self.total = 0

    def append(self, item: dict) -> None:
        item.setdefault("ts", time.time())
        self._items.append(item)
        self.total += 1

    def recent(self, n: int = 32) -> list[dict]:
        return list(self._items)[-n:]

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def __iter__(self):
        return iter(self._items)

    def __reversed__(self):
        return reversed(self._items)

    def __getitem__(self, idx):
        return self._items[idx]


# ------------------------------------------------------ W3C traceparent

_TP_VERSION = "00"

# Ids need uniqueness, not cryptographic strength: Mersenne Twister
# seeded from urandom is ~20x cheaper than uuid4 (~0.7µs vs ~14µs on
# this host), and the cohort path mints ids every interval.
_ids = random.Random(int.from_bytes(os.urandom(16), "big"))


def new_trace_id() -> str:
    return f"{_ids.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"{_TP_VERSION}-{trace_id}-{span_id}-01"


def parse_traceparent(value: str) -> tuple[str, str]:
    """`00-<32 hex trace-id>-<16 hex span-id>-<2 hex flags>` → (trace_id,
    span_id). Raises ValueError on malformed input (the front door
    ignores it and starts a fresh trace — a bad header must never 500 a
    request)."""
    parts = value.strip().split("-")
    if len(parts) != 4:
        raise ValueError(f"malformed traceparent: {value!r}")
    _, trace_id, span_id, flags = parts
    if (
        len(trace_id) != 32
        or len(span_id) != 16
        or len(flags) != 2
        or trace_id == "0" * 32
        or span_id == "0" * 16
    ):
        raise ValueError(f"malformed traceparent: {value!r}")
    int(trace_id, 16), int(span_id, 16), int(flags, 16)  # hex-validate
    return trace_id, span_id


# ----------------------------------------------------------------- spans


class Span:
    """One operation in a trace: identity + parent linkage, wall-clock
    bounds, attributes, events, links to other traces, and a status.
    Mutable until `end()`; cheap by design (plain slots, no registry
    work until the span finishes)."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name",
        "start_ts", "end_ts", "_pc0",
        "attrs", "events", "links", "status", "message",
    )

    def __init__(
        self,
        trace_id: str,
        span_id: str,
        parent_id: str,
        name: str,
        attrs: dict | None = None,
        start_ts: float | None = None,
    ):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ts = time.time() if start_ts is None else start_ts
        self._pc0 = time.perf_counter()
        self.end_ts: float | None = None
        self.attrs = dict(attrs) if attrs else {}
        self.events: list[dict] = []
        self.links: list[dict] = []
        self.status = "ok"
        self.message = ""

    def set_attribute(self, key: str, value) -> None:
        self.attrs[key] = value

    def add_event(self, name: str, **attrs) -> None:
        self.events.append({"name": name, "ts": time.time(), **attrs})

    def add_link(self, trace_id: str, span_id: str = "", **attrs) -> None:
        link = {"trace_id": trace_id, "span_id": span_id}
        if attrs:
            link.update(attrs)
        self.links.append(link)

    def set_status(self, status: str, message: str = "") -> None:
        self.status = status
        if message:
            self.message = message

    def end(self) -> None:
        if self.end_ts is None:
            self.end_ts = self.start_ts + (time.perf_counter() - self._pc0)

    @property
    def duration_ms(self) -> float:
        end = self.end_ts
        if end is None:
            end = self.start_ts + (time.perf_counter() - self._pc0)
        return (end - self.start_ts) * 1000.0

    def as_dict(self) -> dict:
        """OTLP-ish span shape (camelCase ids/times; attributes kept as
        a flat dict rather than the keyValue list for readability)."""
        out = {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentSpanId": self.parent_id,
            "name": self.name,
            "startTimeUnixNano": int(self.start_ts * 1e9),
            "endTimeUnixNano": int(
                (self.end_ts if self.end_ts is not None else self.start_ts)
                * 1e9
            ),
            "durationMs": round(self.duration_ms, 3),
            "status": {"code": self.status.upper(), "message": self.message},
        }
        if self.attrs:
            out["attributes"] = self.attrs
        if self.events:
            out["events"] = self.events
        if self.links:
            out["links"] = self.links
        return out


# The propagation channel: follows a request through every awaited call
# on its task (and through explicit copies into worker threads), exactly
# like overload.py's deadline contextvar.
_current_span: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "nakama_current_span", default=None
)


def current_span() -> Span | None:
    return _current_span.get()


def current_trace_ids() -> tuple[str, str] | None:
    """(trace_id, span_id) of the active span, or None — the logger's
    correlation hook (one contextvar read per log line)."""
    sp = _current_span.get()
    if sp is None:
        return None
    return sp.trace_id, sp.span_id


def current_traceparent() -> str | None:
    sp = _current_span.get()
    if sp is None:
        return None
    return format_traceparent(sp.trace_id, sp.span_id)


def add_event(name: str, **attrs) -> None:
    """Attach an event to the active span; no-op without one."""
    sp = _current_span.get()
    if sp is not None:
        sp.add_event(name, **attrs)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Real child span under the active span. Yields the Span (set
    attributes/events/status on it) or None when there is no active
    trace or tracing is disabled — the disarmed fast path is one
    contextvar read."""
    parent = _current_span.get()
    if parent is None or not TRACES.enabled:
        yield None
        return
    sp = Span(parent.trace_id, new_span_id(), parent.span_id, name, attrs)
    token = _current_span.set(sp)
    try:
        yield sp
    except BaseException as e:
        sp.set_status("error", f"{type(e).__name__}: {e}")
        raise
    finally:
        _current_span.reset(token)
        sp.end()
        TRACES.add_span(sp)


@contextlib.contextmanager
def root_span(name: str, traceparent: str = "", **attrs):
    """Root span of a new trace (or a child continuing an ingested W3C
    `traceparent`). On exit the trace is submitted for tail-based
    sampling — unless holds (`TRACES.hold`) keep it open for deferred
    spans (a matchmaker ticket waiting to match)."""
    if not TRACES.enabled:
        yield None
        return
    parent_span = ""
    trace_id = ""
    if traceparent:
        try:
            trace_id, parent_span = parse_traceparent(traceparent)
        except ValueError:
            trace_id = ""
    if not trace_id:
        trace_id = new_trace_id()
    sp = Span(trace_id, new_span_id(), parent_span, name, attrs)
    token = _current_span.set(sp)
    try:
        yield sp
    except BaseException as e:
        sp.set_status("error", f"{type(e).__name__}: {e}")
        raise
    finally:
        _current_span.reset(token)
        sp.end()
        TRACES.add_span(sp)
        TRACES.root_done(sp)


def emit_span(
    trace_id: str,
    parent_id: str,
    name: str,
    *,
    start_ts: float,
    end_ts: float,
    status: str = "ok",
    message: str = "",
    links: list[dict] | None = None,
    **attrs,
) -> None:
    """Record an already-finished span into `trace_id` post-hoc — how
    the matchmaker attaches cohort stage timings (dispatch→ready→
    collected→published) to a ticket's trace after the fact, from
    ledger timestamps instead of live context."""
    if not TRACES.enabled:
        return
    sp = Span(trace_id, new_span_id(), parent_id, name, attrs,
              start_ts=start_ts)
    if links:
        sp.links = list(links)
    if status != "ok":
        sp.set_status(status, message)
    sp.end_ts = max(start_ts, end_ts)
    TRACES.add_span(sp)


def emit_trace(
    name: str,
    *,
    start_ts: float,
    end_ts: float,
    status: str = "ok",
    message: str = "",
    links: list[dict] | None = None,
    **attrs,
) -> str:
    """Record a complete single-span trace post-hoc (the storage
    group-commit span: one root per drain, its batched units attached
    as span links). Returns the trace id ("" when disabled)."""
    if not TRACES.enabled:
        return ""
    sp = Span(new_trace_id(), new_span_id(), "", name, attrs,
              start_ts=start_ts)
    if links:
        sp.links = list(links)
    if status != "ok":
        sp.set_status(status, message)
    sp.end_ts = max(start_ts, end_ts)
    TRACES.add_span(sp)
    TRACES.root_done(sp)
    return sp.trace_id


# ------------------------------------------------------------ trace store


class _ActiveTrace:
    __slots__ = ("spans", "root", "holds", "started", "dropped")

    def __init__(self):
        self.spans: list[Span] = []
        self.root: Span | None = None
        self.holds = 0
        self.started = time.time()
        self.dropped = 0  # spans past the per-trace cap: counted


class TraceStore:
    """Process-wide bounded trace sink with tail-based sampling (one
    per process like faults.PLANE — spans are recorded via the
    contextvar from every subsystem, so the sink must be reachable
    without threading an instance through each of them).

    In-flight spans buffer per trace id; when the root span finishes
    (and any holds are released) the whole trace is judged at once:

    - any span with status "error"        → kept ("error")
    - root duration >= `slow_ms`          → kept ("slow")
    - otherwise                           → kept with probability
      `sample_rate`, decided deterministically from the trace id
      ("sampled"), else dropped (span data discarded, counters kept).

    Bounded everywhere: `max_active` in-flight traces (oldest evicted
    and finalized early), `max_spans` per trace (extra spans counted,
    not stored), `capacity` kept traces."""

    # One source of truth for the defaults: __init__ AND reset() both
    # apply these, so a future default change cannot drift between them
    # (reset() exists precisely to kill suite-order coupling).
    DEFAULTS = {
        "enabled": True,
        "capacity": 256,
        "sample_rate": 0.01,
        "slow_ms": 1000.0,
        "max_active": 512,
        "max_spans": 64,
    }

    def __init__(self, **overrides):
        self._lock = threading.Lock()
        self._export_file = None
        self._apply_defaults(overrides)

    def _apply_defaults(self, overrides: dict | None = None) -> None:
        cfg = {**self.DEFAULTS, **(overrides or {})}
        self.enabled = cfg["enabled"]
        self.capacity = cfg["capacity"]
        self.sample_rate = cfg["sample_rate"]
        self.slow_ms = cfg["slow_ms"]
        self.max_active = cfg["max_active"]
        self.max_spans = cfg["max_spans"]
        self.metrics = None
        if self._export_file is not None:
            try:
                self._export_file.close()
            except OSError:
                pass
            self._export_file = None
        self.export_path = ""
        self._active: OrderedDict[str, _ActiveTrace] = OrderedDict()
        # Tombstones of finalized trace ids (bounded): late spans for a
        # closed trace are counted and dropped, never allowed to
        # resurrect an active entry — resurrection double-finalizes the
        # trace and leaves rootless orphans squatting in the buffer.
        self._closed: OrderedDict[str, None] = OrderedDict()
        self.late_spans = 0
        # Kept records whose JSONL export is pending: the file write
        # happens OUTSIDE the lock (see _drain_export) so a slow disk
        # can never serialize the request plane behind it.
        self._export_pending: list[dict] = []
        self.kept: deque[dict] = deque(maxlen=self.capacity)
        self.finished_total = 0
        self.kept_total = 0
        self.kept_by: Counter = Counter()

    def configure(
        self,
        *,
        enabled: bool | None = None,
        capacity: int | None = None,
        sample_rate: float | None = None,
        slow_ms: float | None = None,
        max_active: int | None = None,
        max_spans: int | None = None,
        export_path: str | None = None,
        sample_salt: str | None = None,
        metrics=None,
    ) -> None:
        global _SAMPLE_SALT
        if sample_salt:
            # Fleet-shared sampling salt: every node judges a trace id
            # the same way, so cross-node fragments live or die
            # together (the stitching prerequisite). Still a secret
            # w.r.t. clients — traceparent senders cannot mint
            # always-kept ids without knowing it.
            _SAMPLE_SALT = sample_salt.encode()
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if capacity is not None and capacity != self.capacity:
                self.capacity = max(1, int(capacity))
                self.kept = deque(self.kept, maxlen=self.capacity)
            if sample_rate is not None:
                self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
            if slow_ms is not None:
                self.slow_ms = float(slow_ms)
            if max_active is not None:
                self.max_active = max(1, int(max_active))
            if max_spans is not None:
                self.max_spans = max(1, int(max_spans))
            if export_path is not None and export_path != self.export_path:
                if self._export_file is not None:
                    try:
                        self._export_file.close()
                    except OSError:
                        pass
                    self._export_file = None
                self.export_path = export_path
            if metrics is not None:
                self.metrics = metrics

    def reset(self) -> None:
        """Drop all state AND restore the constructor-default config.
        The store is process-global, so a reset that kept the previous
        caller's sampling posture would make test outcomes depend on
        suite order."""
        with self._lock:
            self._apply_defaults()

    # -------------------------------------------------------- recording

    def _entry(self, trace_id: str) -> _ActiveTrace:
        entry = self._active.get(trace_id)
        if entry is None:
            entry = _ActiveTrace()
            self._active[trace_id] = entry
            while len(self._active) > self.max_active:
                # Evict the oldest in-flight trace and judge it as-is
                # (attrs mark the truncation) — a leak of held traces
                # must never grow the buffer without bound.
                old_id, old = self._active.popitem(last=False)
                self._finalize(old_id, old, truncated=True)
        return entry

    def add_span(self, sp: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            if sp.trace_id in self._closed:
                # A late span for an already-finalized trace (evicted
                # under hold pressure, or released by the expiry
                # sweep): counted, never resurrected.
                self.late_spans += 1
                return
            entry = self._entry(sp.trace_id)
            if len(entry.spans) < self.max_spans:
                entry.spans.append(sp)
            else:
                entry.dropped += 1
        self._drain_export()

    def hold(self, trace_id: str) -> None:
        """Keep `trace_id` open past its root's end — deferred spans
        (matchmaker cohort stages) arrive later; `release` closes it."""
        if not self.enabled:
            return
        with self._lock:
            if trace_id in self._closed:
                return
            self._entry(trace_id).holds += 1
        self._drain_export()

    def release(self, trace_id: str) -> None:
        with self._lock:
            entry = self._active.get(trace_id)
            if entry is None:
                return
            entry.holds -= 1
            if entry.holds <= 0 and entry.root is not None:
                self._active.pop(trace_id, None)
                self._finalize(trace_id, entry)
        self._drain_export()

    def root_done(self, sp: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            entry = self._active.get(sp.trace_id)
            if entry is None:
                return
            entry.root = sp
            if entry.holds <= 0:
                self._active.pop(sp.trace_id, None)
                self._finalize(sp.trace_id, entry)
        self._drain_export()

    # --------------------------------------------------------- sampling

    @staticmethod
    def _p_sample(trace_id: str, rate: float) -> bool:
        """Deterministic per trace id WITHIN a process (tests need no
        seed plumbing; a trace is judged the same every time), but
        salted per boot: trace ids can be client-supplied via
        traceparent, and an unsalted prefix hash would let any caller
        mint always-kept ids and churn genuine error traces out of the
        bounded kept ring."""
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        h = zlib.crc32(_SAMPLE_SALT + trace_id.encode())
        return (h / 0xFFFFFFFF) < rate

    def _finalize(
        self, trace_id: str, entry: _ActiveTrace, truncated: bool = False
    ) -> None:
        # Called with the lock held.
        self._closed[trace_id] = None
        while len(self._closed) > 4096:
            self._closed.popitem(last=False)
        self.finished_total += 1
        root = entry.root
        # Slow is judged on the FULL span extent, not the root alone:
        # held traces (a cohort's dispatch→published, a ticket's
        # add→matched) carry their duration in post-hoc spans appended
        # long after the root span ended.
        extent_ms = 0.0
        if entry.spans:
            t0 = min(s.start_ts for s in entry.spans)
            t1 = max(
                (s.end_ts if s.end_ts is not None else s.start_ts)
                for s in entry.spans
            )
            extent_ms = (t1 - t0) * 1000.0
        reason = None
        if any(s.status == "error" for s in entry.spans):
            reason = "error"
        elif extent_ms >= self.slow_ms:
            reason = "slow"
        elif self._p_sample(trace_id, self.sample_rate):
            reason = "sampled"
        decision = f"kept_{reason}" if reason else "dropped"
        if self.metrics is not None:
            try:
                self.metrics.traces_sampled.labels(decision=decision).inc()
            except Exception:
                pass
        if reason is None:
            return
        self.kept_total += 1
        self.kept_by[reason] += 1
        record = {
            "trace_id": trace_id,
            "root": root.name if root is not None else "",
            # Wall extent over ALL spans (a held trace's story runs
            # long past its root span's end).
            "duration_ms": round(extent_ms, 3) if entry.spans else None,
            "status": (
                "error"
                if any(s.status == "error" for s in entry.spans)
                else "ok"
            ),
            "reason": reason,
            # Either form of loss is flagged: evicted-early from the
            # active buffer, or spans dropped past the per-trace cap —
            # a missing stage span must read as truncation, not as the
            # stage never having happened.
            "truncated": truncated or entry.dropped > 0,
            "spans_dropped": entry.dropped,
            "n_spans": len(entry.spans),
            "ts": entry.started,
            "spans": [s.as_dict() for s in entry.spans],
        }
        self.kept.append(record)
        if self.export_path:
            self._export_pending.append(record)

    def _drain_export(self) -> None:
        """Write pending kept records to the JSONL export OUTSIDE the
        lock — called by the public entry points after releasing it, so
        a slow disk never serializes span recording behind a write."""
        if not self.export_path:
            return
        while True:
            with self._lock:
                if not self._export_pending:
                    return
                record = self._export_pending.pop(0)
            try:
                if self._export_file is None:
                    self._export_file = open(
                        self.export_path, "a", buffering=1
                    )
                self._export_file.write(json.dumps(record) + "\n")
            except OSError:
                self.export_path = ""  # dead sink: stop paying for it
                return

    # ------------------------------------------------------------ reads

    def list(self, n: int = 32) -> list[dict]:
        """Newest-first kept-trace summaries (no span bodies)."""
        with self._lock:
            out = [
                {k: v for k, v in rec.items() if k != "spans"}
                for rec in list(self.kept)[-n:]
            ]
        out.reverse()
        return out

    def get(self, trace_id: str) -> dict | None:
        """Full kept trace in the OTLP-ish shape, or None."""
        with self._lock:
            for rec in reversed(self.kept):
                if rec["trace_id"] == trace_id:
                    return {
                        **{k: v for k, v in rec.items() if k != "spans"},
                        "resourceSpans": [
                            {"scopeSpans": [{"spans": rec["spans"]}]}
                        ],
                    }
        return None

    def kept_since(self, cursor: int, limit: int = 64) -> tuple[int, list[dict], int]:
        """Kept-trace records appended after `cursor` (a `kept_total`
        watermark), oldest first, at most `limit` — the fleet-obs
        exporter's incremental read. Returns ``(new_cursor, records,
        evicted)``: `evicted` counts records that aged out of the
        bounded ring before this read (the exporter surfaces them as
        loss, never silence). Records are the store's own dicts —
        callers must not mutate them."""
        with self._lock:
            total = self.kept_total
            if cursor >= total:
                return total, [], 0
            ring_start = total - len(self.kept)
            start = max(cursor, ring_start)
            evicted = start - cursor
            take = list(self.kept)[start - ring_start:]
            if limit and len(take) > limit:
                take = take[:limit]
            return start + len(take), take, evicted

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "sample_rate": self.sample_rate,
                "slow_ms": self.slow_ms,
                "finished_total": self.finished_total,
                "kept_total": self.kept_total,
                "kept_by": dict(self.kept_by),
                "active": len(self._active),
                "retained": len(self.kept),
                "late_spans": self.late_spans,
            }


# The process-wide store (faults.PLANE precedent): configured by
# server.py from config.tracing; tests reset/configure it directly.
TRACES = TraceStore()


def emit_matched_spans(
    ctx: tuple[str, str],
    entry: dict | None,
    *,
    cohort_trace: str = "",
    published: bool = True,
) -> None:
    """Close a matched ticket's trace: synthesize the cohort stage
    spans (dispatch→ready→collected→published) from the delivery-ledger
    entry into the ticket's own trace, link the cohort's trace, and
    release the hold taken at `matchmaker.add`. The whole add→matched
    story then reads off ONE trace id."""
    trace_id, parent = ctx
    now = time.time()
    if entry is not None:
        base = entry.get("dispatched_ts") or now
        umbrella = Span(
            trace_id, new_span_id(), parent, "matchmaker.matched",
            start_ts=base,
        )
        umbrella.end_ts = now
        link_trace = cohort_trace or entry.get("trace_id") or ""
        if link_trace:
            umbrella.add_link(link_trace, kind="cohort")
        if entry.get("slipped"):
            umbrella.set_attribute("slipped", True)
        TRACES.add_span(umbrella)
        stages = (
            ("matchmaker.dispatch_to_ready", entry.get("ready_lag_s")),
            ("matchmaker.collected", entry.get("collect_lag_s")),
            ("matchmaker.published", entry.get("publish_lag_s")),
        )
        if not published:
            stages = stages[:-1]
        for name, lag in stages:
            if lag is None:
                continue
            emit_span(
                trace_id, umbrella.span_id, name,
                start_ts=base, end_ts=base + float(lag),
            )
    TRACES.release(trace_id)


# ------------------------------------------------------------- SLO plane


class SloRecorder:
    """Multi-window (5m/1h) error-budget burn-rate recorder.

    Each SLO is (target, threshold): an observation is *good* when its
    value is at/under the threshold; the burn rate over a window is
    `bad_fraction / (1 - target)` — burn 1.0 spends the budget exactly
    at its sustainable pace, 14+ is the classic page-now fast burn.
    Ring-bucketed at 10s over one hour: O(1) observes, O(buckets)
    reads (the ladder samples at ~4Hz, so reads are off the hot path).
    """

    BUCKET_S = 10
    N_BUCKETS = 360  # one hour of 10s buckets
    WINDOWS = (("5m", 300), ("1h", 3600))

    def __init__(self, slos: dict[str, dict], metrics=None):
        # slos: name -> {"target": 0.99, "threshold_ms": 200}
        self.slos = {
            name: {
                "target": float(spec.get("target", 0.99)),
                "threshold_ms": float(spec.get("threshold_ms", 0.0)),
            }
            for name, spec in slos.items()
        }
        self.metrics = metrics
        self._lock = threading.Lock()
        n = self.N_BUCKETS
        self._good = {name: [0] * n for name in self.slos}
        self._bad = {name: [0] * n for name in self.slos}
        self._epoch = {name: [-1] * n for name in self.slos}

    def observe(self, name: str, value_ms: float) -> None:
        spec = self.slos.get(name)
        if spec is None:
            return
        self.observe_good(name, value_ms <= spec["threshold_ms"])

    def observe_good(self, name: str, good: bool) -> None:
        if name not in self.slos:
            return
        b = int(time.monotonic() // self.BUCKET_S)
        i = b % self.N_BUCKETS
        with self._lock:
            if self._epoch[name][i] != b:
                self._epoch[name][i] = b
                self._good[name][i] = 0
                self._bad[name][i] = 0
            if good:
                self._good[name][i] += 1
            else:
                self._bad[name][i] += 1

    def burn_rate(self, name: str, window_s: int) -> float:
        spec = self.slos.get(name)
        if spec is None:
            return 0.0
        budget = max(1e-9, 1.0 - spec["target"])
        b_now = int(time.monotonic() // self.BUCKET_S)
        k = max(1, min(self.N_BUCKETS, window_s // self.BUCKET_S))
        good = bad = 0
        with self._lock:
            for back in range(k):
                b = b_now - back
                i = b % self.N_BUCKETS
                if self._epoch[name][i] == b:
                    good += self._good[name][i]
                    bad += self._bad[name][i]
        total = good + bad
        if total == 0:
            return 0.0
        return (bad / total) / budget

    def burn_rates(self) -> dict[str, dict[str, float]]:
        return {
            name: {
                label: round(self.burn_rate(name, w), 3)
                for label, w in self.WINDOWS
            }
            for name in self.slos
        }

    def sample(self) -> dict[str, dict[str, float]]:
        """Compute all burn rates and publish the gauges — called from
        the overload ladder's sampling loop and the console, never per
        request."""
        rates = self.burn_rates()
        if self.metrics is not None:
            for name, windows in rates.items():
                for label, value in windows.items():
                    try:
                        self.metrics.slo_burn_rate.labels(
                            slo=name, window=label
                        ).set(value)
                    except Exception:
                        pass
        return rates

    def max_burn(self, window: str = "5m") -> float:
        w = dict(self.WINDOWS)[window]
        return max(
            (self.burn_rate(name, w) for name in self.slos), default=0.0
        )

    def snapshot(self) -> dict:
        return {
            "slos": self.slos,
            "burn_rates": self.burn_rates(),
        }


# ------------------------------------------------- aggregate Tracing obj


_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once first used


def annotate(name: str):
    """A host span named `name` in a captured `jax.profiler` trace, on
    the thread that opens it. For the coarse sites of a backend that
    runs on JAX only (a handful a tick and a cohort): with no capture
    running it costs a flag read."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


def cpu_stamp() -> tuple:
    """The calling thread's account at one point: the wall clock, the
    thread's own CPU, the whole process's CPU and, where the platform
    keeps them by the thread, its involuntary context switches and
    minor page faults. Two of these around a stage, once a cohort,
    never a match; `cpu_split` makes the row keys."""
    usage = getrusage(RUSAGE_THREAD) if RUSAGE_THREAD is not None else None
    return time.perf_counter(), time.thread_time(), time.process_time(), usage


def cpu_split(stage: str, before: tuple, after: tuple) -> dict:
    """Was the thread running or waiting between two `cpu_stamp`s?
    `<stage>_cpu_s` is its own CPU; `_offcpu_s` the wall less that (it
    waited for the GIL, was descheduled or blocked); `_other_cpu_s` the
    CPU the process's other threads burned meanwhile;
    `_invol_switches` and `_minor_faults` the times it was taken off
    its core and the pages it touched first (left out where the
    platform does not count them by the thread)."""
    wall0, cpu0, process0, usage0 = before
    wall1, cpu1, process1, usage1 = after
    cpu = cpu1 - cpu0
    out = {
        f"{stage}_cpu_s": cpu,
        f"{stage}_offcpu_s": wall1 - wall0 - cpu,
        f"{stage}_other_cpu_s": process1 - process0 - cpu,
    }
    if usage1 is not None:
        out[f"{stage}_invol_switches"] = usage1.ru_nivcsw - usage0.ru_nivcsw
        out[f"{stage}_minor_faults"] = usage1.ru_minflt - usage0.ru_minflt
    return out


class AddStages:
    """Where `mm.add` and its envelope spent their time since the last
    interval record, summed: `Tracing.record` folds these into the
    interval breadcrumb and starts them again from zero."""

    __slots__ = (
        "adds", "adds_enveloped", "pipeline_s", "parse_s", "register_s",
        "journal_s", "trace_s", "last_s", "_in_envelope",
    )

    def __init__(self):
        self.last_s = 0.0  # the newest add alone; never folded
        self._in_envelope = {}  # id(session) -> its add's seconds
        self._zero()

    def _zero(self) -> None:
        self.adds = self.adds_enveloped = 0
        self.pipeline_s = self.parse_s = self.register_s = 0.0
        self.journal_s = self.trace_s = 0.0

    def take(self) -> dict:
        """The sums as breadcrumb keys, and a fresh start."""
        out = dict(
            adds=self.adds,
            adds_enveloped=self.adds_enveloped,
            add_pipeline_s=self.pipeline_s,
            add_parse_s=self.parse_s,
            add_register_s=self.register_s,
            add_journal_s=self.journal_s,
            add_trace_s=self.trace_s,
        )
        self._zero()
        return out

    def add(self, t0, t_parsed, t_registered, t_journaled, t_end) -> None:
        """One `mm.add` that returned a ticket, by its five stamps."""
        self.adds += 1
        self.parse_s += t_parsed - t0
        self.register_s += t_registered - t_parsed
        self.journal_s += t_journaled - t_registered
        self.trace_s += t_end - t_journaled
        self.last_s = t_end - t0

    def enveloped(self, session) -> None:
        """The `mm.add` that just returned came in `session`'s
        `matchmaker_add` envelope: its time is not the pipeline's."""
        self._in_envelope[id(session)] = self.last_s

    def envelope_done(self, session, seconds: float) -> None:
        """`Pipeline.process` took `seconds` over one `matchmaker_add`
        envelope of `session`. Counted where its `mm.add` returned a
        ticket, less that add; a refused envelope is no add."""
        add_s = self._in_envelope.pop(id(session), None)
        if add_s is not None:
            self.adds_enveloped += 1
            self.pipeline_s += seconds - add_s


class Tracing:
    def __init__(self, config=None, logger=None):
        port = 0
        capacity = 256
        if config is not None:
            port = getattr(config, "profiler_port", 0)
            capacity = getattr(config, "breadcrumb_capacity", 256)
        self.logger = logger
        self._profiler_started = False
        self.breadcrumbs = Ledger(capacity)
        self._crumb_seq = 0
        self.add_stages = AddStages()
        # Per-cohort pipelined delivery ledger (dispatch→delivered lag,
        # deadline slips): slips are observable here and via metrics,
        # not inferred from bench WARN lines.
        self.deliveries = Ledger(capacity)
        # Group-commit drain spans from the storage write batcher
        # (record_db_drain): batch size / drain time / queue depth.
        self.db_drains = Ledger(capacity)
        # Degradation-ladder transitions (faults.py CircuitBreaker) and
        # reclamation events: breaker open/half-open/closed flips plus
        # in-flight cohort reclamations, so an operator can read the
        # outage timeline off the ledger instead of correlating logs.
        self.breaker_events = Ledger(capacity)
        # Overload-ladder transitions (overload.py OverloadController):
        # OK→WARN→SHED flips with the per-signal levels that drove
        # them, so "why did we shed at 14:02" reads off the ledger.
        self.overload_events = Ledger(capacity)
        if port:
            self.start_profiler_server(port)

    @property
    def deliveries_total(self) -> int:
        """Monotonic count of deliveries ever recorded (survives the
        bounded deque filling) — kept as a property for the pre-Ledger
        callers."""
        return self.deliveries.total

    def ledger_totals(self) -> dict:
        """Monotonic "how many ever" count per ledger (console)."""
        return {
            "breadcrumbs": self.breadcrumbs.total,
            "deliveries": self.deliveries.total,
            "db_drains": self.db_drains.total,
            "breaker_events": self.breaker_events.total,
            "overload_events": self.overload_events.total,
        }

    # ------------------------------------------------------ trace server

    def start_profiler_server(self, port: int):
        """Expose the JAX profiler so `tensorboard --logdir` / xprof can
        capture device traces from a live server."""
        import jax

        if self._profiler_started:
            return
        jax.profiler.start_server(port)
        self._profiler_started = True
        if self.logger is not None:
            self.logger.info("jax profiler server started", port=port)

    @contextlib.contextmanager
    def device_trace(self, out_dir: str):
        """Capture one jax.profiler trace around a block (the
        console's on-demand capture)."""
        import jax

        jax.profiler.start_trace(out_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    # ------------------------------------------------------- breadcrumbs

    def open_crumb(self, **fields) -> dict:
        """Start a breadcrumb: its `seq` (what a cohort's `interval_seq`
        names) and `_pc_start`. `record` closes and stores it."""
        self._crumb_seq += 1
        return {
            "seq": self._crumb_seq,
            "_pc_start": time.perf_counter(),
            **fields,
        }

    @contextlib.contextmanager
    def span(self, crumb: dict, key: str, annotation: str, annotate=annotate):
        """Accumulating timing crumb (NOT a request-scoped trace span —
        that is the module-level `span()`): adds elapsed seconds under
        `key` on the aggregate interval breadcrumb, and shows the block
        as `annotation` in a captured profile. A caller that may run on
        a host-only backend hands in the seam's `backend.annotate`."""
        t0 = time.perf_counter()
        try:
            with annotate(annotation):
                yield
        finally:
            crumb[key] = crumb.get(key, 0.0) + time.perf_counter() - t0

    def record(self, crumb: dict, *, interval: bool = False):
        """Close and store a breadcrumb. An `interval` crumb (one per
        `process_slots` call) takes the add stages summed since the
        last one."""
        if interval:
            crumb.update(self.add_stages.take())
        crumb["_pc_end"] = time.perf_counter()
        self.breadcrumbs.append(crumb)

    def recent(self, n: int = 32) -> list[dict]:
        return self.breadcrumbs.recent(n)

    def gap_seconds_since(self, since: float) -> float:
        """Seconds the interval loop's gap passes (crumbs of `kind`
        "gap", not shed) ran at or after perf_counter `since`: a
        cohort's `gap_in_flight_s`, asked at its accept with its
        dispatch stamp. Crumbs are stored as they end, so the walk from
        the newest stops at the first that ended before `since`."""
        total = 0.0
        for crumb in reversed(self.breadcrumbs):
            if crumb["_pc_end"] <= since:
                break
            if crumb.get("kind") == "gap" and crumb.get("shed") is False:
                total += crumb["_pc_end"] - max(since, crumb["_pc_start"])
        return total

    # -------------------------------------------------- cohort deliveries

    def record_delivery(self, **fields) -> dict:
        """One pipelined cohort delivered or lost: the row its record
        makes (`tpu.py Cohort.row`). Kept separate from interval
        breadcrumbs so mid-gap deliveries don't dilute per-interval
        timing rows. Returns the stored row: the publish lag and the
        delivery call's stamps are written on it in place (local.py),
        so holders of the return value see them."""
        self.deliveries.append(fields)
        return fields

    def recent_deliveries(self, n: int = 32) -> list[dict]:
        return self.deliveries.recent(n)

    def delivery_stage_stats(self) -> dict:
        """p50/p99 per delivery stage over the retained ledger — the
        one-call attribution surface (console): a delivery-gap
        regression names its stage here instead of hiding inside a
        single end-to-end number."""
        stages = (  # chain order, each a lag since dispatch
            "device_done_lag_s",
            "fetch_lag_s",
            "ready_lag_s",
            "collect_lag_s",
            "accept_lag_s",
            "publish_lag_s",
        )
        out: dict[str, dict] = {}
        for key in stages:
            vals = sorted(
                d[key]
                for d in self.deliveries
                if isinstance(d.get(key), (int, float))
            )
            if vals:
                out[key] = {
                    "p50": vals[len(vals) // 2],
                    "p99": vals[min(len(vals) - 1, int(len(vals) * 0.99))],
                    "n": len(vals),
                }
        return out

    def slip_count(self) -> int:
        """Deliveries in the retained window that missed their cohort's
        interval deadline."""
        return sum(1 for d in self.deliveries if d.get("slipped"))

    # ---------------------------------------------------- db drain spans

    def record_db_drain(self, **fields):
        """One group-commit drain by the storage write batcher: batch
        size, drain duration, and post-drain queue depth (storage/db.py
        WriteBatcher). A separate ledger so high-rate write drains don't
        evict the interval breadcrumbs."""
        self.db_drains.append(fields)

    def recent_db_drains(self, n: int = 32) -> list[dict]:
        return self.db_drains.recent(n)

    # ------------------------------------------------ degradation ladder

    def record_breaker(self, **fields):
        """One breaker transition or reclamation event (matchmaker
        backend / storage drains): state flip, reason, and counts. Also
        attached as an event to the active trace span, so an error
        trace carries its breaker context inline."""
        sp = _current_span.get()
        if sp is not None:
            sp.add_event("breaker", **fields)
        self.breaker_events.append(fields)

    def recent_breaker_events(self, n: int = 32) -> list[dict]:
        return self.breaker_events.recent(n)

    # ------------------------------------------------- overload ladder

    def record_overload(self, **fields):
        """One overload-ladder transition (overload.py): old/new level
        and the per-signal levels at the sample that drove it."""
        self.overload_events.append(fields)

    def recent_overload_events(self, n: int = 32) -> list[dict]:
        return self.overload_events.recent(n)

"""Device telemetry plane: compile-watch, kernel clocks, HBM ledger.

PR 6 gave every request one trace id down to `device.dispatch`; below
that line the chip was a black box. This plane is the always-on layer
under the host spans, in the continuous-profiling shape of Google-Wide
Profiling (Ren et al., 2010): cheap enough to leave enabled in
production, attributed enough to answer "what changed". One instance
per process (`DEVOBS`, the faults.PLANE / tracing.TRACES precedent) —
device calls happen on interval loops, worker threads, and prewarm
threads, so the sink must be reachable without threading an instance
through each of them. Four instruments:

1. **Compile-watch** — every named jit entry point (matchmaker
   scatter/score/assign, leaderboard flush/rank/sweep) registers here;
   a `jax.monitoring` listener attributes each XLA backend compile to
   the kernel whose `device_call` context is active on the compiling
   thread. Compiles are counted and timed per kernel; once the warmup
   window (`warmup_intervals` interval ticks) closes, a compile inside
   a hot-path context raises an "unexpected recompile" WARN + span
   event + `xla_recompiles_total{kernel}` — shape churn becomes a
   counter, not a mystery p99 spike. Prewarm threads pass
   `expect_compile=True`: compiling ahead of the hot path is the cure,
   not the disease.

2. **Kernel clocks** — per-kernel wall-time stats (count, EMA, p50/p99
   over a bounded ring) around each device call, plus a bounded
   process-wide timeline of (kernel, ts, duration) events the delivery
   ledger slices per cohort (`timeline_between`), so host stage spans
   and device phases read off one record. Wall time here is the time
   the HOST was held by the call: for async-dispatched kernels that is
   dispatch + (re)compile cost — exactly the component that lands in an
   interval's p99 — while the D2H fetch clocks carry the compute+
   transfer tail.

3. **HBM ledger** — ownership-tagged device-buffer accounting
   (`matchmaker.pool`, `matchmaker.dispatch`, `leaderboard.boards`, …)
   registered at alloc/resize/free: `device_memory_bytes{owner}`
   gauges + a process high-watermark, cross-checked against
   `device.memory_stats()` where the backend provides it (TPU runtimes
   do; the CPU backend returns None), plus h2d/d2h transfer counters
   per call site (`device_transfer_bytes{site,direction}`).

4. The console serves all of it at `/v2/console/device` (plus the
   on-demand bounded `jax.profiler` capture reusing
   Tracing.device_trace); `bench.py --device-obs` gates the always-on
   cost under 1% of the 100k interval headline
   (`device_telemetry_overhead_regression`).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque

from . import tracing as trace_api

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Persistent compile cache (jaxenv.enable_compile_cache): one request
# event per compile that consults it, one hit event per compile it saved.
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# Kernel name used for compiles that happen outside any device_call
# context (library warmup, test scaffolding): counted, never judged.
UNATTRIBUTED = "unattributed"


class _KernelClock:
    """Per-named-kernel wall-time stats + compile counters."""

    __slots__ = (
        "name", "calls", "total_s", "ema_s", "ring",
        "compiles", "compile_total_s", "last_compile_s",
        "recompiles", "last_recompile_ts", "_time_child",
    )

    RING = 256
    EMA_ALPHA = 0.1

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.total_s = 0.0
        self.ema_s = 0.0
        self.ring: deque[float] = deque(maxlen=self.RING)
        self.compiles = 0
        self.compile_total_s = 0.0
        self.last_compile_s = 0.0
        self.recompiles = 0
        self.last_recompile_ts = 0.0
        self._time_child = None  # cached labeled histogram child

    def record(self, dur_s: float) -> None:
        self.calls += 1
        self.total_s += dur_s
        self.ring.append(dur_s)
        # EMA seeded by the first sample so early reads aren't dragged
        # toward zero by the initializer.
        if self.calls == 1:
            self.ema_s = dur_s
        else:
            self.ema_s += self.EMA_ALPHA * (dur_s - self.ema_s)

    def stats(self) -> dict:
        vals = sorted(self.ring)
        n = len(vals)
        p50 = vals[n // 2] if n else 0.0
        p99 = vals[min(n - 1, int(n * 0.99))] if n else 0.0
        return {
            "kernel": self.name,
            "calls": self.calls,
            "p50_ms": round(p50 * 1000, 3),
            "p99_ms": round(p99 * 1000, 3),
            "ema_ms": round(self.ema_s * 1000, 3),
            "total_s": round(self.total_s, 3),
            "compiles": self.compiles,
            "compile_total_s": round(self.compile_total_s, 3),
            "last_compile_s": round(self.last_compile_s, 3),
            "recompiles": self.recompiles,
        }


class _Call:
    """Context manager for one timed device call (allocation-light: the
    plane hands these out from `device_call`)."""

    __slots__ = ("plane", "kernel", "expect_compile", "t0")

    def __init__(self, plane, kernel, expect_compile):
        self.plane = plane
        self.kernel = kernel
        self.expect_compile = expect_compile

    def __enter__(self):
        tls = self.plane._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        stack.append((self.kernel, self.expect_compile))
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        plane = self.plane
        plane._tls.stack.pop()
        clock = plane._kernels.get(self.kernel)
        if clock is None:
            clock = plane.register(self.kernel)
        with plane._lock:
            clock.record(dur)
            plane.timeline.append(
                (self.kernel, time.time(), round(dur * 1000, 3))
            )
        child = clock._time_child
        if child is not None:
            try:
                child.observe(dur)
            except Exception:
                pass
        return False


class _NullCall:
    """Disarmed context: two attribute reads, nothing else."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CALL = _NullCall()


class DeviceTelemetry:
    """The process-wide plane. Thread model: `device_call` runs on
    interval loops, cohort worker threads, and prewarm threads
    concurrently, so every read-modify-write on shared state — clock
    fields, transfer entries, the memory ledger, compile bookkeeping —
    happens under `_lock` (augmented assignment is NOT bytecode-atomic;
    two cohort workers sharing the `matchmaker.fetch` clock would
    silently drop increments). Metrics publishes happen outside the
    lock; the hot path is one uncontended acquire per device call."""

    DEFAULTS = {
        "enabled": True,
        "warmup_intervals": 3,
        "timeline_depth": 256,
        "capture_max_ms": 10_000,
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._listener_active = False
        self.metrics = None
        self.logger = None
        self._apply_defaults()

    def _apply_defaults(self, overrides: dict | None = None) -> None:
        cfg = {**self.DEFAULTS, **(overrides or {})}
        self.enabled = bool(cfg["enabled"])
        self.warmup_intervals = max(0, int(cfg["warmup_intervals"]))
        self.timeline_depth = max(16, int(cfg["timeline_depth"]))
        self.capture_max_ms = max(100, int(cfg["capture_max_ms"]))
        self._kernels: dict[str, _KernelClock] = {}
        self.timeline: deque[tuple] = deque(maxlen=self.timeline_depth)
        self.intervals_seen = 0
        self.warmed = self.warmup_intervals == 0
        # HBM ledger: owner -> bytes, plus the total high-watermark.
        self._memory: dict[str, int] = {}
        self.memory_high_water = 0
        # (site, direction) -> [count, bytes]
        self._transfers: dict[tuple[str, str], list[int]] = {}
        self.compiles_total = 0
        self.recompiles_total = 0
        self.cache_requests = 0
        self.cache_hits = 0

    def configure(
        self,
        *,
        enabled: bool | None = None,
        warmup_intervals: int | None = None,
        timeline_depth: int | None = None,
        capture_max_ms: int | None = None,
        metrics=None,
        logger=None,
    ) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if warmup_intervals is not None:
                self.warmup_intervals = max(0, int(warmup_intervals))
                self.warmed = (
                    self.intervals_seen >= self.warmup_intervals
                )
            if timeline_depth is not None and (
                int(timeline_depth) != self.timeline_depth
            ):
                self.timeline_depth = max(16, int(timeline_depth))
                self.timeline = deque(
                    self.timeline, maxlen=self.timeline_depth
                )
            if capture_max_ms is not None:
                self.capture_max_ms = max(100, int(capture_max_ms))
            if metrics is not None:
                self.metrics = metrics
                for clock in self._kernels.values():
                    self._bind_clock_metric(clock)
                # Ledger rows written before this registry existed
                # (the pool allocates at backend construction, the
                # server binds metrics after) republish now.
                try:
                    for owner, nbytes in self._memory.items():
                        metrics.device_memory.labels(owner=owner).set(
                            nbytes
                        )
                    metrics.device_memory_high_water.set(
                        self.memory_high_water
                    )
                except Exception:
                    pass
            if logger is not None:
                self.logger = logger

    def reset(self) -> None:
        """Drop all state AND restore default config (TRACES.reset
        discipline: the plane is process-global, so a reset keeping a
        previous caller's warmup posture would couple test outcomes to
        suite order). Metrics/logger bindings drop too — the next
        server (or test) binds its own."""
        with self._lock:
            self.metrics = None
            self.logger = None
            self._apply_defaults()

    # -------------------------------------------------------- compile-watch

    def _bind_clock_metric(self, clock: _KernelClock) -> None:
        try:
            clock._time_child = self.metrics.device_kernel_time.labels(
                kernel=clock.name
            )
        except Exception:
            clock._time_child = None

    def register(self, kernel: str) -> _KernelClock:
        """Register a named jit entry point (idempotent). Installs the
        process-wide compile listener on first registration with jax
        already imported — host-only deployments that never touch a
        device path never pay the jax import."""
        clock = self._kernels.get(kernel)
        if clock is None:
            with self._lock:
                clock = self._kernels.get(kernel)
                if clock is None:
                    clock = _KernelClock(kernel)
                    if self.metrics is not None:
                        self._bind_clock_metric(clock)
                    self._kernels[kernel] = clock
        self._ensure_listener()
        return clock

    def _ensure_listener(self) -> None:
        if self._listener_active or "jax" not in sys.modules:
            return
        with self._lock:
            if self._listener_active:
                return
            import jax.monitoring as monitoring

            monitoring.register_event_duration_secs_listener(
                _compile_listener
            )
            monitoring.register_event_listener(_cache_listener)
            self._listener_active = True

    def device_call(self, kernel: str, expect_compile: bool = False):
        """Context manager timing one device call under `kernel` and
        attributing any XLA compile fired inside it. Disarmed cost is
        one attribute read + a constant return."""
        if not self.enabled:
            return _NULL_CALL
        return _Call(self, kernel, expect_compile)

    def on_compile(self, duration_s: float) -> None:
        """One XLA backend compile completed on this thread (monitoring
        listener). Attributed to the innermost active device_call."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            kernel, expected = stack[-1]
        else:
            kernel, expected = UNATTRIBUTED, True
        clock = self._kernels.get(kernel)
        if clock is None:
            clock = self.register(kernel)
        with self._lock:
            clock.compiles += 1
            clock.compile_total_s += duration_s
            clock.last_compile_s = duration_s
            self.compiles_total += 1
            unexpected = (
                self.warmed and not expected and kernel != UNATTRIBUTED
            )
            if unexpected:
                clock.recompiles += 1
                clock.last_recompile_ts = time.time()
                self.recompiles_total += 1
        m = self.metrics
        if m is not None:
            try:
                m.xla_compiles.labels(kernel=kernel).inc()
                m.xla_compile_time.observe(duration_s)
                if unexpected:
                    m.xla_recompiles.labels(kernel=kernel).inc()
            except Exception:
                pass
        if unexpected:
            # The compile that would otherwise be a mystery p99 spike:
            # WARN with attribution, and an event on the active trace
            # span so an error/slow-kept trace carries it inline.
            trace_api.add_event(
                "xla.recompile",
                kernel=kernel,
                duration_ms=round(duration_s * 1000, 1),
            )
            if self.logger is not None:
                try:
                    self.logger.warn(
                        "unexpected XLA recompile after warmup —"
                        " a compile shape leaked into the hot path",
                        kernel=kernel,
                        duration_ms=round(duration_s * 1000, 1),
                        intervals_seen=self.intervals_seen,
                    )
                except Exception:
                    pass

    def interval_tick(self) -> None:
        """One processing interval elapsed (matchmaker process_slots).
        Closes the warmup window after `warmup_intervals` ticks."""
        self.intervals_seen += 1
        if not self.warmed and self.intervals_seen >= self.warmup_intervals:
            self.warmed = True

    def mark_warm(self) -> None:
        """Force the warmup window closed (tests, bench)."""
        self.warmed = True

    # ----------------------------------------------------------- HBM ledger

    def _apply_mem_locked(self, owner: str, nbytes: int) -> int:
        """Write one ledger row (caller holds `_lock`); returns the
        clamped value for the gauge publish."""
        if nbytes <= 0:
            self._memory.pop(owner, None)
            nbytes = 0
        else:
            self._memory[owner] = int(nbytes)
        total = sum(self._memory.values())
        if total > self.memory_high_water:
            self.memory_high_water = total
        return nbytes

    def _publish_mem(self, owner: str, nbytes: int) -> None:
        m = self.metrics
        if m is not None:
            try:
                m.device_memory.labels(owner=owner).set(nbytes)
                m.device_memory_high_water.set(self.memory_high_water)
            except Exception:
                pass

    def mem_set(self, owner: str, nbytes: int) -> None:
        """Absolute device-resident bytes held by `owner` (alloc /
        resize / restore all land here; 0 frees the row)."""
        if not self.enabled:
            return
        with self._lock:
            value = self._apply_mem_locked(owner, int(nbytes))
        self._publish_mem(owner, value)

    def mem_add(self, owner: str, delta: int) -> None:
        """Relative adjustment (transient dispatch buffers: + at
        launch, − when the fetch releases them). Read-modify-write
        under ONE lock acquisition: the dispatch thread's + races a
        previous cohort worker's − on the same owner, and a lost
        update would drift the gauge permanently."""
        if not self.enabled or not delta:
            return
        with self._lock:
            value = self._apply_mem_locked(
                owner, self._memory.get(owner, 0) + int(delta)
            )
        self._publish_mem(owner, value)

    def transfer(self, site: str, direction: str, nbytes: int) -> None:
        """One host↔device transfer at a named call site; direction is
        "h2d" or "d2h"."""
        if not self.enabled:
            return
        with self._lock:
            entry = self._transfers.setdefault((site, direction), [0, 0])
            entry[0] += 1
            entry[1] += int(nbytes)
        m = self.metrics
        if m is not None:
            try:
                m.device_transfers.labels(
                    site=site, direction=direction
                ).inc()
                m.device_transfer_bytes.labels(
                    site=site, direction=direction
                ).inc(max(0, int(nbytes)))
            except Exception:
                pass

    @staticmethod
    def backend_memory_stats() -> dict | None:
        """The runtime's own view (`device.memory_stats()`), where the
        backend provides one (TPU plugins do; CPU returns None) — the
        cross-check against the ownership ledger."""
        try:
            import jax

            out = {}
            for d in jax.devices():
                stats = (
                    d.memory_stats() if hasattr(d, "memory_stats")
                    else None
                )
                if stats:
                    out[str(d.id)] = {
                        k: v for k, v in stats.items()
                        if isinstance(v, (int, float))
                    }
            return out or None
        except Exception:
            return None

    # ---------------------------------------------------------------- reads

    def timeline_between(
        self, t0: float, t1: float, limit: int = 64
    ) -> list[dict]:
        """Kernel events whose wall timestamp falls in [t0, t1] —
        how a delivery-ledger entry gets its device phase chain."""
        out = []
        # list(deque) is one C-level copy (GIL-atomic against the
        # worker-thread appends); iterating the live deque is not.
        for kernel, ts, dur_ms in list(self.timeline):
            if t0 <= ts <= t1:
                out.append({"kernel": kernel, "ts": ts, "ms": dur_ms})
                if len(out) >= limit:
                    break
        return out

    def recent_timeline(self, n: int = 64) -> list[dict]:
        return [
            {"kernel": k, "ts": ts, "ms": ms}
            for k, ts, ms in list(self.timeline)[-n:]
        ]

    def memory_by_owner(self) -> dict[str, int]:
        with self._lock:
            return dict(self._memory)

    def kernel_stats(self) -> list[dict]:
        with self._lock:  # registers mutate the dict from any thread
            clocks = sorted(self._kernels.items())
        return [clock.stats() for _, clock in clocks]

    def stats(self) -> dict:
        mem = self.memory_by_owner()
        with self._lock:
            transfer_rows = sorted(
                (k, list(v)) for k, v in self._transfers.items()
            )
        transfers = [
            {
                "site": site,
                "direction": direction,
                "count": entry[0],
                "bytes": entry[1],
            }
            for (site, direction), entry in transfer_rows
        ]
        return {
            "enabled": self.enabled,
            "warmup": {
                "intervals_seen": self.intervals_seen,
                "warmup_intervals": self.warmup_intervals,
                "warmed": self.warmed,
            },
            "kernels": self.kernel_stats(),
            "compiles": {
                "total": self.compiles_total,
                "recompiles_total": self.recompiles_total,
                "listener": self._listener_active,
                "cache_requests": self.cache_requests,
                "cache_hits": self.cache_hits,
            },
            "memory": {
                "by_owner": mem,
                "total_bytes": sum(mem.values()),
                "high_water_bytes": self.memory_high_water,
                "backend": self.backend_memory_stats(),
            },
            "transfers": transfers,
        }

    # ------------------------------------------------------- console report

    def report_lines(self) -> list[str]:
        """The plain-text device report: kernel clocks, HBM owners and
        transfer sites, one table for every script that prints them."""
        s = self.stats()
        lines = ["device telemetry:"]
        lines.append(
            f"  warmup: {s['warmup']['intervals_seen']} intervals seen,"
            f" warmed={s['warmup']['warmed']}"
        )
        lines.append(
            "  kernel                     calls   p50ms   p99ms   emams"
            "  compiles  recompiles"
        )
        for k in s["kernels"]:
            lines.append(
                f"  {k['kernel']:<26} {k['calls']:>5}"
                f" {k['p50_ms']:>7.2f} {k['p99_ms']:>7.2f}"
                f" {k['ema_ms']:>7.2f} {k['compiles']:>9}"
                f" {k['recompiles']:>11}"
            )
        mem = s["memory"]
        lines.append(
            f"  memory: total={mem['total_bytes']:,}B"
            f" high_water={mem['high_water_bytes']:,}B"
        )
        for owner, nbytes in sorted(mem["by_owner"].items()):
            lines.append(f"    {owner:<24} {nbytes:>14,}B")
        for t in s["transfers"]:
            lines.append(
                f"  transfer {t['site']:<24} {t['direction']}"
                f" n={t['count']} bytes={t['bytes']:,}"
            )
        return lines


def _compile_listener(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        DEVOBS.on_compile(duration)


def _cache_listener(event: str, **kw) -> None:
    if event == _CACHE_REQUEST_EVENT:
        with DEVOBS._lock:
            DEVOBS.cache_requests += 1
    elif event == _CACHE_HIT_EVENT:
        with DEVOBS._lock:
            DEVOBS.cache_hits += 1


# The process-wide plane (faults.PLANE precedent): configured by
# server.py from config.devobs; tests reset/configure it directly.
DEVOBS = DeviceTelemetry()

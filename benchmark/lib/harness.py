"""What every traffic kind shares: the server under test, the two
wrappers the benchmark puts around it, and the load step.

The program is imported, never patched on disk. The only wrappers are
the timer around `mm.process` (tick stamps, host time of the pass, a
profiler annotation) and the benchmark's sessions. Everything a traffic
kind leaves on `Ctx` is read afterwards by `run.py`, the judge and the
per-layer readers.
"""

from __future__ import annotations

import asyncio
import contextlib
import importlib.util
import json
import logging
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(kind: str, **fields) -> None:
    """One earlier printed line (README.md lists them)."""
    print(json.dumps({"line": kind, **fields}, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """`benchmark/<folder>/<name>.py`, found by the name a data file
    gives: how a later PR adds a recipe, a traffic kind or a reader
    without editing a file that is there."""
    path = os.path.join(BENCH, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {folder}/{name}.py under benchmark/")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """The benchmark's own count of compiles (jax.monitoring): backend
    compiles, and requests to the persistent cache with their hits. A
    request inside the window is a program that was not warm, whether
    or not the cache had it."""

    def __init__(self):
        self.backend = 0
        self.backend_s = 0.0
        self.requests = 0
        self.hits = 0
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.backend += 1
            self.backend_s += duration

    def _event(self, event: str, **kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            self.requests += 1
        elif event == _CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self) -> dict:
        return dict(backend=self.backend, backend_s=round(self.backend_s, 2),
                    cache_requests=self.requests, cache_hits=self.hits)


class Ctx:
    """One run's state. Traffic kinds fill it; nothing else writes it."""

    def __init__(self, args, workload, config, traffic, device, t_process):
        self.args = args
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.device = device
        self.t_process = t_process  # perf_counter at process start
        self.rehearse = bool(args.rehearse)
        self.compiles = Compiles()
        self.recipe = load_module("recipes", config["recipe"])
        self.server = None
        self.mm = None
        self.backend = None
        self.overrides = {}
        self.sessions = []  # every session whose ticket the window can see
        self._seq = 0
        self.ticks = []  # (perf_counter at process() start, host seconds)
        self.pool_at_tick = []
        self.add_spans = []  # (start, seconds) per Pipeline.process(add)
        self.late = []  # how late the generator issued each add, seconds
        self.failed = 0
        self.attempted = 0
        self.t0 = None  # window start / end, perf_counter
        self.t1 = None
        self.t0_wall = None
        self.compiles_at_t0 = None
        self.compiles_at_t1 = None
        self.eligible = []  # sessions the yield check counts
        self.grace_s = 0.0  # tails and yield count tickets due this long before the close
        self.journal = None
        self.notes = {}

    # ------------------------------------------------------- sessions

    def new_session(self, spec: dict, in_window: bool):
        from .session import BenchSession

        self._seq += 1
        s = BenchSession(self._seq, spec, in_window)
        self.server.session_registry.add(s)
        return s

    def drop_sessions(self, sessions) -> None:
        for s in sessions:
            self.server.session_registry.remove(s.id)

    # ---------------------------------------------------------- adds

    async def add_direct(self, specs, in_window: bool) -> list:
        """`mm.add(..., embedding=)`: the socket envelope has no
        embedding field, so a pool with embeddings is loaded through the
        matchmaker's public add, each presence bound to a registered
        session. The ack is the call's return."""
        from nakama_tpu.matchmaker.types import MatchmakerPresence

        mm = self.mm
        out = []
        for k, spec in enumerate(specs):
            s = self.new_session(spec, in_window)
            p = MatchmakerPresence(
                user_id=s.user_id, session_id=s.id, username=s.username
            )
            s.due_t = time.perf_counter()
            s.ticket, _ = mm.add(
                [p], s.id, "", spec["query"], spec["min_count"],
                spec["max_count"], 1, spec["strs"], spec["nums"],
                embedding=spec.get("emb"),
            )
            s.ack_t = time.perf_counter()
            out.append(s)
            if k % 256 == 255:
                await asyncio.sleep(0)  # let the server's loop run
        return out

    async def add_enveloped(self, s, due_t: float) -> None:
        """One `matchmaker_add` envelope through `Pipeline.process`, as the
        socket read loop hands it over; timed from `due_t`."""
        spec = s.spec
        s.due_t = due_t
        self.attempted += 1
        t_a = time.perf_counter()
        self.late.append(t_a - due_t)
        try:
            await self.server.pipeline.process(s, {"matchmaker_add": {
                "query": spec["query"],
                "min_count": spec["min_count"],
                "max_count": spec["max_count"],
                "string_properties": spec["strs"],
                "numeric_properties": spec["nums"],
            }})
        except Exception as e:  # the pipeline answers its own errors
            s.errors.append(repr(e))
        self.add_spans.append((t_a, time.perf_counter() - t_a))
        if s.ack_t is None or s.errors:
            self.failed += 1

    # -------------------------------------------------------- window

    def open_window(self) -> None:
        self.compiles_at_t0 = self.compiles.snapshot()
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()

    def close_window(self) -> None:
        self.t1 = time.perf_counter()
        self.compiles_at_t1 = self.compiles.snapshot()

    def first_tick_after(self, t: float):
        for at, _ in self.ticks:
            if at >= t:
                return at
        return None

    async def drain(self, limit_s: float = 60.0) -> float:
        """Past the close: wait for cohorts still in flight, so that an
        answer that comes late is judged by what it says. Returns the
        seconds waited."""
        t = time.perf_counter()
        while (
            self.backend.pipeline_depth()
            and time.perf_counter() - t < limit_s
        ):
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.05)
        return time.perf_counter() - t


def build_server(ctx: Ctx, data_dir: str, log_path: str):
    """A real NakamaServer: `Config()` as shipped plus the configuration
    file's `overrides` (printed, so nobody reads the result as the
    default server's)."""
    from nakama_tpu.config import Config
    from nakama_tpu.logger import Logger
    from nakama_tpu.server import NakamaServer

    conf = ctx.config
    cfg = Config()
    cfg.name = "bench"
    cfg.data_dir = data_dir
    cfg.socket.port = 0
    cfg.socket.grpc_port = -1
    overrides = dict(conf["overrides"])
    backend = None
    if ctx.rehearse:
        overrides.pop("matchmaker.backend")
        overrides.update({k: v for k, v in conf["rehearse"].items()
                          if "." in k})
    for dotted, value in overrides.items():
        section, key = dotted.split(".")
        if isinstance(value, list):  # database.address: a file of the run's
            value = [v.format(data_dir=data_dir) for v in value]
        target = getattr(cfg, section)
        if not hasattr(target, key):
            raise SystemExit(f"Config().{section} has no {key}")
        setattr(target, key, value)
    mc = cfg.matchmaker
    shipped = {k: conf[k] for k in (
        "pool_capacity", "numeric_fields", "string_fields",
        "max_constraints", "candidates_per_ticket", "embedding_dims",
        "max_intervals",
    )}
    if not ctx.rehearse:
        have = {k: getattr(mc, k) for k in shipped}
        if have != shipped:
            raise SystemExit(
                f"the program's shipped widths {have} are not the"
                f" configuration's {shipped}"
            )
    stream = open(log_path, "w", buffering=1)
    log = Logger(level=logging.INFO, fmt="json", streams=[stream])
    if ctx.rehearse:
        # CPU rehearsal: config backend="tpu" is refused off-TPU, so the
        # interpreting backend is handed in, as the repo's tests do.
        from nakama_tpu.matchmaker.tpu import TpuBackend

        kw = {k: v for k, v in conf["rehearse"].items()
              if k in ("big_row_block", "big_col_block")}
        backend = TpuBackend(mc, log, **kw)
        overrides["matchmaker.backend"] = "TpuBackend(interpret), handed in"
        overrides.update(kw)
    ctx.overrides = overrides
    ctx.server = NakamaServer(cfg, log, matchmaker_backend=backend)
    ctx.mm = ctx.server.matchmaker
    ctx.backend = ctx.mm.backend
    ctx._log_stream = stream
    return ctx.server


def time_process(ctx: Ctx) -> None:
    """The timer around `mm.process`: when each tick started, how long
    the host was in it, how many tickets the pool held."""
    import jax

    mm = ctx.mm
    process = mm.process

    def timed_process():
        ctx.pool_at_tick.append(len(mm))
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.process"):
                return process()
        finally:
            ctx.ticks.append((t, time.perf_counter() - t))

    mm.process = timed_process


@contextlib.asynccontextmanager
async def running(ctx: Ctx):
    """The server started, and stopped whatever happens (stop() joins
    cohort workers and prewarm threads and takes the shipped checkpoint)."""
    await ctx.server.start()
    try:
        yield
    finally:
        t = time.perf_counter()
        await ctx.server.stop()
        ctx.notes["stop_s"] = round(time.perf_counter() - t, 2)
        ctx._log_stream.close()


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None

"""The seam between the interval host and the match-forming backend.

`local.ProcessBackend` declares every backend method and attribute that
`LocalMatchmaker`, the server, the console and the pipeline use; both
backends implement all of it, and nothing above the seam probes for a
method or reads a backend's private state.
"""

import asyncio
import inspect
import pathlib
import re

import pytest

from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import (
    CpuBackend,
    LocalMatchmaker,
    MatchmakerPresence,
)
from nakama_tpu.matchmaker.local import ProcessBackend
from nakama_tpu.tracing import Tracing

PKG = pathlib.Path(__file__).resolve().parent.parent / "nakama_tpu"

SEAM = sorted(n for n in vars(ProcessBackend) if not n.startswith("_"))
SEAM_METHODS = [n for n in SEAM if callable(getattr(ProcessBackend, n))]


def _tpu_backend():
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=256, candidates_per_ticket=64, numeric_fields=8,
        string_fields=8, max_constraints=8,
    )
    return TpuBackend(cfg, quiet_logger(), row_block=8, col_block=64)


def test_seam_declares_the_surface_the_host_uses():
    assert SEAM == sorted([
        "accepted_cohorts", "annotate", "attach", "breaker",
        "claim_guard_join", "collect_ready", "count_cohorts", "describe",
        "flush", "guard_point", "in_flight", "join_head", "mesh",
        "next_deadline", "on_add", "on_remove_slots",
        "pipeline_backlogged", "pipeline_depth", "process_slots",
        "reclaim_stale", "restore_state", "set_ready_callback",
        "snapshot_state", "store", "tracing", "wait_idle",
    ])


@pytest.mark.parametrize("make", [CpuBackend, _tpu_backend])
def test_backend_implements_every_seam_method(make):
    backend = make()
    assert isinstance(backend, ProcessBackend)
    for name in SEAM:
        assert hasattr(backend, name), name
    for name in SEAM_METHODS:
        declared = inspect.signature(getattr(ProcessBackend, name))
        got = inspect.signature(getattr(type(backend), name))
        # Called the declared way, it binds: same names, and nothing
        # required that the seam does not pass.
        for pname, p in got.parameters.items():
            if p.default is inspect.Parameter.empty and p.kind not in (
                p.VAR_POSITIONAL, p.VAR_KEYWORD,
            ):
                assert pname in declared.parameters, (name, pname)
        for pname in declared.parameters:
            assert pname in got.parameters, (name, pname)
    # process_slots is the one method with no body at the seam.
    assert type(backend).process_slots is not ProcessBackend.process_slots


def test_queue_less_backend_reads_as_idle():
    """What each queue method means for a backend with no queue: nothing
    ready, no deadline, never backlogged, idle at once."""
    b = CpuBackend()
    assert b.collect_ready(rev_precision=False) is None
    assert b.next_deadline() is None and b.guard_point() is None
    assert not b.claim_guard_join() and not b.join_head()
    assert not b.pipeline_backlogged() and b.pipeline_depth() == 0
    assert not b.in_flight(0) and b.accepted_cohorts == ()
    assert b.snapshot_state() is None and b.describe() is None
    assert b.mesh is None and b.breaker is None
    for idle in (
        b.wait_idle, b.reclaim_stale, b.count_cohorts, b.flush,
    ):
        assert idle() is None
    with b.annotate("mm.publish"):
        pass


def test_nothing_above_the_seam_probes_or_reads_private_state():
    local = (PKG / "matchmaker" / "local.py").read_text()
    assert not re.search(r"getattr\(\s*self\.backend", local)
    for path in PKG.rglob("*.py"):
        if path.name == "tpu.py" and path.parent.name == "matchmaker":
            continue
        hits = re.findall(r"backend\._[a-z]\w*", path.read_text())
        assert not hits, (str(path), hits)


class _Recording(CpuBackend):
    """The oracle, noting which seam methods the host called."""

    def __init__(self):
        self.called = set()

    def __getattribute__(self, name):
        if name in SEAM_METHODS:
            object.__getattribute__(self, "called").add(name)
        return object.__getattribute__(self, name)


async def test_local_matchmaker_over_cpu_backend_runs_through_the_seam():
    """Start, tick, deliver, stop: the interval loop, the delivery stage
    and the gap work all go through declared methods, and the interval
    record is the matchmaker's own also over the oracle."""
    backend = _Recording()
    got = []
    cfg = MatchmakerConfig(
        backend="cpu", interval_sec=1, delivery_watchdog_sec=0.1
    )
    handed = Tracing()
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend, on_matched=got.append,
        tracing=handed,
    )
    assert mm.tracing is handed and backend.tracing is handed
    assert backend.store is mm.store
    mm.start()
    try:
        for i in range(2):
            p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
            mm.add([p], p.session_id, "", "*", 2, 2, 1, {}, {})
        assert mm.tracing.add_stages.adds == 2
        deadline = asyncio.get_running_loop().time() + 10
        while not got and asyncio.get_running_loop().time() < deadline:
            await asyncio.sleep(0.05)
    finally:
        mm.stop()
    assert len(got) == 1 and len(got[0][0]) == 2
    assert len(mm) == 0
    assert backend.called >= {
        "attach", "on_add", "process_slots", "on_remove_slots",
        "annotate", "set_ready_callback", "guard_point", "collect_ready",
        "pipeline_backlogged", "count_cohorts", "flush", "wait_idle",
    }, backend.called
    # A matchmaker that is handed no record makes its own.
    assert isinstance(
        LocalMatchmaker(quiet_logger(), cfg, backend=CpuBackend()).tracing,
        Tracing,
    )

"""Crash-recovery plane coverage (ISSUE 7).

Deterministic proofs for recovery.py and its seams:

- TicketJournal: LSN-ordered appends through the group-commit write
  pipeline, lazy payload resolution at drain time, degraded-to-
  in-memory on write failure (armed `journal.append`) with heal, drop
  mode tears the batch without wedging anything.
- Snapshot/restore: SlotStore + TpuBackend checkpoint round trips
  preserving slot assignment, reverse maps, active flags, dispatch
  order, and the allocator; freeze/thaw ticket fidelity.
- recover(): checkpoint load + LSN-ordered journal-tail replay —
  add/remove/matched consumption, unpublished re-pool with payloads,
  idempotence under double recovery, armed `journal.replay` degrades
  instead of wedging.
- Checkpointer: pointer row + truncation (unpublished rows preserved)
  as one atomic unit; RecoveryPlane settles consumed unpublished rows.
- The graceful-stop write-loss regression: `drain_writes` COMMITS the
  queued write backlog before close() can reject it, and the
  shutdown_grace default is nonzero.
- Typed session close: structured close code + Retry-After hint +
  sessions_closed metric.
- The named `crash_recovery_regression` bench gate thresholds.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from nakama_tpu import faults
from nakama_tpu.config import Config, MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.tpu import TpuBackend
from nakama_tpu.matchmaker.types import freeze_ticket, thaw_ticket
from nakama_tpu.recovery import (
    Checkpointer,
    RecoveryPlane,
    TicketJournal,
    recover,
)
from nakama_tpu.storage.db import Database, DatabaseError


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    faults.disarm()
    yield
    faults.disarm()


def _cfg(**kw):
    base = dict(
        pool_capacity=64,
        candidates_per_ticket=16,
        numeric_fields=4,
        string_fields=4,
        max_constraints=4,
        max_intervals=50,
    )
    base.update(kw)
    return MatchmakerConfig(**base)


def _mm(cfg=None, on_matched=None):
    cfg = cfg or _cfg()
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=16)
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend, on_matched=on_matched
    )
    return mm, backend


def _add(mm, i, query="+properties.mode:m1", strs=None, minmax=(2, 2)):
    p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
    tid, _ = mm.add(
        [p], p.session_id, "", query, minmax[0], minmax[1], 1,
        strs if strs is not None else {"mode": "m1"}, {},
    )
    return tid


def _match_until(mm, backend, got, want_entries, timeout=60):
    deadline = time.perf_counter() + timeout
    while (
        sum(b.entry_count for b in got) < want_entries
        and time.perf_counter() < deadline
    ):
        mm.process()
        backend.wait_idle(timeout=30)
        mm.collect_pipelined()
    return sum(b.entry_count for b in got)


# ------------------------------------------------------------ journal


async def test_journal_appends_lsn_ordered_and_lazy_payloads(tmp_path):
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger())
    mm, backend = _mm()
    mm.journal = j
    t1 = _add(mm, 1)
    t2 = _add(mm, 2)
    j.record_remove([t2])
    assert j.lsn == 3 and j.pending == 3 and j.durable_lsn == 0
    assert await j.flush()
    assert j.durable_lsn == 3 and j.pending == 0
    rows = await db.fetch_all(
        "SELECT lsn, op, payload FROM matchmaker_journal ORDER BY lsn"
    )
    assert [r["op"] for r in rows] == ["add", "add", "remove"]
    import json

    add_payload = json.loads(rows[0]["payload"])
    assert add_payload["ticket"] == t1
    assert add_payload["presences"][0]["session_id"] == "s1"
    assert json.loads(rows[2]["payload"])["tickets"] == [t2]
    mm.stop()
    await db.close()


async def test_journal_degrades_in_memory_and_heals(tmp_path):
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger())
    j._append("add", {"ticket": "a"})
    faults.arm("journal.append", "raise")  # persistent outage
    assert not await j.flush()  # degraded, records retained
    assert j.degraded and j.pending == 1
    faults.disarm()
    assert await j.flush()  # storage back: heals
    assert not j.degraded and j.durable_lsn == 1 and j.pending == 0
    mm_rows = await db.fetch_all(
        "SELECT lsn FROM matchmaker_journal"
    )
    assert len(mm_rows) == 1
    await db.close()


async def test_journal_drop_mode_tears_batch_without_wedging(tmp_path):
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger())
    j._append("add", {"ticket": "a"})
    faults.arm("journal.append", "drop", count=1)
    assert await j.flush()  # batch torn away, journal continues
    assert j.dropped == 1 and j.pending == 0 and not j.degraded
    j._append("add", {"ticket": "b"})
    assert await j.flush()
    rows = await db.fetch_all("SELECT op FROM matchmaker_journal")
    assert len(rows) == 1  # only the post-drop record landed
    await db.close()


async def test_journal_buffer_cap_drops_oldest(tmp_path):
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger(), flush_max=4, buffer_cap=4)
    for i in range(10):
        j._append("add", {"ticket": f"t{i}"})
    assert j.pending == 4 and j.dropped == 6
    await db.close()


async def test_journal_eviction_preserves_unpublished_records(tmp_path):
    """Review fix: `unpublished` payloads exist nowhere else — the
    degraded-buffer eviction must never drop them."""
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger(), flush_max=4, buffer_cap=4)
    j._append("unpublished", {"tickets": [{"ticket": "keep-me"}]})
    for i in range(10):
        j._append("add", {"ticket": f"t{i}"})
    assert j.pending == 5  # cap 4 + the preserved unpublished record
    assert j._buf[0][1] == "unpublished"
    assert await j.flush()
    ops = [
        r["op"]
        for r in await db.fetch_all(
            "SELECT op FROM matchmaker_journal ORDER BY lsn"
        )
    ]
    assert ops[0] == "unpublished"
    await db.close()


async def test_journal_concurrent_flush_and_drain_no_loss(tmp_path):
    """Review fix: a checkpoint-barrier flush racing the background
    drain must not double-consume the buffer head — every record lands
    exactly once."""
    db = Database(f"{tmp_path}/j.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger(), flush_max=8)
    for i in range(64):
        j._append("add", {"ticket": f"t{i}"})  # kicks the drain task
    # Race an explicit flush against the kicked drain.
    await asyncio.gather(j.flush(), j.flush())
    await j.flush()
    rows = await db.fetch_all(
        "SELECT lsn FROM matchmaker_journal ORDER BY lsn"
    )
    assert [r["lsn"] for r in rows] == list(range(1, 65))
    assert j.durable_lsn == 64 and j.pending == 0
    await db.close()


# -------------------------------------------------- snapshot / restore


def test_freeze_thaw_roundtrip_fidelity():
    mm, backend = _mm()
    tid = _add(mm, 1, query="+properties.mode:m7", strs={"mode": "m7"})
    t = mm.store.get(tid)
    row = freeze_ticket(t)
    out = thaw_ticket(row, {})
    assert out.ticket == t.ticket and out.query == t.query
    assert out.min_count == t.min_count and out.max_count == t.max_count
    assert out.session_ids == t.session_ids
    assert out.created_seq == t.created_seq
    assert out.entries[0].presence.user_id == "u1"
    assert out.parsed_query is not None
    assert out.string_properties == t.string_properties
    mm.stop()


def test_store_snapshot_restore_roundtrip_and_allocator():
    cfg = _cfg()
    mm, backend = _mm(cfg)
    tids = [_add(mm, i) for i in range(6)]
    mm.remove([tids[2]])
    snap = mm.snapshot_state()

    mm2, backend2 = _mm(cfg)
    mm2.restore_state(snap)
    store = mm2.store
    assert len(store) == 5
    for tid in tids:
        if tid == tids[2]:
            assert store.get(tid) is None
        else:
            t = store.get(tid)
            assert t is not None and t.ticket == tid
    # Reverse maps rebuilt: session counts resolve.
    assert store.session_ticket_count("s0") == 1
    assert store.session_ticket_count("s2") == 0
    # Allocator integrity: adds after restore land on free slots and
    # the pool keeps working end to end.
    new_tid = _add(mm2, 99)
    assert store.get(new_tid) is not None
    got = []
    mm2.on_matched = got.append
    assert _match_until(mm2, backend2, got, 2) >= 2
    mm.stop()
    mm2.stop()


def test_sharded_pool_snapshot_restore_roundtrip():
    """Mesh regression: checkpoint/restore must round-trip a pool whose
    slot axis is SHARDED over the 8-device mesh — snapshot fetches the
    sharded columns, restore re-places them with the same NamedSharding,
    and the restored mesh backend keeps matching (on the mesh path)."""
    import jax

    assert len(jax.devices()) >= 8, "conftest provides the 8-CPU mesh"
    cfg = _cfg(pool_capacity=512, mesh_devices=8)
    def build():
        backend = TpuBackend(
            cfg, quiet_logger(), row_block=8, col_block=64
        )
        mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend)
        return mm, backend

    mm, backend = build()
    assert backend.mesh is not None
    tids = [_add(mm, i) for i in range(6)]
    mm.remove([tids[2]])
    snap = mm.snapshot_state()

    mm2, backend2 = build()
    mm2.restore_state(snap)
    # The restored pool kept its mesh placement (one shard per device).
    flags = backend2.pool.device["flags"]
    assert len(flags.sharding.device_set) == 8
    assert len(mm2.store) == 5
    for tid in tids:
        if tid == tids[2]:
            assert mm2.store.get(tid) is None
        else:
            assert mm2.store.get(tid) is not None
    # And the sharded dispatch path still matches end to end.
    got = []
    mm2.on_matched = got.append
    assert _match_until(mm2, backend2, got, 4) >= 4
    assert backend2.mesh_breaker.state == "closed"
    mm.stop()
    mm2.stop()


def test_restore_refuses_capacity_mismatch():
    mm, _ = _mm(_cfg())
    snap = mm.snapshot_state()
    mm2, _ = _mm(_cfg(pool_capacity=128))
    with pytest.raises(ValueError):
        mm2.restore_state(snap)
    mm.stop()
    mm2.stop()


def test_backend_restore_preserves_dispatch_order_and_masks():
    cfg = _cfg()
    mm, backend = _mm(cfg)
    for i in range(4):
        _add(mm, i)
    # host-only query (regex-ish wildcard term) lands in the host mask.
    host_tid = _add(
        mm, 9, query="+properties.mode:mm*", strs={"mode": "mm1"}
    )
    snap = mm.snapshot_state()
    mm2, backend2 = _mm(cfg)
    mm2.restore_state(snap)
    assert host_tid in backend2.host_only
    assert int(backend2.host_only_mask.sum()) == 1
    assert backend2._nonpair_count == int(backend._nonpair_count)
    # Dispatch ring order == (created_at, created_seq) order.
    live = mm2.store.live_slots()
    meta = mm2.store.meta
    order = np.lexsort(
        (meta["created_seq"][live], meta["created"][live])
    )
    ring = backend2._ring[: backend2._ring_n]
    ring = ring[backend2._ring_valid[: backend2._ring_n]]
    assert list(ring) == list(live[order])
    mm.stop()
    mm2.stop()


# ------------------------------------------------------------- recover


async def test_recover_checkpoint_plus_tail_replay(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    mm, backend = _mm(cfg)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    ck = Checkpointer(
        j, db, f"{tmp_path}/r.ckpt", quiet_logger(), interval_sec=1
    )
    keep = [_add(mm, i) for i in range(3)]
    assert await ck.checkpoint(mm) is not None
    # Tail past the checkpoint: one more add, one removal.
    late = _add(mm, 7)
    mm.remove([keep[0]])
    await j.flush()

    mm2, backend2 = _mm(cfg)
    stats = await recover(
        mm2, db, f"{tmp_path}/r.ckpt", "local", quiet_logger()
    )
    assert stats["checkpoint_lsn"] == 3
    assert stats["reinserted"] == 1 and stats["removed"] == 1
    ids = set(mm2.tickets.keys())
    assert ids == {keep[1], keep[2], late}
    mm.stop()
    mm2.stop()
    await db.close()


async def test_recover_is_idempotent(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    mm, backend = _mm(cfg)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    tids = {_add(mm, i) for i in range(4)}
    await j.flush()
    mm2, _ = _mm(cfg)
    await recover(mm2, db, f"{tmp_path}/none.ckpt", "local", quiet_logger())
    # Second replay over the same journal: duplicate guard absorbs it.
    await recover(mm2, db, f"{tmp_path}/none.ckpt", "local", quiet_logger())
    assert set(mm2.tickets.keys()) == tids and len(mm2.store) == 4
    mm.stop()
    mm2.stop()
    await db.close()


async def test_matched_records_consume_tickets_on_replay(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    got = []
    mm, backend = _mm(cfg, on_matched=got.append)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    _add(mm, 1)
    _add(mm, 2)
    unmatched = _add(
        mm, 3, query="+properties.mode:zz", strs={"mode": "xx"}
    )
    assert _match_until(mm, backend, got, 2) == 2
    await j.flush()
    rows = await db.fetch_all(
        "SELECT op FROM matchmaker_journal ORDER BY lsn"
    )
    assert "matched" in {r["op"] for r in rows}

    mm2, _ = _mm(cfg)
    stats = await recover(
        mm2, db, f"{tmp_path}/none.ckpt", "local", quiet_logger()
    )
    # The matched pair is consumed (exactly-once); the unmatched
    # ticket is back poolside.
    assert set(mm2.tickets.keys()) == {unmatched}
    assert stats["repooled_unpublished"] == 0
    mm.stop()
    mm2.stop()
    await db.close()


async def test_unpublished_match_repools_and_settles(tmp_path):
    """Publish failure → `unpublished` journal record (full payloads)
    → checkpoint truncation PRESERVES it → RecoveryPlane re-pools the
    tickets, re-journals them as adds, and deletes the consumed row."""
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    got = []
    mm, backend = _mm(cfg, on_matched=got.append)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    ck = Checkpointer(
        j, db, f"{tmp_path}/r.ckpt", quiet_logger(), interval_sec=1
    )
    pair = {_add(mm, 1), _add(mm, 2)}
    faults.arm("delivery.publish", "drop", count=1)
    deadline = time.perf_counter() + 60
    while j.appended < 3 and time.perf_counter() < deadline:
        mm.process()
        backend.wait_idle(timeout=30)
        mm.collect_pipelined()
    faults.disarm()
    assert not got  # the publish really was dropped
    # A checkpoint AFTER the unpublished match: truncation must keep
    # the unpublished row (the snapshot cannot cover those tickets).
    assert await ck.checkpoint(mm) is not None
    rows = await db.fetch_all(
        "SELECT op FROM matchmaker_journal ORDER BY lsn"
    )
    assert [r["op"] for r in rows] == ["unpublished"]

    # Warm restart through the plane: re-pool + settle.
    config = Config()
    config.recovery.recovery_dir = str(tmp_path)
    config.data_dir = str(tmp_path)
    mm2, backend2 = _mm(cfg)
    plane = RecoveryPlane(
        config, db, mm2, quiet_logger(), node="local"
    )
    plane.path = f"{tmp_path}/r.ckpt"
    plane.checkpointer.path = plane.path
    stats = await plane.recover()
    assert stats["repooled_unpublished"] == 2
    assert set(mm2.tickets.keys()) == pair
    # Settlement: the unpublished row is replaced by fresh add records.
    rows = await db.fetch_all(
        "SELECT op FROM matchmaker_journal ORDER BY lsn"
    )
    assert [r["op"] for r in rows] == ["add", "add"]
    # The re-pooled pair matches after restart — exactly once.
    got2 = []
    mm2.on_matched = got2.append
    assert _match_until(mm2, backend2, got2, 2) == 2
    mm.stop()
    mm2.stop()
    await db.close()


async def test_replay_fault_degrades_not_wedges(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    mm, backend = _mm(cfg)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    _add(mm, 1)
    await j.flush()
    mm2, _ = _mm(cfg)
    faults.arm("journal.replay", "raise", count=1)
    stats = await recover(
        mm2, db, f"{tmp_path}/none.ckpt", "local", quiet_logger()
    )
    # Degraded boot: nothing recovered, nothing wedged, stats sane.
    assert stats["tickets"] == 0 and stats["replayed_rows"] == 0
    mm.stop()
    mm2.stop()
    await db.close()


async def test_checkpoint_write_fault_survivable(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    mm, backend = _mm()
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    _add(mm, 1)
    ck = Checkpointer(
        j, db, f"{tmp_path}/r.ckpt", quiet_logger(), interval_sec=1
    )
    faults.arm("checkpoint.write", "raise", count=1)
    assert await ck.checkpoint(mm) is None  # failed, contained
    # Journal survives untruncated; drop mode discards a round the
    # same way; then the next clean checkpoint succeeds.
    assert len(await db.fetch_all("SELECT 1 FROM matchmaker_journal")) == 1
    faults.arm("checkpoint.write", "drop", count=1)
    assert await ck.checkpoint(mm) is None  # dropped, contained
    assert len(await db.fetch_all("SELECT 1 FROM matchmaker_journal")) == 1
    assert await ck.checkpoint(mm) is not None
    assert len(await db.fetch_all("SELECT 1 FROM matchmaker_journal")) == 0
    mm.stop()
    await db.close()


async def test_replay_drop_fault_boots_on_snapshot_alone(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    cfg = _cfg()
    mm, backend = _mm(cfg)
    j = TicketJournal(db, quiet_logger())
    mm.journal = j
    _add(mm, 1)
    await j.flush()
    mm2, _ = _mm(cfg)
    faults.arm("journal.replay", "drop", count=1)
    stats = await recover(
        mm2, db, f"{tmp_path}/none.ckpt", "local", quiet_logger()
    )
    # The tail replay was discarded (drop = the work unit is thrown
    # away): degraded boot, zero rows applied, nothing wedged.
    assert stats["replayed_rows"] == 0 and stats["tickets"] == 0
    mm.stop()
    mm2.stop()
    await db.close()


async def test_first_checkpoint_waits_a_full_interval(tmp_path):
    db = Database(f"{tmp_path}/r.db", read_pool_size=1)
    await db.connect()
    j = TicketJournal(db, quiet_logger())
    ck = Checkpointer(
        j, db, f"{tmp_path}/r.ckpt", quiet_logger(), interval_sec=60
    )
    assert not ck.due()  # anchored at construction, not at epoch 0
    await db.close()


# ----------------------------------------- graceful stop (write loss)


async def test_drain_writes_commits_backlog_before_close(tmp_path):
    """The PR 7 graceful-stop regression: queued write units COMMIT
    through drain_writes before close() — a clean stop under load must
    not reject acknowledged-queueable work anymore."""
    db = Database(f"{tmp_path}/d.db", read_pool_size=1)
    await db.connect()
    await db.execute("CREATE TABLE kv (k TEXT PRIMARY KEY, v INT)")
    writes = [
        asyncio.ensure_future(
            db.execute(
                "INSERT INTO kv (k, v) VALUES (?, ?)", (f"k{i}", i)
            )
        )
        for i in range(64)
    ]
    # Let the submissions reach the batcher queue (the server's stop
    # path runs after the API quiesced, so in-flight handlers have
    # already enqueued by the time it drains).
    await asyncio.sleep(0.05)
    assert await db.drain_writes(5.0)
    await db.close()
    results = await asyncio.gather(*writes, return_exceptions=True)
    failed = [r for r in results if isinstance(r, Exception)]
    assert not failed  # every queued write committed, none rejected
    # And the rows really landed (fresh connection).
    db2 = Database(f"{tmp_path}/d.db", read_pool_size=1)
    await db2.connect()
    rows = await db2.fetch_all("SELECT COUNT(*) AS n FROM kv")
    assert rows[0]["n"] == 64
    await db2.close()


def test_shutdown_grace_default_nonzero():
    assert Config().shutdown_grace_sec > 0


# ------------------------------------------------- typed session close


async def test_session_close_structured_code_and_metric():
    from nakama_tpu.api.session_ws import WebSocketSession
    from nakama_tpu.metrics import Metrics

    class FakeWs:
        def __init__(self):
            self.sent = []
            self.close_args = None

        async def send(self, data):
            self.sent.append(data)

        async def close(self, code=1000, reason=""):
            self.close_args = (code, reason)

    metrics = Metrics()
    ws = FakeWs()
    session = WebSocketSession(
        ws,
        user_id="u",
        username="n",
        vars={},
        format="json",
        expiry=0,
        logger=quiet_logger(),
        metrics=metrics,
    )
    # The writer task normally spawns in consume(); start it so the
    # close path's flush actually drains the Retry-After envelope.
    session._writer_task = asyncio.get_running_loop().create_task(
        session._writer()
    )
    await session.close(
        "server shutting down",
        code=1012,
        kind="shutdown",
        retry_after_sec=3.0,
    )
    assert ws.close_args == (1012, "server shutting down")
    snap = metrics.snapshot()
    assert snap.get("nakama_sessions_closed_total{reason=shutdown}") == 1.0
    # The Retry-After hint rode a final envelope before the close.
    def _text(s):
        return s.decode() if isinstance(s, bytes) else s

    assert any("server_restart" in _text(s) for s in ws.sent)
    assert any("retry_after_sec" in _text(s) for s in ws.sent)


async def test_session_close_plain_ws_fallback():
    from nakama_tpu.api.session_ws import WebSocketSession

    class BareWs:
        closed = False

        async def send(self, data):
            pass

        async def close(self):  # no code/reason support
            self.closed = True

    ws = BareWs()
    session = WebSocketSession(
        ws,
        user_id="u",
        username="n",
        vars={},
        format="json",
        expiry=0,
        logger=quiet_logger(),
    )
    await session.close("bye")
    assert ws.closed


# ------------------------------------------------------- the bench gate


def test_crash_recovery_regression_gate():
    import bench

    gate = bench.crash_recovery_regression
    # Clean run: no regression.
    reasons, bad = gate(0, 0, 6, 6, 1.2, 0.02)
    assert not bad and reasons == []
    # Each failure mode trips it with a named reason.
    assert gate(3, 0, 6, 6, 1.2, 0.02)[1]
    assert "tickets_lost=3" in gate(3, 0, 6, 6, 1.2, 0.02)[0][0]
    assert gate(0, 1, 6, 6, 1.2, 0.02)[1]
    assert gate(0, 0, 5, 6, 1.2, 0.02)[1]
    assert gate(0, 0, 6, 6, bench.CRASH_RECOVERY_BUDGET_S, 0.02)[1]
    assert gate(0, 0, 6, 6, 1.2, 1.0)[1]

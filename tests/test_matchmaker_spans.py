"""The spans inside the matchmaker (tracing.py: interval record, cohort
record, delivery call), on a small pool on the interpreting backend.

What the benchmark's per-layer metrics read is pinned here: the stage
chain of a cohort's ledger row and the ids that tie it to its interval,
the add stages folded into the interval breadcrumb, the publish stages
of the delivery call, what a lost cohort leaves, and that every key a
`ledger`, `ledger_ratio` or `crumb` metric file names is one the program writes.
"""

import asyncio
import gc
import glob
import json
import os
import threading
import time

import pytest

from nakama_tpu import faults
from nakama_tpu.api.matchmaker_events import make_matched_handler
from nakama_tpu.api.pipeline import Components, Pipeline
from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.local import PartialPublish
from nakama_tpu.matchmaker.types import MatchmakerEntry
from nakama_tpu.matchmaker.tpu import TpuBackend
from nakama_tpu.realtime.message_router import LocalMessageRouter
from nakama_tpu.realtime.session_registry import LocalSessionRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAGS = (
    "device_done_lag_s", "fetch_lag_s", "ready_lag_s", "collect_lag_s",
    "accept_lag_s", "publish_lag_s",
)
ADD_STAGES = (
    "add_pipeline_s", "add_parse_s", "add_register_s", "add_journal_s",
    "add_trace_s",
)
PUBLISH_STAGES = (
    "publish_materialise_s", "publish_hook_s", "publish_token_s",
    "publish_envelope_s", "publish_route_s",
)


class _Session:
    def __init__(self, i):
        self.id = f"sp-s{i}"
        self.user_id = f"sp-u{i}"
        self.username = f"sp-n{i}"
        self.format = "json"
        self.got = []

    def send(self, envelope):
        self.got.append(envelope)
        return True


class _Journal:
    """The matchmaker's journal hooks, kept in a list."""

    def __init__(self):
        self.rows = []

    def record_add(self, ticket):
        self.rows.append(("add", ticket.ticket))

    def record_matched(self, resolver):
        self.rows.append(("matched", resolver))

    def record_unpublished(self, resolver):
        self.rows.append(("unpublished", resolver))


class Rig:
    """A matchmaker on the device backend behind the realtime pipeline:
    sessions in a registry, the shipped matched handler, a journal."""

    def __init__(self, **kw):
        cfg = MatchmakerConfig(
            pool_capacity=256, candidates_per_ticket=64, numeric_fields=8,
            string_fields=8, max_constraints=8, max_intervals=99, **kw,
        )
        log = quiet_logger()
        self.backend = TpuBackend(cfg, log, row_block=8, col_block=64)
        self.sessions = LocalSessionRegistry(log)
        router = LocalMessageRouter(log, self.sessions, tracker=None)
        self.mm = LocalMatchmaker(
            log, cfg, backend=self.backend,
            on_matched=make_matched_handler(log, router, "n1", "k" * 32),
        )
        self.tracing = self.mm.tracing
        self.mm.journal = _Journal()
        self.pipeline = Pipeline(log, Components(
            config=None, tracker=None, router=router, status_registry=None,
            matchmaker=self.mm,
        ))
        self.ready = threading.Event()
        self.backend.set_ready_callback(self.ready.set)
        self._n = 0

    def session(self):
        self._n += 1
        s = _Session(self._n)
        self.sessions.add(s)
        return s

    async def add_enveloped(self, mode):
        s = self.session()
        await self.pipeline.process(s, {"matchmaker_add": {
            "query": f"properties.mode:{mode}", "min_count": 2,
            "max_count": 2, "string_properties": {"mode": mode},
        }})
        assert "matchmaker_ticket" in s.got[0], s.got
        return s

    def add_direct(self, mode):
        s = self.session()
        p = MatchmakerPresence(
            user_id=s.user_id, session_id=s.id, username=s.username
        )
        self.mm.add(
            [p], s.id, "", f"properties.mode:{mode}", 2, 2, 1,
            {"mode": mode}, {},
        )
        return s

    def dispatch_and_wait(self):
        """One tick that dispatches a cohort, then its worker's signal."""
        self.ready.clear()
        self.mm.process()
        assert self.ready.wait(60), "the cohort never became ready"

    def crumbs(self):
        return [c for c in self.tracing.recent(256) if "actives" in c]


async def _cycle(rig, pairs, via="collect"):
    """`pairs` 1v1 matches through add → tick → delivery; the sessions."""
    sessions = []
    for k in range(pairs):
        sessions.append(await rig.add_enveloped(f"m{k}"))
        sessions.append(rig.add_direct(f"m{k}"))
    rig.dispatch_and_wait()
    batch = rig.mm.collect_pipelined() if via == "collect" else rig.mm.process()
    assert len(batch) == pairs
    return sessions


# ------------------------------------------------------ the cohort record


@pytest.mark.parametrize("via", ["collect", "process"])
async def test_cohort_row_is_one_ordered_chain_tied_to_its_interval(via):
    rig = Rig()
    await _cycle(rig, 3, via)
    (row,) = rig.tracing.recent_deliveries(1)
    lags = [row[k] for k in LAGS]
    assert all(isinstance(v, float) for v in lags), row
    assert 0.0 <= lags[0] and lags == sorted(lags), row
    assert any(v != round(v, 3) for v in lags), row  # as stamped
    assert row["status"] == "ok" and row["slipped"] is False
    assert row["actives"] == 6 and row["d2h_bytes"] > 0
    assert (row["matches"], row["envelopes"]) == (3, 6)
    # the interval that dispatched it names it, and it names the interval
    (crumb,) = [c for c in rig.crumbs() if c["seq"] == row["interval_seq"]]
    assert crumb["cohort_seq"] == row["seq"]
    assert crumb["_pc_start"] <= row["_pc_dispatch"] <= crumb["_pc_end"]
    stats = rig.tracing.delivery_stage_stats()
    assert list(stats) == list(LAGS)
    assert stats["device_done_lag_s"]["p50"] == row["device_done_lag_s"]


def test_publish_stamp_lands_on_the_cohorts_a_call_shipped_and_no_other():
    """A cohort that matched nothing publishes nothing: the next call's
    publish stamp must not land on its row."""
    rig = Rig()
    rig.add_direct("alone")  # no partner: a cohort with no match
    rig.dispatch_and_wait()
    assert len(rig.mm.collect_pipelined()) == 0
    asyncio.run(_cycle(rig, 1))
    empty, full = rig.tracing.recent_deliveries(2)
    assert (empty["matches"], full["matches"]) == (0, 1)
    assert "publish_lag_s" not in empty
    assert "publish_matches" not in empty
    assert empty["delivery_held_s"] >= empty["deliver_remove_s"] >= 0.0
    assert full["publish_lag_s"] >= full["accept_lag_s"]


async def test_publish_stamp_survives_a_saturated_ledger():
    """Once the bounded ledger is full its length stops moving: the
    publish stamp still lands on the shipped cohort's own row, and on
    no row that was there before."""
    rig = Rig()
    cap = 256  # Tracing's ledger capacity with no config
    for k in range(cap + 8):
        rig.tracing.record_delivery(_pc_dispatch=float(k), seq=-k)
    assert len(rig.tracing.deliveries) == cap
    await _cycle(rig, 1)
    assert len(rig.tracing.deliveries) == cap
    *older, row = rig.tracing.recent_deliveries(cap)
    assert row is rig.backend.accepted_cohorts[0].entry
    assert row["publish_lag_s"] >= row["accept_lag_s"] > 0.0
    assert row["delivery_held_s"] > 0.0
    assert not any("publish_lag_s" in r for r in older)


# ---------------------------------------------------- the interval record


async def test_interval_crumb_counts_the_adds_since_the_last_tick():
    rig = Rig()
    rig.add_direct("before")
    rig.mm.process()
    t0 = time.perf_counter()
    for k in range(5):
        await rig.add_enveloped(f"e{k}")
    for k in range(3):
        rig.add_direct(f"d{k}")
    wall = time.perf_counter() - t0
    rig.mm.process()
    first, second = rig.crumbs()[-2:]
    assert (first["adds"], first["adds_enveloped"]) == (1, 0)
    assert (second["adds"], second["adds_enveloped"]) == (8, 5)
    assert first["add_pipeline_s"] == 0.0
    for key in ADD_STAGES:
        assert second[key] > 0.0, (key, second)
    assert sum(second[k] for k in ADD_STAGES) <= wall
    assert second["seq"] == first["seq"] + 1
    assert first["_pc_end"] <= second["_pc_start"] <= second["_pc_end"]
    assert len([r for r in rig.mm.journal.rows if r[0] == "add"]) == 9
    # the sums start again: a tick with no add since reads zero
    rig.mm.process()
    third = rig.crumbs()[-1]
    assert (third["adds"], third["add_parse_s"]) == (0, 0.0)


async def test_refused_envelope_is_no_add():
    """An envelope the pipeline or the matchmaker refuses made no
    ticket: it is in neither count and in no stage sum."""
    rig = Rig()
    rig.mm.process()
    bad = rig.session()
    await rig.pipeline.process(bad, {"matchmaker_add": {
        "query": "*", "min_count": 3, "max_count": 2,
    }})
    assert "error" in bad.got[0], bad.got
    twice = await rig.add_enveloped("t")
    for _ in range(rig.mm.config.max_tickets):  # the session's limit
        await rig.pipeline.process(twice, {"matchmaker_add": {
            "query": "*", "min_count": 2, "max_count": 2,
        }})
    assert "error" in twice.got[-1], twice.got
    made = sum(1 for e in twice.got if "matchmaker_ticket" in e)
    rig.mm.process()
    crumb = rig.crumbs()[-1]
    assert (crumb["adds"], crumb["adds_enveloped"]) == (made, made)
    assert len([r for r in rig.mm.journal.rows if r[0] == "add"]) == made
    assert not rig.tracing.add_stages._in_envelope


# ------------------------------------------------------ the delivery call


async def test_publish_stages_add_up_to_the_publish_lag():
    rig = Rig()
    # Enough matches that the few untimed microseconds around the
    # stamps are small beside them, and no collector pause among them.
    gc.collect()
    gc.disable()
    try:
        sessions = await _cycle(rig, 32)
    finally:
        gc.enable()
    (row,) = rig.tracing.recent_deliveries(1)
    matched = [
        e for s in sessions for e in s.got if "matchmaker_matched" in e
    ]
    assert row["publish_envelopes"] == len(matched) == 64
    assert row["publish_matches"] == row["matches"] == 32
    assert row["publish_tokens"] == 32  # no hook took a match
    assert row["envelopes"] == 64
    assert all(
        len(e["matchmaker_matched"]["users"]) == 2
        and e["matchmaker_matched"]["token"] for e in matched
    )
    whole = row["publish_lag_s"] - row["collect_lag_s"]
    parts = (
        row["accept_lag_s"] - row["collect_lag_s"]
        + row["deliver_remove_s"]
        + sum(row[k] for k in PUBLISH_STAGES)
    )
    assert 0.8 * whole <= parts <= whole, (parts, whole, row)
    assert row["delivery_held_s"] >= whole
    assert row["publish_hook_s"] < row["publish_token_s"]  # no runtime
    # the handler's sums were moved, not copied
    assert set(rig.mm.on_matched.stages.values()) == {0}


class _EveryOtherMatchIsTheHooks:
    """A runtime whose matched hook names a match of its own for every
    second match it is shown."""

    def __init__(self):
        self.shown = 0

    def matchmaker_matched(self):
        def hook(entries):
            self.shown += 1
            return "" if self.shown % 2 else f"auth-{self.shown}.n1"

        return hook


@pytest.mark.parametrize("via", ["collect", "process"])
async def test_row_counts_tokens_as_matches_less_the_hooks(via):
    rig = Rig()
    rig.mm.on_matched = make_matched_handler(
        quiet_logger(), rig.pipeline.c.router, "n1", "k" * 32,
        runtime=_EveryOtherMatchIsTheHooks(),
    )
    sessions = await _cycle(rig, 5, via)
    (row,) = rig.tracing.recent_deliveries(1)
    bodies = [
        e["matchmaker_matched"] for s in sessions for e in s.got
        if "matchmaker_matched" in e
    ]
    assert row["publish_matches"] == row["matches"] == 5
    assert row["publish_envelopes"] == len(bodies) == 10
    assert row["publish_tokens"] == 3 == 5 - 2
    assert sum("token" in b for b in bodies) == 2 * 3
    assert sum("match_id" in b for b in bodies) == 2 * 2
    assert len({b["token"] for b in bodies if "token" in b}) == 3
    assert set(rig.mm.on_matched.stages.values()) == {0}


def _thousand_matches(rig):
    """1,000 matches of two, each entry's session in the rig's registry."""
    matches = []
    for _ in range(1000):
        pair = []
        for _ in range(2):
            s = rig.session()
            pair.append(MatchmakerEntry(
                ticket=f"t-{s.id}",
                presence=MatchmakerPresence(s.user_id, s.id, s.username),
            ))
        matches.append(pair)
    return matches


def test_row_counts_one_router_call_a_match_and_no_collection_in_the_call():
    """The fan-out's two counters on the delivery call's row: a router
    call a match, and no young-generation collection while the handler
    ran, which without the pause a batch of this size sets off by the
    dozen (14,000 tracked objects against a threshold of 700)."""
    rig = Rig()
    matches = _thousand_matches(rig)
    assert gc.isenabled() and gc.get_threshold()[0] <= 2000
    row = {}
    assert rig.mm._publish(matches, row) is True
    assert gc.isenabled()
    assert row["publish_route_calls"] == row["publish_matches"] == 1000
    assert row["publish_envelopes"] == 2000
    assert row["publish_gc_collections"] == 0
    assert all(
        len(rig.sessions.get(e.presence.session_id).got) == 1
        for pair in matches for e in pair
    )
    assert set(rig.mm.on_matched.stages.values()) == {0}


class _StampedCohort:
    """What `_deliver` needs of a cohort's record, keeping the stamp."""

    def __init__(self):
        self.entry = {}
        self.t_accept = time.perf_counter()
        self.stamp = None

    def published(self, now):
        self.stamp = now
        return 0.0


def test_the_deferred_collection_starts_after_the_publish_stamp():
    """The call's survivors are walked once, by the first collection
    after the handler: it must not land between the last envelope and
    the cohort's publish stamp, or `publish_lag_s` would read a
    collector pass as publishing."""
    rig = Rig()
    matches = _thousand_matches(rig)
    cohort = _StampedCohort()
    started = []

    def note(phase, info):
        if phase == "start":
            started.append(time.perf_counter())

    gc.collect()
    gc.callbacks.append(note)
    try:
        rig.mm._deliver(matches, None, None, [cohort])
    finally:
        gc.callbacks.remove(note)
    assert cohort.entry["publish_gc_collections"] == 0
    assert started, "14,000 survivors and no collection after the call"
    assert cohort.stamp is not None and min(started) >= cohort.stamp


@pytest.mark.parametrize("via", ["collect", "process"])
async def test_cohort_row_carries_the_fan_outs_counters(via):
    rig = Rig()
    await _cycle(rig, 5, via)
    (row,) = rig.tracing.recent_deliveries(1)
    assert row["publish_route_calls"] == row["publish_matches"] == 5
    assert row["publish_gc_collections"] == 0


def _raises(batch):
    raise RuntimeError("handler broke")


def _holds(batch):
    raise PartialPublish({"t-held"})


@pytest.mark.parametrize("enabled_before", [True, False])
@pytest.mark.parametrize("handler, returns", [
    (lambda batch: None, True),
    (_raises, False),
    (_holds, frozenset({"t-held"})),
], ids=["clean", "raises", "partial"])
def test_publish_leaves_the_collector_as_it_found_it(
    handler, returns, enabled_before
):
    """Paused while the handler runs, and afterwards what it was before:
    enabled again after a clean call, a raising handler and a partial
    publish; never enabled for a caller that had it disabled."""
    rig = Rig()
    inside = []

    def on_matched(batch):
        inside.append(gc.isenabled())
        return handler(batch)

    rig.mm.on_matched = on_matched
    row = {}
    was = gc.isenabled()
    try:
        (gc.enable if enabled_before else gc.disable)()
        assert rig.mm._publish([["a match"]], row) == returns
        assert gc.isenabled() is enabled_before
    finally:
        (gc.enable if was else gc.disable)()
    assert inside == [False]
    assert row["publish_gc_collections"] == 0


def test_dropped_publish_does_not_touch_the_collector():
    rig = Rig()
    rig.mm.on_matched = _raises  # never reached
    faults.arm("delivery.publish", "drop", count=1)
    try:
        row = {}
        assert rig.mm._publish([["a match"]], row) is False
    finally:
        faults.disarm()
    assert gc.isenabled() and row == {}


# ------------------------------------------------------------ a lost cohort


def test_failed_collect_leaves_one_error_row_and_no_half_stamped_one():
    rig = Rig()
    faults.arm("device.collect", "raise", count=1)
    try:
        rig.add_direct("x")
        rig.add_direct("x")
        rig.dispatch_and_wait()
        assert len(rig.mm.collect_pipelined()) == 0
    finally:
        faults.disarm()
    (lost,) = rig.tracing.recent_deliveries(8)
    assert (lost["status"], lost["error_stage"]) == ("error", "collect")
    assert lost["collect_lag_s"] >= lost["ready_lag_s"] > 0.0
    # the worker raised before the device wait: no stage it never reached
    for key in ("device_done_lag_s", "fetch_lag_s", "accept_lag_s"):
        assert lost[key] is None, (key, lost)
    for key in ("publish_lag_s", "deliver_remove_s", "delivery_held_s"):
        assert key not in lost, (key, lost)
    assert rig.crumbs()[-1]["cohort_seq"] == lost["seq"]
    assert rig.backend.inflight_reclaimed == 2
    # the pair retries, and its row is whole
    rig.dispatch_and_wait()
    assert len(rig.mm.collect_pipelined()) == 1
    good = rig.tracing.recent_deliveries(1)[0]
    assert good["status"] == "ok" and good["seq"] == lost["seq"] + 1
    assert all(isinstance(good[k], float) for k in LAGS)
    assert "error_stage" not in good
    stats = rig.tracing.delivery_stage_stats()
    assert stats["publish_lag_s"]["n"] == 1 and stats["ready_lag_s"]["n"] == 2


# ------------------------- the keys the benchmark's metric files name


def _metric_files(readers=("ledger", "ledger_ratio", "crumb")):
    """The benchmark's metric files that name one of `readers`."""
    out = []
    for path in sorted(glob.glob(
        os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")
    )):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] in readers:
            out.append(pytest.param(spec, id=os.path.basename(path)[:-5]))
    return out


@pytest.fixture(scope="module")
def produced():
    """The ledger rows and interval crumbs of two small pipelined runs:
    one on the exact kernel with the native assembler, one over
    `big_pool_threshold`, where a pool of solo 1v1 tickets is paired on
    the device and the row carries the pairing's counters."""
    rows, crumbs = [], []
    for kw in ({}, {"big_pool_threshold": 2}):
        rig = Rig(**kw)
        asyncio.run(_cycle(rig, 2))
        rig.backend.count_cohorts()  # the interval loop's idle-gap sweep
        rig.mm.process()
        rows += rig.tracing.recent_deliveries(8)
        crumbs += rig.crumbs()
    assert [c["kernel"]["kernel"] for c in crumbs if "kernel" in c] == [
        "topk_candidates", "topk_candidates_big+pair_partners"]
    return rows, crumbs


@pytest.mark.parametrize("spec", _metric_files())
def test_metric_file_names_only_keys_the_program_writes(spec, produced):
    rows, crumbs = produced
    args = spec["args"]
    if spec["reader"] == "ledger":
        keys, records = args["plus"] + args.get("minus", []), rows
    else:
        keys = args["sum"] + [args["per"]] if "per" in args else args["sum"]
        records = crumbs if spec["reader"] == "crumb" else rows
    assert keys
    for key in keys:
        assert any(isinstance(r.get(key), (int, float)) for r in records), key

"""TPU backend tests: golden-parity vs the CPU oracle (SURVEY.md §4 — same
ticket pool through both backends must produce equivalent-validity matches),
plus kernel/compiler/assembler unit coverage. Runs on the virtual CPU
device from conftest; the same code path runs on real TPU."""

import numpy as np
import pytest

from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.tpu import TpuBackend

_uid = 0


def presence():
    global _uid
    _uid += 1
    return MatchmakerPresence(
        user_id=f"uid-{_uid}", session_id=f"sid-{_uid}", username=f"u{_uid}"
    )


def tpu_config(**kw):
    defaults = dict(
        pool_capacity=256,
        candidates_per_ticket=256,  # K = capacity → exact hit lists
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        # Matching-semantics tests pin the synchronous path (one
        # process() == one delivered interval); the shipped default is
        # pipelined and has its own tests (test_matchmaker_cadence.py
        # and the pipelined cases below, which opt back in).
        interval_pipelining=False,
    )
    defaults.update(kw)
    return MatchmakerConfig(**defaults)


def make_tpu_mm(**kw):
    cfg = tpu_config(**kw)
    collected = []
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=64)
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend, on_matched=collected.append
    )
    return mm, collected


def add(mm, query="*", mn=2, mx=2, multiple=1, strs=None, nums=None, party=""):
    p = presence()
    return (
        mm.add([p], p.session_id, party, query, mn, mx, multiple, strs or {}, nums or {})[0],
        p,
    )


# ---------------------------------------------------------------- behavior


def test_basic_1v1_match():
    mm, got = make_tpu_mm()
    add(mm, "properties.mode:a", strs={"mode": "a"})
    add(mm, "properties.mode:a", strs={"mode": "a"})
    add(mm, "properties.mode:b", strs={"mode": "b"})
    mm.process()
    assert len(got) == 1 and len(got[0]) == 1 and len(got[0][0]) == 2
    assert len(mm) == 1


def test_numeric_range_and_min_count():
    mm, got = make_tpu_mm(max_intervals=2)
    for r in (10, 12, 14):
        add(mm, "+properties.rank:>=5 +properties.rank:<=20", mn=3, mx=5, nums={"rank": r})
    mm.process()
    assert not got  # under max, not last interval
    mm.process()
    assert len(got) == 1 and len(got[0][0]) == 3


def test_party_and_session_semantics():
    mm, got = make_tpu_mm()
    party = [presence() for _ in range(3)]
    mm.add(party, "", "party-1", "*", 4, 4, 1, {}, {})
    add(mm, mn=4, mx=4)
    mm.process()
    assert len(got) == 1 and len(got[0][0]) == 4

    # A party must never match itself even across two tickets.
    mm2, got2 = make_tpu_mm()
    p1 = [presence(), presence()]
    p2 = [presence(), presence()]
    mm2.add(p1, "", "party-x", "*", 4, 4, 1, {}, {})
    mm2.add(p2, "", "party-x", "*", 4, 4, 1, {}, {})
    mm2.process()
    mm2.process()
    assert not got2


def test_host_only_regex_query_fallback():
    mm, got = make_tpu_mm()
    add(mm, "properties.maps:/.*(m1|m2).*/", strs={"maps": "m0,m1"})
    add(mm, "*", strs={"maps": "m1,m3"})
    mm.process()
    assert len(got) == 1  # regex active handled by host oracle path


def test_mutual_match_rev_precision_on_device():
    mm, got = make_tpu_mm(rev_precision=True)
    add(mm, "properties.a:x", strs={"a": "x"})  # accepts B ✓; B rejects A
    add(mm, "properties.a:never", strs={"a": "x"})
    mm.process()
    mm.process()
    assert not got

    mm2, got2 = make_tpu_mm(rev_precision=True)
    add(mm2, "properties.a:x", strs={"a": "x"})
    add(mm2, "properties.a:x", strs={"a": "x"})
    mm2.process()
    assert len(got2) == 1


def test_boost_ordering_device():
    mm, got = make_tpu_mm()
    add(mm, "*", strs={"pad": "1"})
    add(mm, "*", strs={"tier": "silver"})
    t_search, _ = add(
        mm, "properties.tier:gold^5 properties.tier:silver", strs={"tier": "none"}
    )
    t_gold, _ = add(mm, "*", strs={"tier": "gold"})
    mm.process()
    assert got
    for entry_set in got[0]:
        tickets = {e.ticket for e in entry_set}
        if t_search in tickets:
            assert t_gold in tickets


def test_count_multiple_on_device():
    mm, got = make_tpu_mm(max_intervals=1)
    for _ in range(5):
        add(mm, mn=2, mx=6, multiple=2)
    mm.process()
    assert got
    assert all(len(s) % 2 == 0 for s in got[0])


def test_slot_reuse_after_removal():
    mm, got = make_tpu_mm(pool_capacity=64, candidates_per_ticket=64)
    t, p = add(mm)
    mm.remove_session(p.session_id, t)
    for _ in range(40):
        add(mm, mn=2, mx=2)
    mm.process()
    assert len(got[0]) == 20


# ------------------------------------------------------------ oracle parity


def _random_pool(rng, n, party_frac=0.0, multiple=False):
    """Build identical ticket streams for two matchmakers."""
    specs = []
    for i in range(n):
        mode = rng.choice(["a", "b", "c"])
        rank = float(rng.integers(0, 100))
        lo, hi = sorted(rng.integers(0, 100, size=2).tolist())
        mn, mx = rng.choice([(2, 2), (2, 4), (3, 5)])
        mult = int(rng.choice([1, 2])) if multiple else 1
        q = (
            f"+properties.mode:{mode} "
            f"+properties.rank:>={lo} +properties.rank:<={hi}"
        )
        n_members = int(rng.choice([1, 2])) if party_frac and rng.random() < party_frac else 1
        specs.append(
            dict(
                query=q,
                mn=int(mn),
                mx=int(mx),
                mult=mult,
                strs={"mode": str(mode)},
                nums={"rank": rank},
                members=n_members,
            )
        )
    return specs


def _run(mm, specs, intervals=3):
    global _uid
    matched = []
    mm.on_matched = matched.append
    for i, s in enumerate(specs):
        members = [
            MatchmakerPresence(user_id=f"u{i}m{j}", session_id=f"s{i}m{j}")
            for j in range(s["members"])
        ]
        party = f"party-{i}" if s["members"] > 1 else ""
        mm.add(
            members,
            members[0].session_id if not party else "",
            party,
            s["query"],
            s["mn"],
            s["mx"],
            s["mult"],
            s["strs"],
            s["nums"],
        )
    for _ in range(intervals):
        mm.process()
    return matched


def _validate_matches(matched_batches, specs, mutual: bool):
    """Every produced match must satisfy member count constraints and session
    uniqueness. Query satisfaction is guaranteed one-directionally by the
    searching (active) ticket — always the LAST entries in a match — and in
    every direction only when rev_precision is on (reference semantics)."""
    count = 0
    for batch in matched_batches:
        for entry_set in batch:
            size = len(entry_set)
            idxs = [int(e.presence.user_id.split("m")[0][1:]) for e in entry_set]
            for i in idxs:
                s = specs[i]
                assert s["mn"] <= size <= s["mx"], (size, s)
                assert size % s["mult"] == 0
            sids = [e.presence.session_id for e in entry_set]
            assert len(sids) == len(set(sids))
            checkers = set(idxs) if mutual else {idxs[-1]}
            for i in checkers:
                s = specs[i]
                lo = int(s["query"].split(">=")[1].split(" ")[0])
                hi = int(s["query"].split("<=")[1].split(" ")[0])
                mode = s["strs"]["mode"]
                for j in idxs:
                    if j == i:
                        continue
                    assert specs[j]["strs"]["mode"] == mode
                    assert lo <= specs[j]["nums"]["rank"] <= hi
            count += size
    return count


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("rev", [False, True])
def test_parity_random_pools(seed, rev):
    rng = np.random.default_rng(seed)
    specs = _random_pool(rng, 48, party_frac=0.3, multiple=True)

    cfg = MatchmakerConfig(max_intervals=2, rev_precision=rev)
    cpu_mm = LocalMatchmaker(quiet_logger(), cfg)
    cpu_matches = _run(cpu_mm, specs)

    mm, _ = make_tpu_mm(max_intervals=2, rev_precision=rev)
    tpu_matches = _run(mm, specs)

    cpu_count = _validate_matches(cpu_matches, specs, mutual=rev)
    tpu_count = _validate_matches(tpu_matches, specs, mutual=rev)
    # Both backends must produce valid matches; the TPU path must match at
    # least as many entries as the CPU oracle (quality >= parity; SURVEY §7).
    assert tpu_count >= cpu_count


def test_parity_identical_on_1v1():
    # With min=max=2 and distinct scores the greedy outcome is deterministic:
    # both backends must produce the exact same pairings.
    rng = np.random.default_rng(7)
    specs = []
    for i in range(30):
        mode = rng.choice(["a", "b"])
        specs.append(
            dict(
                query=f"+properties.mode:{mode}",
                mn=2, mx=2, mult=1,
                strs={"mode": str(mode)},
                nums={},
                members=1,
            )
        )

    cfg = MatchmakerConfig(max_intervals=2)
    cpu_mm = LocalMatchmaker(quiet_logger(), cfg)
    cpu_matches = _run(cpu_mm, specs, intervals=1)
    mm, _ = make_tpu_mm(max_intervals=2)
    tpu_matches = _run(mm, specs, intervals=1)

    def pairs(batches):
        out = set()
        for batch in batches:
            for es in batch:
                out.add(tuple(sorted(e.presence.user_id for e in es)))
        return out

    assert pairs(cpu_matches) == pairs(tpu_matches)


def test_should_clause_on_high_index_numeric_field():
    # Regression: _pair_accepts64 indexed both v_num (numeric_fields wide)
    # and v_str (string_fields wide) with the shared sh_fld matrix; a
    # should-gated numeric range on a numeric field with index >=
    # string_fields raised IndexError and killed the whole interval.
    mm, got = make_tpu_mm(string_fields=4)
    # numeric cols: 3 builtins + f0..f4 fill all 8; f4 lands at col 7,
    # which is >= string_fields=4 — and the registry does NOT overflow, so
    # the tickets stay on the device path where _pair_accepts64 runs.
    nums = {f"f{i}": float(i) for i in range(5)}
    add(mm, "properties.f4:>=1", nums=nums)
    add(mm, "properties.f4:>=1", nums=nums)
    assert not mm.backend.host_only
    mm.process()
    assert len(got) == 1 and len(got[0][0]) == 2


def test_pipelined_slot_reuse_is_dropped():
    # Regression: under interval_pipelining, a slot freed and reused between
    # dispatch and collection was validated against the NEW occupant's exact
    # mirrors while the kernel scored the OLD occupant — the new ticket could
    # be delivered into a match the old one earned.
    mm, got = make_tpu_mm(interval_pipelining=True, max_intervals=10)
    t1, p1 = add(mm, "properties.mode:a", strs={"mode": "a"})
    t2, p2 = add(mm, "properties.mode:a", strs={"mode": "a"})
    mm.process()  # dispatch only: first pipelined interval collects nothing
    assert not got
    slot2 = mm.backend.pool.slot_of[t2]
    mm.remove([t2])
    # Wildcard query + mode=b values: validation against t3's own mirror
    # passes, but pairing it into t1's match violates t1's query.
    t3, p3 = add(mm, "*", strs={"mode": "b"})
    assert mm.backend.pool.slot_of[t3] == slot2  # LIFO free list reuses slot
    mm.process()  # collects interval-1 work referencing the reused slot
    matched_users = {
        e.presence.user_id for batch in got for match in batch for e in match
    }
    assert p3.user_id not in matched_users


def test_pipelined_dropped_match_reactivates_members():
    # Regression: a min==max ticket goes inactive after its single active
    # interval; under pipelining its work is collected one interval later,
    # and if that match is invalidated by churn the ticket was stranded
    # passively forever. Backends now reactivate members of dropped matches.
    mm, got = make_tpu_mm(interval_pipelining=True, max_intervals=10)
    t1, _ = add(mm, "properties.mode:a", strs={"mode": "a"})
    t2, _ = add(mm, "properties.mode:a", strs={"mode": "a"})
    mm.process()  # dispatch W1 (u1,u2)
    mm.remove([t2])
    add(mm, "*", strs={"mode": "b"})  # reuses t2's slot
    mm.process()  # W1's (t1,t2) match dropped via gen check; t1 reactivated
    # fresh compatible pair; with t1 reactivated everyone can still pair up
    p4 = add(mm, "properties.mode:a", strs={"mode": "a"})[1]
    p5 = add(mm, "properties.mode:a", strs={"mode": "a"})[1]
    for _ in range(6):
        mm.process()
    # every mode:a ticket must eventually match (t1 with the wildcard or a
    # fresh one; the fresh pair with each other) — nothing stranded
    assert len(mm) <= 1, (len(mm), [t.query for t in mm.tickets.values()])


def test_device_pool_rebuild_from_host_extract():
    """Checkpoint/resume (SURVEY §5): the device pool is reconstructible
    from the host ticket map at any time — extract() from a live TPU
    backend, insert() into a FRESH backend (simulating device-state loss
    or node handover), and the rebuilt pool forms the same matches."""
    mm1, got1 = make_tpu_mm(max_intervals=4)
    for i in range(12):
        mode = f"m{i % 2}"
        add(mm1, f"+properties.mode:{mode}", strs={"mode": mode})
    snapshot = mm1.extract()
    assert len(snapshot) == 12

    # Fresh matchmaker + fresh device backend: nothing survives but the
    # host-side extract.
    mm2, got2 = make_tpu_mm(max_intervals=4)
    mm2.insert(snapshot)
    assert len(mm2) == 12
    mm2.process()
    mm2.process()  # pipelined second pass if enabled (not in this helper)
    users = {
        e.presence.user_id for batch in got2 for match in batch for e in match
    }
    assert len(users) == 12  # everyone re-matched on the rebuilt pool


def test_host_only_budget_defers_overflow():
    """VERDICT r2 weak #6: the O(actives x pool) host-oracle fallback is
    budgeted per interval — overflow defers (oldest-first) instead of
    dragging the interval back to CPU-oracle speed, and deferred tickets
    still match on later intervals."""
    mm, got = make_tpu_mm(host_budget_per_interval=4, max_intervals=99)
    for _ in range(12):
        # Regex term → HostOnlyQuery → oracle fallback path.
        add(mm, "properties.maps:/.*m1.*/", strs={"maps": "m1"})
    assert len(mm.backend.host_only) == 12
    mm.process()
    # Budget 4 → at most 2 pairs formed the first interval.
    first = sum(len(batch) for batch in got)
    assert 0 < first <= 2
    for _ in range(6):
        mm.process()
    total_entries = sum(len(s) for batch in got for s in batch)
    assert total_entries == 12  # every deferred ticket eventually matched


# ------------------------------------------------------- device pairing


def _pairing_mm(**kw):
    """Synchronous big-path pool: pure 1v1, it takes the device
    pairing handshake."""
    defaults = dict(
        big_pool_threshold=64,
        interval_pipelining=False,
        candidates_per_ticket=128,  # complete lists: full pairing exists
        max_intervals=2,
    )
    defaults.update(kw)
    return make_tpu_mm(**defaults)


def _fill_pairs(mm, n, modes=4):
    users = []
    for i in range(n):
        m = i % modes
        _, p = add(
            mm,
            f"properties.mode:m{m}",
            strs={"mode": f"m{m}"},
        )
        users.append((p.user_id, m))
    return dict(users)


def test_device_pairing_runs_and_matches_validly():
    mm, got = _pairing_mm()
    calls = []
    import nakama_tpu.matchmaker.device2 as d2

    orig = d2.pair_partners
    d2.pair_partners = lambda *a, **kw: calls.append(1) or orig(*a, **kw)
    try:
        mode_of = _fill_pairs(mm, 128)
        assert mm.backend.pool.high_water >= 64
        mm.process()
    finally:
        d2.pair_partners = orig
    assert calls, "device pairing path did not run"
    matched = 0
    for batch in got:
        for entry_set in batch:
            assert len(entry_set) == 2
            a, b = entry_set
            # Exact validity: identical mode term both ways, distinct
            # sessions.
            assert mode_of[a.presence.user_id] == mode_of[b.presence.user_id]
            assert a.presence.session_id != b.presence.session_id
            matched += 2
    # 128 tickets in 4 equal mode buckets of 32: a full pairing exists;
    # the handshake must pair nearly everyone (leftovers retry, but with
    # k=16 dense compatibility there should be none).
    assert matched >= 120, matched


def test_device_pairing_respects_incompatible_tickets():
    mm, got = _pairing_mm()
    # 65 tickets in one mode (odd count: exactly one leftover) + 3 in a
    # lonely mode that can pair among themselves (one leftover each side).
    for i in range(65):
        add(mm, "properties.mode:x", strs={"mode": "x"})
    for i in range(3):
        add(mm, "properties.mode:y", strs={"mode": "y"})
    mm.process()
    for batch in got:
        for es in batch:
            m = {e.string_properties["mode"] for e in es}
            assert len(m) == 1  # never cross-mode
    # Leftovers: one x (odd), one y (odd) at most... 65+3 -> >= 66 matched
    total = sum(len(es) for batch in got for es in batch)
    assert total >= 64


@pytest.mark.parametrize("nonpair", [0, 1])
def test_pairs_are_chosen_by_pool_shape_alone(nonpair):
    """No option selects the grouping: a pure-1v1 big pool takes
    `pair_partners`, and one ticket that is no pair (min 3) sends the
    same pool to the native assembler."""
    mm, got = _pairing_mm()
    calls = []
    import nakama_tpu.matchmaker.device2 as d2

    orig = d2.pair_partners
    d2.pair_partners = lambda *a, **kw: calls.append(1) or orig(*a, **kw)
    try:
        for i in range(70):
            add(mm, "properties.mode:x", strs={"mode": "x"})
        for i in range(nonpair):
            add(mm, "properties.mode:x", mn=3, mx=3, strs={"mode": "x"})
        mm.process()
    finally:
        d2.pair_partners = orig
    (crumb,) = mm.tracing.recent(1)
    if nonpair:
        assert not calls
        assert crumb["kernel"]["kernel"] == "topk_candidates_big"
    else:
        assert calls
        assert crumb["kernel"]["kernel"] == (
            "topk_candidates_big+pair_partners"
        )
    assert sum(len(es) for b in got for es in b) >= 68


def test_device_pairing_parity_with_oracle_validity():
    # Same pool through the CPU oracle and the pairing path: the pairing
    # match SET need not be identical (parallel greedy vs sequential) but
    # every match must be one the oracle's rules accept, and the matched
    # coverage must not regress.
    specs = [("m%d" % (i % 3), i) for i in range(90)]
    cfg = MatchmakerConfig(max_intervals=2, backend="cpu")
    from nakama_tpu.matchmaker.local import CpuBackend

    cpu_mm = LocalMatchmaker(quiet_logger(), cfg, backend=CpuBackend())
    cpu_got = []
    cpu_mm.on_matched = cpu_got.append
    for m, i in specs:
        p = presence()
        cpu_mm.add(
            [p], p.session_id, "", f"properties.mode:{m}", 2, 2, 1,
            {"mode": m}, {},
        )
    cpu_mm.process()
    cpu_total = sum(len(es) for b in cpu_got for es in b)

    mm, got = _pairing_mm()
    for m, i in specs:
        p = presence()
        mm.add(
            [p], p.session_id, "", f"properties.mode:{m}", 2, 2, 1,
            {"mode": m}, {},
        )
    mm.process()
    tpu_total = sum(len(es) for b in got for es in b)
    assert tpu_total >= cpu_total - 2, (tpu_total, cpu_total)


def test_device_pairing_engages_under_pipelining():
    # The shipped default posture for a pure-1v1 big pool: pipelined
    # intervals + device pairing. The handshake must run, delivery must
    # land through the queued dispatch→collect flow (mid-gap collect,
    # no second process()), and matches must stay exactly valid.
    mm, got = _pairing_mm(interval_pipelining=True)
    calls = []
    import nakama_tpu.matchmaker.device2 as d2

    orig = d2.pair_partners
    d2.pair_partners = lambda *a, **kw: calls.append(1) or orig(*a, **kw)
    try:
        mode_of = _fill_pairs(mm, 128)
        mm.process()  # dispatch only: pipelined interval
        assert calls, "pairing handshake did not run under pipelining"
        assert not got  # delivery is mid-gap, not same-interval
        mm.backend.wait_idle(30)
        mm.collect_pipelined()
    finally:
        d2.pair_partners = orig
    matched = 0
    for batch in got:
        for entry_set in batch:
            assert len(entry_set) == 2
            a, b = entry_set
            assert mode_of[a.presence.user_id] == mode_of[b.presence.user_id]
            assert a.presence.session_id != b.presence.session_id
            matched += 2
    assert matched >= 120, matched


def test_pipelined_deadline_surface_and_guarded_collect():
    import time

    mm, got = make_tpu_mm(interval_pipelining=True, max_intervals=10)
    assert mm._next_cohort_deadline() is None
    add(mm, "properties.mode:a", strs={"mode": "a"})
    add(mm, "properties.mode:a", strs={"mode": "a"})
    mm.process()  # dispatch cohort 0
    deadline = mm._next_cohort_deadline()
    # Deadline = dispatch + one interval (15s default here), in the
    # future and bounded by it.
    now = time.perf_counter()
    assert deadline is not None and now < deadline <= now + 16
    assert mm.backend.pipeline_depth() == 1
    # Guard-style collect: block-joins the head cohort's assembly and
    # delivers it NOW — no second process(), no explicit wait_idle.
    batch = mm.collect_pipelined(block_until=time.perf_counter() + 30)
    assert batch is not None and len(batch) == 1
    assert len(got) == 1 and len(got[0][0]) == 2
    assert mm._next_cohort_deadline() is None
    assert mm.backend.pipeline_depth() == 0
    # The delivery ledger recorded the cohort, unslipped.
    deliveries = mm.backend.tracing.recent_deliveries()
    assert deliveries and deliveries[-1]["slipped"] is False


def test_pair_partners_pad_rows_do_not_clobber_slot0():
    # Regression (round-4 review): pad rows (active_slots == -1) used a
    # clamped scatter index of 0, overwriting slot 0's row mapping with
    # -1; the pairing path then reported the same pair from both sides
    # (duplicate slots -> double-free downstream).
    import jax.numpy as jnp

    from nakama_tpu.matchmaker.device2 import pair_partners

    cand = jnp.asarray(
        [[1, -1], [0, -1], [0, -1], [-1, -1]], dtype=jnp.int32
    )
    active = jnp.asarray([0, 1, 2, -1], dtype=jnp.int32)
    partner, formed, listed, ran = pair_partners(
        cand, active, cap=8, rounds=4)
    partner = np.asarray(partner)
    proposer = partner >= 0
    # the counters: one row each, pairs round by round, filled cells,
    # rows each round ran at (four rows: one step, or none once no row
    # is open)
    assert np.asarray(formed).shape == (1, 4)
    assert set(np.asarray(ran)[0].tolist()) <= {0, 4}
    assert np.asarray(ran)[0, 0] == 4
    assert int(np.asarray(formed).sum()) == int(proposer.sum())
    assert np.asarray(listed).tolist() == [3]
    pairs = {
        tuple(sorted((int(active[i]), int(partner[i]))))
        for i in np.nonzero(proposer)[0]
    }
    # Exactly one pair may claim slot 0; each pair reported once.
    assert len(pairs) == int(proposer.sum())
    flat = [s for p in pairs for s in p]
    assert len(flat) == len(set(flat))


def test_store_duplicate_id_readd_after_lazy_remove():
    # Regression (round-4 review): re-adding a ticket id that is still
    # in the undrained graveyard triggered the drain-retry path, which
    # retried with the PRE-drain slot and left the allocated slot on the
    # free list — the next add then popped an occupied slot.
    mm, got = make_tpu_mm()
    t1, p1 = add(mm, "properties.mode:q", strs={"mode": "q"})
    t2, p2 = add(mm, "properties.mode:q", strs={"mode": "q"})
    mm.process()  # both matched -> lazy (deferred) removal, no drain yet
    assert sum(len(es) for b in got for es in b) == 2
    # Re-add tickets with the SAME ids via insert (handover redelivery).
    from nakama_tpu.matchmaker.types import MatchmakerExtract

    mm.insert(
        [
            MatchmakerExtract(
                presences=[p1],
                session_id=p1.session_id,
                party_id="",
                query="properties.mode:q",
                min_count=2,
                max_count=2,
                count_multiple=1,
                string_properties={"mode": "q"},
                numeric_properties={},
                ticket=t1,
                created_at=1.0,
                intervals=0,
            )
        ]
    )
    assert t1 in mm.tickets
    # Allocator must stay consistent: a burst of fresh adds succeeds.
    for _ in range(8):
        add(mm, "properties.mode:z", strs={"mode": "z"})
    assert len(mm) == 1 + 8

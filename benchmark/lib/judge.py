"""The comparison that decides `correct`.

What it compares is what the timed path produced at the timed size:
every `matchmaker_matched` envelope that reached a benchmark session,
from the window's first tick to a minute past its close, against what
the clients sent (the seeded specs) under the plain reference's
semantics and against the matches the plain matcher forms from the same
tickets at the same ticks (`reference.py`). Nothing here reads the
program's own view of a match; a match is reconstructed from the
envelopes alone.

A session is one player. A party ticket (a spec with `count` > 1) is
held by `count` sessions that all hold its ticket id: a match's players
are grouped by that id, so a party that is split, a size outside one
ticket's bounds or off its `count_multiple`, or a query that accepts
only its own party is an invalid match; the plain matcher is fed one
row a ticket; the yield counts players.

Numbers compared, each with its limit (PERF.md section 2 gives the
readings the limits were set from):

  invalid_matches       matches the reference's semantics refuse        0
  in_two_matches        sessions that got envelopes of two matches      0
  envelope_errors       members without their envelope, envelopes
                        twice, to a stranger, or naming another ticket  0
  ingest_mismatch       members whose properties in the envelope are
                        not the ones their client sent                  0
  yield_shortfall       share of the eligible players that the plain
                        matcher matched and the program did not, net    cell's
  similarity_shortfall  1 - the program's mean pair similarity over
                        the plain matcher's (tickets with embeddings;
                        pairs of players of different tickets)          cell's
"""

from __future__ import annotations

from . import reference


def ticket_of(s) -> str:
    """The ticket a session's client holds; a session never acknowledged
    stands alone."""
    return s.ticket if s.ticket is not None else s.id


def tickets_of(indices: list[int], specs: list[dict]) -> tuple[int, ...]:
    """A match as the ticket index of each of its players, with a
    party's index once: the form `reference.mean_pair_similarity` reads."""
    seen = set()
    out = []
    for i in indices:
        if specs[i].get("count", 1) > 1:
            if i in seen:
                continue
            seen.add(i)
        out.append(i)
    return tuple(out)


def judge(sessions, rev: bool, eligible, limits: dict, ticks, k: int,
          max_intervals: int = 2) -> dict:
    """`sessions`: every BenchSession whose ticket was in the pool during
    the window. `eligible`: the subset the cell says must have been
    matched by the close if the plain matcher matched it. `limits`: the
    cell's {"yield_shortfall", "similarity_shortfall"}. `ticks`: when
    the interval loop ran (the session stamps' clock); `k`,
    `max_intervals`: the configuration's. Returns {"checks": [{"name",
    "value", "limit", "ok"}], "notes": [first faults seen], ...}."""
    by_id = {s.id: s for s in sessions}
    notes: list[str] = []

    def note(what: str) -> None:
        if len(notes) < 8:
            notes.append(what)

    matches: dict[str, dict] = {}
    envelope_errors = in_two = 0
    for s in sessions:
        keys = set()
        for body in s.matched:
            key = body.get("token") or body.get("match_id")
            if not key:
                envelope_errors += 1
                note(f"{s.id}: envelope without token or match_id")
                continue
            keys.add(key)
            rec = matches.setdefault(key, {"users": body["users"], "recv": []})
            rec["recv"].append(s.id)
            if body.get("ticket") != s.ticket:
                envelope_errors += 1
                note(f"{s.id}: envelope names ticket {body.get('ticket')},"
                     f" acked {s.ticket}")
            if body.get("self", {}).get("presence", {}).get(
                    "session_id") != s.id:
                envelope_errors += 1
                note(f"{s.id}: envelope's self is another session")
        if len(keys) > 1:
            in_two += 1
            note(f"{s.id}: in {len(keys)} matches")

    invalid = ingest = entries = 0
    for key, rec in matches.items():
        ids = [u["presence"]["session_id"] for u in rec["users"]]
        entries += len(ids)
        strangers = [i for i in ids if i not in by_id]
        if strangers:
            envelope_errors += len(strangers)
            note(f"match {key[:12]}: unknown member {strangers[0]}")
            continue
        for i in set(ids) | set(rec["recv"]):
            got, want = rec["recv"].count(i), ids.count(i)
            if got != want:
                envelope_errors += abs(got - want)
                note(f"match {key[:12]}: {i} received {got} envelopes,"
                     f" is listed {want} times")
        members = []
        for u in rec["users"]:
            s = by_id[u["presence"]["session_id"]]
            spec = s.spec
            strs = u.get("string_properties") or {}
            nums = u.get("numeric_properties") or {}
            if (
                strs != spec["strs"]
                or {k: float(v) for k, v in nums.items()} != spec["nums"]
                or u["presence"].get("user_id") != s.user_id
                or u["presence"].get("username") != s.username
            ):
                ingest += 1
                note(f"{s.id}: envelope carries {strs} {nums},"
                     f" sent {spec['strs']} {spec['nums']}")
            members.append(dict(
                session=s.id, query=spec["query"],
                min_count=spec["min_count"], max_count=spec["max_count"],
                strs=strs, nums=nums, ticket=ticket_of(s),
                count=spec.get("count", 1),
                count_multiple=spec.get("count_multiple", 1),
            ))
        fault = reference.match_fault(members, rev)
        if fault:
            invalid += 1
            note(f"match {key[:12]} of {len(members)}: {fault}")

    # The plain matcher over the same tickets at the same ticks: one
    # row a ticket, a party's sessions together, oldest first.
    held: dict[str, list] = {}
    for s in sorted((s for s in sessions if s.ack_t is not None),
                    key=lambda s: (s.ack_t, s.id)):
        held.setdefault(ticket_of(s), []).append(s)
    pooled = sorted(held.values(), key=lambda p: (p[0].ack_t, p[0].id))
    index = {s.id: i for i, party in enumerate(pooled) for s in party}
    specs = [party[0].spec for party in pooled]
    plain = reference.replay(
        specs, [party[0].ack_t for party in pooled], ticks, k, rev,
        max_intervals
    ) if len(pooled) >= 2 else []
    plain_matched = {i for g in plain for i in g}
    # The yield counts players: a party's sessions each.
    eligible = [s for s in eligible if s.id in index]
    shortfall = 0.0
    n_plain = sum(1 for s in eligible if index[s.id] in plain_matched)
    n_matched = sum(1 for s in eligible if s.matched)
    if eligible:
        shortfall = (n_plain - n_matched) / len(eligible)
    similarity = None
    want = reference.mean_pair_similarity(specs, plain)
    if want:
        formed = [
            tickets_of([index[u["presence"]["session_id"]]
                        for u in rec["users"]], specs)
            for rec in matches.values()
            if len(rec["users"]) >= 2 and all(
                u["presence"]["session_id"] in index for u in rec["users"])
        ]
        got = reference.mean_pair_similarity(specs, formed)
        similarity = 1.0 - (got or 0.0) / want

    def row(name, value, limit):
        return dict(name=name, value=value, limit=limit, ok=value <= limit)

    checks = [
        row("invalid_matches", invalid, 0),
        row("in_two_matches", in_two, 0),
        row("envelope_errors", envelope_errors, 0),
        row("ingest_mismatch", ingest, 0),
        row("yield_shortfall", shortfall, limits["yield_shortfall"]),
    ]
    if similarity is not None:
        checks.append(row("similarity_shortfall", similarity,
                          limits["similarity_shortfall"]))
    return dict(
        checks=checks,
        notes=notes,
        matches=len(matches),
        matched_entries=entries,
        eligible=len(eligible),
        eligible_plain_matched=n_plain,
        eligible_matched=n_matched,
        plain_matches=len(plain),
        plain_pair_similarity=want,
    )


def delivered(specs: list[dict], groups, ack_t) -> list:
    """Sessions as a run leaves them, had `groups` (tuples of indices
    into `specs`) been delivered as matches: how the plain matcher and
    the controls are put in the program's place. A ticket's `count`
    sessions all hold it (`t<index>`), in ticket order, and each of them
    gets its match's envelope."""
    from .session import BenchSession

    out = []
    parties = []
    for i, spec in enumerate(specs):
        party = []
        for _ in range(spec.get("count", 1)):
            s = BenchSession(len(out), spec, True)
            s.ticket = f"t{i}"
            s.ack_t = ack_t[i]
            out.append(s)
            party.append(s)
        parties.append(party)
    for n, group in enumerate(groups):
        members = [(m, s) for m in group for s in parties[m]]
        users = [{
            "presence": {"user_id": s.user_id, "session_id": s.id,
                         "username": s.username},
            "party_id": f"p{m}" if len(parties[m]) > 1 else "",
            "string_properties": specs[m]["strs"],
            "numeric_properties": specs[m]["nums"],
        } for m, s in members]
        for (_, s), user in zip(members, users):
            s.send({"matchmaker_matched": {
                "ticket": s.ticket, "users": users, "token": f"tok{n}",
                "self": {"presence": user["presence"]},
            }})
    return out


def players(sessions: list, tickets) -> list:
    """The sessions `delivered` made for the tickets at these indices
    into its `specs`: a party's each."""
    held = {f"t{i}" for i in tickets}
    return [s for s in sessions if s.ticket in held]

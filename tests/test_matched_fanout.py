"""A match's fan-out (api/matchmaker_events.py `on_matched`, the routers'
`send_envelopes`): every session of a match receives exactly one
`matchmaker_matched` envelope that is, as JSON, the one the plain form
below builds — the construction the handler had before its fan-out became
one pass over a match's entries and one router call — over the local
router and over the cluster's."""

from __future__ import annotations

import itertools
import json
import time
import uuid

import pytest

from fixtures import FakeSession, quiet_logger

from nakama_tpu.api import matchmaker_events, session_token
from nakama_tpu.api.matchmaker_events import make_matched_handler
from nakama_tpu.cluster.presence import ClusterMessageRouter
from nakama_tpu.matchmaker import MatchmakerPresence
from nakama_tpu.matchmaker.types import MatchmakerEntry
from nakama_tpu.realtime import LocalMessageRouter, LocalSessionRegistry

KEY = "k" * 32
NODE = "n1"
NOW = 1_790_000_000.25
ENTROPY = bytes(range(11, 11 + 64)) + bytes(range(250, 186, -1))
SIZES = (2, 4, 10)
OUTCOMES = ("token", "match_id")


class _Counter:
    def __init__(self):
        self.value = 0

    def inc(self, n=1):
        self.value += n


class _Metrics:
    def __init__(self):
        self.outgoing_dropped = _Counter()


class _Runtime:
    """A runtime whose matched hook takes every match."""

    def matchmaker_matched(self):
        return lambda entries: f"auth-{entries[0].ticket}.{NODE}"


class _Bus:
    """The cluster bus as the router uses it: frames kept, in order."""

    def __init__(self, ok=True):
        self.frames = []
        self.handlers = {}
        self.ok = ok

    def on(self, kind, handler):
        self.handlers[kind] = handler

    def send(self, node, kind, body):
        self.frames.append((node, kind, body))
        return self.ok


def _entry(k, j, ticket=None, party="", node="", props=False):
    return MatchmakerEntry(
        ticket=ticket or f"t{k}.{j}",
        presence=MatchmakerPresence(
            user_id=f"u{k}.{j}", session_id=f"s{k}.{j}",
            username=f'na"me{j}é', node=node,
        ),
        party_id=party,
        string_properties={"mode": f"m{j}", "region": "eu"} if props else {},
        numeric_properties={"rank": 1500.5 + j, "level": j} if props else {},
    )


def _match(k, size, node_of=lambda j: ""):
    """One match of `size` entries; a match of four holds a party ticket
    of two entries, with string and numeric properties among them."""
    if size == 4:
        return [
            _entry(k, 0, node=node_of(0), props=True),
            _entry(k, 1, ticket=f"party-t{k}", party=f"p{k}",
                   node=node_of(1), props=True),
            _entry(k, 2, ticket=f"party-t{k}", party=f"p{k}",
                   node=node_of(2)),
            _entry(k, 3, node=node_of(3), props=True),
        ]
    return [_entry(k, j, node=node_of(j), props=j % 3 == 0)
            for j in range(size)]


def _plain_envelopes(entries, outcome):
    """The envelopes of one match as the handler built them an entry at a
    time: `as_dict()` for `users` and again for `self`, the ticket looked
    up by session."""
    ticket_of = {e.presence.session_id: e.ticket for e in entries}
    users = [
        {
            "presence": e.presence.as_dict(),
            "party_id": e.party_id,
            "string_properties": e.string_properties,
            "numeric_properties": e.numeric_properties,
        }
        for e in entries
    ]
    envelopes = {}
    for entry in entries:
        body = {
            "ticket": ticket_of[entry.presence.session_id],
            "users": users,
            "self": {"presence": entry.presence.as_dict()},
        }
        body.update(outcome)
        envelopes[entry.presence.session_id] = {"matchmaker_matched": body}
    return envelopes


def _pin(monkeypatch):
    monkeypatch.setattr(time, "time", lambda: NOW)
    monkeypatch.setattr(
        matchmaker_events.os, "urandom",
        lambda n: bytes(itertools.islice(itertools.cycle(ENTROPY), n)),
    )


def _outcome(kind, k, entries):
    """What the match `k` of a pinned batch carries: the hook's match id,
    or the token `session_token.generate` mints of the pinned ids."""
    if kind == "match_id":
        return {"match_id": f"auth-{entries[0].ticket}.{NODE}"}
    cycle = bytes(itertools.islice(itertools.cycle(ENTROPY), 32 * (k + 1)))
    tid, mid = (
        str(uuid.UUID(bytes=cycle[i:i + 16], version=4))
        for i in (32 * k, 32 * k + 16)
    )
    token, _ = session_token.generate(
        KEY,
        ",".join(sorted(
            f"{e.presence.user_id}:{e.presence.username}" for e in entries
        )),
        "", 30,
        vars={"kind": "match_token", "node": NODE, "mid": f"{mid}.{NODE}"},
        token_id=tid,
    )
    return {"token": token}


def _same_json(got, expected):
    return json.dumps(got, sort_keys=True) == json.dumps(
        expected, sort_keys=True
    )


def _local_rig(kind, metrics=None):
    sessions = LocalSessionRegistry(quiet_logger())
    router = LocalMessageRouter(
        quiet_logger(), sessions, tracker=None, metrics=metrics
    )
    handler = make_matched_handler(
        quiet_logger(), router, NODE, KEY,
        runtime=_Runtime() if kind == "match_id" else None,
    )
    return sessions, handler


def _sessions_of(sessions, matches, skip=()):
    made = {}
    for entries in matches:
        for e in entries:
            sid = e.presence.session_id
            if sid in skip:
                continue
            made[sid] = FakeSession(sid, e.presence.user_id)
            sessions.add(made[sid])
    return made


@pytest.mark.parametrize("kind", OUTCOMES)
@pytest.mark.parametrize("size", SIZES)
def test_each_session_gets_one_envelope_equal_to_the_plain_form(
    monkeypatch, size, kind
):
    _pin(monkeypatch)
    sessions, handler = _local_rig(kind)
    matches = [_match(k, size) for k in range(3)]
    made = _sessions_of(sessions, matches)
    handler(matches)
    for k, entries in enumerate(matches):
        expected = _plain_envelopes(entries, _outcome(kind, k, entries))
        for e in entries:
            (got,) = made[e.presence.session_id].sent
            assert _same_json(got, expected[e.presence.session_id])
            # Key for key in the plain form's order too: a client that
            # reads the envelope as text sees the same bytes.
            assert json.dumps(got) == json.dumps(
                expected[e.presence.session_id]
            )
    assert handler.stages["publish_route_calls"] == 3
    assert handler.stages["publish_matches"] == 3
    assert handler.stages["publish_envelopes"] == 3 * size


@pytest.mark.parametrize("kind", OUTCOMES)
@pytest.mark.parametrize("size", SIZES)
def test_a_match_shares_its_presence_dicts_and_its_users_list(size, kind):
    """What the one pass saves, and why nothing may mutate an envelope:
    an entry's presence is one object under `users[i]` and under its own
    `self`, and `users` one list in all of a match's bodies."""
    sessions, handler = _local_rig(kind)
    entries = _match(0, size)
    made = _sessions_of(sessions, [entries])
    handler([entries])
    bodies = [
        made[e.presence.session_id].sent[0]["matchmaker_matched"]
        for e in entries
    ]
    for i, body in enumerate(bodies):
        assert body["users"] is bodies[0]["users"]
        assert body["self"]["presence"] is body["users"][i]["presence"]


@pytest.mark.parametrize("kind", OUTCOMES)
@pytest.mark.parametrize("size", SIZES)
def test_unknown_session_is_skipped_and_a_refused_send_is_counted(
    monkeypatch, size, kind
):
    _pin(monkeypatch)
    metrics = _Metrics()
    sessions, handler = _local_rig(kind, metrics)
    matches = [_match(k, size) for k in range(2)]
    gone = matches[0][1].presence.session_id
    full = matches[1][0].presence.session_id
    made = _sessions_of(sessions, matches, skip={gone})
    made[full].queue_full = True
    handler(matches)
    assert metrics.outgoing_dropped.value == 1
    assert made[full].sent == []
    for k, entries in enumerate(matches):
        expected = _plain_envelopes(entries, _outcome(kind, k, entries))
        for e in entries:
            sid = e.presence.session_id
            if sid in (gone, full):
                continue
            (got,) = made[sid].sent
            assert _same_json(got, expected[sid])


@pytest.mark.parametrize("kind", OUTCOMES)
@pytest.mark.parametrize("size", SIZES)
def test_cluster_router_ships_a_remote_recipient_its_own_frame(
    monkeypatch, size, kind
):
    """Odd entries were forwarded from node `f`: each gets one `route`
    frame carrying its own envelope, and `f`'s router hands it to the
    session; the even ones are local sessions' `send`."""
    _pin(monkeypatch)
    log = quiet_logger()
    metrics = _Metrics()
    bus, bus_f = _Bus(), _Bus()
    sessions, sessions_f = LocalSessionRegistry(log), LocalSessionRegistry(log)
    router = ClusterMessageRouter(
        log, sessions, tracker=None, metrics=metrics, bus=bus, node=NODE
    )
    router_f = ClusterMessageRouter(
        log, sessions_f, tracker=None, bus=bus_f, node="f"
    )
    handler = make_matched_handler(
        log, router, NODE, KEY,
        runtime=_Runtime() if kind == "match_id" else None,
    )
    # A local presence may name its node or leave it empty.
    matches = [
        _match(k, size, lambda j: "f" if j % 2 else ("", NODE)[k])
        for k in range(2)
    ]
    local, remote = {}, {}
    for entries in matches:
        for e in entries:
            sid = e.presence.session_id
            s = FakeSession(sid, e.presence.user_id)
            if e.presence.node == "f":
                remote[sid] = s
                sessions_f.add(s)
            else:
                local[sid] = s
                sessions.add(s)
    handler(matches)
    expected = {}
    for k, entries in enumerate(matches):
        expected.update(
            _plain_envelopes(entries, _outcome(kind, k, entries))
        )
    for sid, s in local.items():
        (got,) = s.sent
        assert _same_json(got, expected[sid])
    assert [(n, kind_) for n, kind_, _ in bus.frames] == [
        ("f", "route")
    ] * len(remote)
    assert [body["sids"] for _, _, body in bus.frames] == [
        [sid] for sid in remote
    ]  # entry order, one session a frame
    for _, _, body in bus.frames:
        bus_f.handlers["route"](NODE, json.loads(json.dumps(body)))
    for sid, s in remote.items():
        (got,) = s.sent
        assert _same_json(got, expected[sid])
    assert metrics.outgoing_dropped.value == 0
    assert handler.stages["publish_route_calls"] == 2

    # A frame the bus refuses counts its session as dropped.
    bus.ok = False
    handler(matches[:1])
    assert metrics.outgoing_dropped.value == size // 2

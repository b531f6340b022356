"""The match token the matched handler mints (api/matchmaker_events.py).

The handler serialises the token's payload itself, from parts built once
a handler and ids cut from one entropy read a batch, and signs it through
`session_token.sign`. Pinned here: the token is byte for byte what
`session_token.generate` returns for the same ids, users and time; it
parses to the same claims and joins the relayed match it names; its ids
are version-4 UUIDs, fresh for every match of every batch; and
`generate()` itself returns what it returned before `sign` existed.
"""

import base64
import itertools
import json
import os
import time
import uuid

import numpy as np
import pytest

from fixtures import FakeSession, quiet_logger

from nakama_tpu.api import matchmaker_events, session_token
from nakama_tpu.api.matchmaker_events import make_matched_handler
from nakama_tpu.api.pipeline import Components, Pipeline, PipelineError
from nakama_tpu.config import Config
from nakama_tpu.matchmaker import MatchmakerPresence
from nakama_tpu.matchmaker.types import (
    MatchBatch,
    MatchmakerEntry,
    MatchmakerTicket,
)
from nakama_tpu.realtime import (
    LocalMessageRouter,
    LocalSessionRegistry,
    LocalTracker,
    Stream,
    StreamMode,
)

KEY = "k" * 32
NOW = 1790000000.75
NAMES = {
    "plain": "alice",
    "quote": 'al"ice',
    "backslash": "al\\ice\\",
    "comma": "al,ice,",
    "colon": "al:ice:",
    "newline": "al\nice\r\n",
    "non_ascii": "ålïçé-アリス-\U0001f3ae",
}
SIZES = (2, 4, 10)


class _Router:
    """Keeps what the handler routes: (session id, body) in order."""

    def __init__(self):
        self.sent = []

    def send_envelopes(self, recipients):
        for _node, session_id, envelope in recipients:
            self.sent.append((session_id, envelope["matchmaker_matched"]))


def _match(k, size, name="n"):
    """One match's entries; user ids in no sorted order."""
    return [
        MatchmakerEntry(
            ticket=f"t{k}.{j}",
            presence=MatchmakerPresence(
                user_id=f"u{(7 * j + 3) % size}-{k}",
                session_id=f"s{k}.{j}",
                username=f"{name}{j}",
            ),
        )
        for j in range(size)
    ]


def _columnar(matches, per_ticket=1):
    """The matches as the interval path hands them: columns over a
    snapshot of tickets, `per_ticket` entries to a ticket (1: a pool of
    solo tickets; more: party tickets, a match's last one the shorter),
    the snapshot deferred to the first entry access."""
    tickets, offsets = [], [0]
    for entries in matches:
        for at in range(0, len(entries), per_ticket):
            own = entries[at:at + per_ticket]
            tickets.append(MatchmakerTicket(
                ticket=own[0].ticket, query="*", min_count=2, max_count=10,
                count_multiple=1, session_id="", party_id="", entries=own,
                string_properties={}, numeric_properties={}, created_at=0.0,
            ))
        offsets.append(len(tickets))
    ticket_at = np.empty(len(tickets), dtype=object)
    ticket_at[:] = tickets
    slots = np.arange(len(tickets), dtype=np.int32)
    batch = MatchBatch(
        np.array(offsets, dtype=np.int64), slots,
        counts=np.array([len(t.entries) for t in tickets], dtype=np.int32),
    )
    batch.bind_tickets(lambda: ticket_at[slots])
    return batch


def _user_list(entries):
    return ",".join(sorted(
        f"{e.presence.user_id}:{e.presence.username}" for e in entries
    ))


def _unb64(text):
    return base64.urlsafe_b64decode(text + "=" * (-len(text) % 4))


def _payload(token):
    return json.loads(_unb64(token.split(".")[1]))


def _pin(monkeypatch, entropy):
    """`time.time` and the OS's entropy, pinned; the reads made."""
    reads = []

    def urandom(n):
        reads.append(n)
        return bytes(itertools.islice(itertools.cycle(entropy), n))

    monkeypatch.setattr(time, "time", lambda: NOW)
    monkeypatch.setattr(matchmaker_events.os, "urandom", urandom)
    return reads


def _handler(node="n1", runtime=None):
    router = _Router()
    handler = make_matched_handler(
        quiet_logger(), router, node, KEY, runtime=runtime
    )
    return handler, router


# ------------------------------------------- one token, against generate()


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("size", SIZES)
def test_minted_token_is_generates_byte_for_byte(monkeypatch, size, name):
    entropy = bytes(range(7, 7 + 64)) + bytes(range(255, 191, -1))
    reads = _pin(monkeypatch, entropy)
    node = 'no"de\\1é'
    handler, router = _handler(node)
    matches = [_match(0, size, NAMES[name]), _match(1, size, NAMES[name])]
    handler(matches)
    assert reads == [16 * 2 * 2]  # one read for the batch's four ids
    for k, entries in enumerate(matches):
        bodies = [b for _, b in router.sent[k * size:(k + 1) * size]]
        tokens = {b["token"] for b in bodies}
        assert len(tokens) == 1 and len(bodies) == size
        (token,) = tokens
        # The ids as `uuid.uuid4` would make them of the same bytes.
        tid, mid = (
            str(uuid.UUID(bytes=entropy[i:i + 16], version=4))
            for i in (32 * k, 32 * k + 16)
        )
        expected, claims = session_token.generate(
            KEY, _user_list(entries), "", 30,
            vars={
                "kind": "match_token", "node": node, "mid": f"{mid}.{node}",
            },
            token_id=tid,
        )
        assert token == expected
        parsed = session_token.parse(KEY, token)
        claims.expires_at = float(int(claims.expires_at))  # as `exp` holds it
        assert parsed == claims
        assert parsed.expires_at == int(NOW + 30)
        assert parsed.user_id.count(":" + NAMES[name]) == size


@pytest.mark.parametrize("size", SIZES)
def test_token_ids_are_version_4_uuids_of_the_rfc_variant(size):
    handler, router = _handler("node-7")
    handler([_match(k, size) for k in range(64)])
    for _, body in router.sent[::size]:
        payload = _payload(body["token"])
        rendezvous, dot, node = payload["vrs"]["mid"].partition(".")
        assert (dot, node) == (".", "node-7")
        for text in (payload["tid"], rendezvous):
            parsed = uuid.UUID(text)
            assert str(parsed) == text  # canonical 8-4-4-4-12, lower case
            assert parsed.version == 4 and parsed.variant == uuid.RFC_4122


@pytest.mark.parametrize("size", SIZES)
async def test_match_join_takes_the_token_to_the_match_named_by_mid(size):
    config = Config()
    log = quiet_logger()
    sessions = LocalSessionRegistry(log)
    tracker = LocalTracker(log)
    router = LocalMessageRouter(log, sessions, tracker)
    pipeline = Pipeline(log, Components(
        config=config, tracker=tracker, router=router, status_registry=None,
    ))
    entries = _match(0, size, NAMES["quote"])
    joiners = []
    for e in entries:
        s = FakeSession(
            e.presence.session_id, e.presence.user_id, e.presence.username
        )
        sessions.add(s)
        joiners.append(s)
    make_matched_handler(
        log, router, config.name, config.session.encryption_key
    )([entries])
    mids = set()
    for s in joiners:
        (env,) = s.sent
        token = env["matchmaker_matched"]["token"]
        await pipeline._h_match_join(s, "j", {"token": token})
        match = s.sent[-1]["match"]
        assert match["authoritative"] is False
        assert match["match_id"] == _payload(token)["vrs"]["mid"]
        mids.add(match["match_id"])
    (mid,) = mids
    assert mid.endswith("." + config.name)
    stream = Stream(StreamMode.MATCH_RELAYED, subject=mid)
    assert {p.user_id for p in tracker.list_by_stream(stream)} == {
        e.presence.user_id for e in entries
    }


def _flip_payload_byte(token):
    head, payload, sig = token.split(".")
    raw = bytearray(_unb64(payload))
    at = raw.index(b"u0-0")
    raw[at + 1] = ord("1")  # another user id, still JSON
    forged = base64.urlsafe_b64encode(bytes(raw)).rstrip(b"=").decode()
    return ".".join((head, forged, sig))


@pytest.mark.parametrize("how", ["other_key", "payload_byte", "signature"])
@pytest.mark.parametrize("size", SIZES)
async def test_forged_token_is_refused(size, how):
    handler, router = _handler()
    handler([_match(0, size)])
    token = router.sent[0][1]["token"]
    assert session_token.parse(KEY, token).vars["kind"] == "match_token"
    if how == "other_key":
        key = "j" * 32
    else:
        key = KEY
        if how == "payload_byte":
            token = _flip_payload_byte(token)
            assert "u1-0:" in _payload(token)["uid"]
        else:
            token = token[:-4] + ("AAAA" if token[-4:] != "AAAA" else "BBBB")
    with pytest.raises(session_token.TokenError, match="bad signature"):
        session_token.parse(key, token)
    config = Config()
    config.session.encryption_key = key
    pipeline = Pipeline(quiet_logger(), Components(
        config=config, tracker=None, router=None, status_registry=None,
    ))
    with pytest.raises(PipelineError, match="invalid match token"):
        await pipeline._h_match_join(None, "j", {"token": token})


def test_expiry_is_read_for_each_match_not_once_a_batch(monkeypatch):
    clock = itertools.count(1790000000, 7)
    monkeypatch.setattr(time, "time", lambda: float(next(clock)))
    handler, router = _handler()
    handler([_match(k, 2) for k in range(5)])
    exps = [_payload(b["token"])["exp"] for _, b in router.sent[::2]]
    assert exps == [1790000030 + 7 * k for k in range(5)]


# ------------------------------------------------------------- the batch


HANDED_AS = {
    "list": list,
    "batch": MatchBatch.from_lists,
    "columns": _columnar,
    "party_columns": lambda matches: _columnar(matches, per_ticket=3),
}


@pytest.mark.parametrize("handed_as", HANDED_AS)
def test_two_batches_of_ten_thousand_share_no_id_and_no_token(handed_as):
    handler, router = _handler()
    for call in range(2):
        matches = [_match(10_000 * call + k, 2) for k in range(10_000)]
        handler(HANDED_AS[handed_as](matches))
        assert handler.stages["publish_tokens"] == 10_000 * (call + 1)
    assert handler.stages["publish_matches"] == 20_000
    assert handler.stages["publish_bulk_matches"] == (
        20_000 if "columns" in handed_as else 0
    )
    assert len(router.sent) == 40_000
    tokens, tids, mids = set(), set(), set()
    for (_, first), (_, second) in zip(router.sent[::2], router.sent[1::2]):
        # every entry of a match holds the match's token
        assert first["token"] == second["token"]
        assert first["ticket"] != second["ticket"]
        payload = _payload(first["token"])
        tokens.add(first["token"])
        tids.add(payload["tid"])
        mids.add(payload["vrs"]["mid"])
    assert len(tokens) == len(tids) == len(mids) == 20_000
    assert not tids & {m.partition(".")[0] for m in mids}


def test_entropy_is_read_once_a_batch_and_never_kept(monkeypatch):
    reads = []
    real = os.urandom

    def urandom(n):
        reads.append(n)
        return real(n)

    monkeypatch.setattr(matchmaker_events.os, "urandom", urandom)
    handler, _ = _handler()
    handler([_match(k, 4) for k in range(100)])
    handler([_match(k, 4) for k in range(100, 103)])
    handler(MatchBatch.from_lists([_match(200, 4)]))
    assert reads == [3200, 96, 32]


@pytest.mark.parametrize("n", [0, 1, 2, 257])
def test_uuid4_texts_are_canonical_version_4_from_one_read(monkeypatch, n):
    reads = []
    real = os.urandom

    def urandom(size):
        reads.append(size)
        return real(size)

    monkeypatch.setattr(matchmaker_events.os, "urandom", urandom)
    texts = matchmaker_events._uuid4_texts(n)
    assert reads == [16 * n]  # one read, 122 random bits an id of it
    assert len(texts) == len(set(texts)) == n
    for text in texts:
        assert [len(part) for part in text.split("-")] == [8, 4, 4, 4, 12]
        parsed = uuid.UUID(text)
        assert str(parsed) == text  # lower case, dashes where they belong
        assert parsed.version == 4 and parsed.variant == uuid.RFC_4122


@pytest.mark.parametrize("n", [1, 3, 64])
def test_uuid4_texts_are_uuid4s_of_the_same_bytes(monkeypatch, n):
    entropy = bytes((37 * i + 11) % 256 for i in range(16 * n))
    monkeypatch.setattr(matchmaker_events.os, "urandom", lambda size: entropy)
    assert matchmaker_events._uuid4_texts(n) == [
        str(uuid.UUID(bytes=entropy[i:i + 16], version=4))
        for i in range(0, 16 * n, 16)
    ]


@pytest.mark.parametrize("per_ticket", [1, 3], ids=["solo", "party"])
@pytest.mark.parametrize("size", SIZES)
def test_columns_give_the_envelopes_and_tokens_the_lists_give(
    monkeypatch, size, per_ticket
):
    """Byte for byte: ids pinned by the entropy, expiry by the clock."""
    _pin(monkeypatch, bytes(range(256)))
    matches = [_match(k, size, NAMES["non_ascii"]) for k in range(9)]
    as_lists, lists_router = _handler()
    as_lists(matches)
    as_columns, columns_router = _handler()
    as_columns(_columnar(matches, per_ticket))
    assert len(lists_router.sent) == 9 * size
    assert columns_router.sent == lists_router.sent
    assert json.dumps(columns_router.sent) == json.dumps(lists_router.sent)
    for key in ("publish_matches", "publish_envelopes", "publish_tokens",
                "publish_route_calls"):
        assert as_columns.stages[key] == as_lists.stages[key] > 0
    assert as_columns.stages["publish_bulk_matches"] == 9
    assert as_lists.stages["publish_bulk_matches"] == 0


@pytest.mark.parametrize("handed_as", HANDED_AS)
def test_the_account_is_on_stages_when_the_router_raises(handed_as):
    class Raises(_Router):
        def send_envelopes(self, recipients):
            if len(self.sent) == 3 * 4:
                raise OSError("router down")
            super().send_envelopes(recipients)

    router = Raises()
    handler = make_matched_handler(quiet_logger(), router, "n1", KEY)
    with pytest.raises(OSError, match="router down"):
        handler(HANDED_AS[handed_as]([_match(k, 4) for k in range(10)]))
    stages = handler.stages
    # The three matches that were routed, not the one that was not.
    assert stages["publish_matches"] == stages["publish_route_calls"] == 3
    assert stages["publish_envelopes"] == 12
    assert stages["publish_tokens"] == 4  # counted when minted
    assert stages["publish_bulk_matches"] == (
        3 if "columns" in handed_as else 0
    )
    for key in ("publish_materialise_s", "publish_hook_s", "publish_token_s",
                "publish_envelope_s", "publish_route_s"):
        assert stages[key] > 0.0
    # and the next batch adds to them
    router.sent.append(None)
    handler([_match(99, 4)])
    assert stages["publish_matches"] == 4


class _Runtime:
    """A runtime whose matched hook sends every third match to an
    authoritative match of its own."""

    def matchmaker_matched(self):
        def hook(entries):
            k = int(entries[0].ticket[1:].split(".")[0])
            return f"auth-{k}.n1" if k % 3 == 0 else ""

        return hook


@pytest.mark.parametrize("size", SIZES)
def test_a_hooks_matches_carry_no_token_and_are_not_counted(size):
    handler, router = _handler(runtime=_Runtime())
    handler([_match(k, size) for k in range(30)])
    for k in range(30):
        bodies = [b for _, b in router.sent[k * size:(k + 1) * size]]
        if k % 3 == 0:
            assert all(
                b["match_id"] == f"auth-{k}.n1" and "token" not in b
                for b in bodies
            )
        else:
            assert all(b["token"] and "match_id" not in b for b in bodies)
    stages = handler.stages
    assert stages["publish_matches"] == 30
    assert stages["publish_tokens"] == 30 - 10
    assert stages["publish_envelopes"] == 30 * size


# -------------------------------------------- generate(), as it always was


GENERATED = {
    "session": (
        ("4c2ae592-b2a7-445e-98ec-697694478b1c", "alice", 60),
        {"token_id": "00000000-0000-4000-8000-000000000002"},
        "eyJhbGciOiAiSFMyNTYiLCAidHlwIjogIkpXVCJ9.eyJ0aWQiOiAiMDAwMDAwMDAtMDA"
        "wMC00MDAwLTgwMDAtMDAwMDAwMDAwMDAyIiwgInVpZCI6ICI0YzJhZTU5Mi1iMmE3LTQ"
        "0NWUtOThlYy02OTc2OTQ0NzhiMWMiLCAidXNuIjogImFsaWNlIiwgImV4cCI6IDE3OTA"
        "wMDAwNjAsICJ2cnMiOiB7fX0.Jw6mz04PbmH3a2N941y8ink6dVWwjAoHiALarwG-1Qo",
    ),
    "match": (
        ('u-1:al"ice,u-2:b\\ob é', "nm", 30),
        {
            "vars": {"kind": "match_token", "node": "node1", "mid": "m.node1"},
            "token_id": "00000000-0000-4000-8000-000000000001",
        },
        "eyJhbGciOiAiSFMyNTYiLCAidHlwIjogIkpXVCJ9.eyJ0aWQiOiAiMDAwMDAwMDAtMDA"
        "wMC00MDAwLTgwMDAtMDAwMDAwMDAwMDAxIiwgInVpZCI6ICJ1LTE6YWxcImljZSx1LTI"
        "6Ylxcb2IgXHUwMGU5IiwgInVzbiI6ICJubSIsICJleHAiOiAxNzkwMDAwMDMwLCAidnJ"
        "zIjogeyJraW5kIjogIm1hdGNoX3Rva2VuIiwgIm5vZGUiOiAibm9kZTEiLCAibWlkIjo"
        "gIm0ubm9kZTEifX0.QiEbvCiUXZprPdrcjqZA-yQUfJ7DKUeKV8-1sLRE6Uo",
    ),
}


@pytest.mark.parametrize("which", GENERATED)
def test_generate_returns_the_token_it_returned_at_the_parent(
    monkeypatch, which
):
    """The literals were printed by the tree before `sign` existed, for
    this key, these arguments and this time."""
    monkeypatch.setattr(time, "time", lambda: NOW)
    args, kwargs, literal = GENERATED[which]
    token, claims = session_token.generate(
        "defaultencryptionkey", *args, **kwargs
    )
    assert token == literal
    assert claims == session_token.SessionClaims(
        token_id=kwargs["token_id"], user_id=args[0], username=args[1],
        expires_at=NOW + args[2], vars=kwargs.get("vars", {}),
    )
    parsed = session_token.parse("defaultencryptionkey", token)
    assert (parsed.token_id, parsed.user_id, parsed.username, parsed.vars) == (
        claims.token_id, claims.user_id, claims.username, claims.vars
    )

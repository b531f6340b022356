"""Matchmaker matched-event routing.

Parity with the tail of the reference Process loop (reference
server/matchmaker.go:377-435): for each formed match, consult the runtime's
MatchmakerMatched hook — a returned match id sends users to an authoritative
match; otherwise mint a short-lived match token (30s JWT naming every user)
for relayed-match rendezvous — then route a `matchmaker_matched` envelope to
every matched presence.

A match's fan-out is one pass over its entries and one router call
(`send_envelopes`). Per entry it makes a presence dict, a `users` element, a
`self` dict, a body, an envelope and a `(node, session_id, envelope)` triple,
and no more: the presence dict stands under both `users[i]["presence"]` and
the entry's `self["presence"]`, the `users` list and the token are one object
in all of a match's bodies, and the property dicts are the entries' own. So
nothing may mutate an envelope after it is built: a session that changed what
it was sent would change what the match's other sessions are yet to encode.
"""

from __future__ import annotations

import json
import os
import time
from binascii import hexlify
from json.encoder import encode_basestring_ascii
from time import perf_counter
from typing import Any

from ..logger import Logger
from ..matchmaker.types import MatchBatch, MatchmakerEntry
from . import session_token

MATCH_TOKEN_EXPIRY_SEC = 30

# Byte 6 and byte 8 of sixteen random ones with the version (4) and the
# variant (RFC 4122) set, for `bytes.translate` over a whole batch.
_UUID_VERSION_4 = bytes((b & 0x0F) | 0x40 for b in range(256))
_UUID_VARIANT_RFC = bytes((b & 0x3F) | 0x80 for b in range(256))
# Where each of an id's 32 hex digits stands in its 36 characters: the
# canonical 8-4-4-4-12, a dash at 8, 13, 18 and 23.
_UUID_HEX_AT = [i for i in range(36) if i not in (8, 13, 18, 23)]


def _uuid4_texts(n: int) -> list[str]:
    """`n` version-4 UUIDs in their canonical text, from one read of the
    OS's entropy: 122 random bits an id, as `uuid.uuid4` takes them,
    without its object. The hex goes digit column by digit column into a
    buffer that already holds the dashes, so an id is one slice."""
    raw = bytearray(os.urandom(16 * n))
    raw[6::16] = raw[6::16].translate(_UUID_VERSION_4)
    raw[8::16] = raw[8::16].translate(_UUID_VARIANT_RFC)
    digits = hexlify(raw)
    buf = bytearray(b"-") * (36 * n)
    for digit, at in enumerate(_UUID_HEX_AT):
        buf[at::36] = digits[digit::32]
    text = buf.decode("ascii")
    return [text[i:i + 36] for i in range(0, 36 * n, 36)]


def make_matched_handler(
    logger: Logger,
    router: Any,
    node: str,
    encryption_key: str,
    runtime: Any = None,
):
    log = logger.with_fields(subsystem="matchmaker.matched")

    # The match token is `session_token.generate(key, user_list, "", 30,
    # vars={"kind": "match_token", "node": node, "mid": f"{mid}.{node}"},
    # token_id=tid)` byte for byte (tests/test_match_token.py): what of
    # its payload no match changes is laid out here once, as `json.dumps`
    # lays it out, and a match fills in its two ids, its users and its
    # expiry.
    key = encryption_key.encode()
    node_json = json.dumps(node)
    before_tid = '{"tid": "'
    after_tid = '", "uid": '
    after_uid = ', "usn": "", "exp": '
    after_exp = (
        ', "vrs": {"kind": "match_token", "node": ' + node_json
        + ', "mid": "'
    )
    after_mid = "." + node_json[1:-1] + '"}}'

    # Where a publish goes, summed over the matches of one batch; the
    # matchmaker moves the sums onto the delivery call's ledger row and
    # zeroes them (local.py `_publish`). Five stamps a match, none an
    # entry: a match's bodies are all built before the first is routed,
    # and a match is routed before the next is built, so no session waits
    # for a later match's bodies. `publish_route_calls` counts the router
    # calls: one a match. `publish_bulk_matches` counts the matches that
    # came as slices of a columnar batch's one flat entry list: all of
    # them, or none where the producer handed entry lists.
    stages = dict(
        publish_matches=0, publish_envelopes=0, publish_tokens=0,
        publish_route_calls=0, publish_bulk_matches=0,
        publish_materialise_s=0.0, publish_hook_s=0.0,
        publish_token_s=0.0, publish_envelope_s=0.0, publish_route_s=0.0,
    )

    def on_matched(matched: list[list[MatchmakerEntry]]):
        # What no match changes is bound once a batch, and the sums are
        # locals until the batch ends, however it ends.
        hook = runtime.matchmaker_matched() if runtime is not None else None
        sign = session_token.sign
        now = time.time
        send_envelopes = router.send_envelopes
        bulk = isinstance(matched, MatchBatch) and matched.offsets is not None
        n_matches = n_envelopes = n_tokens = 0
        materialise_s = hook_s = envelope_s = route_s = 0.0
        t_batch = perf_counter()
        # Two ids a match, the rendezvous' and the token's own, from one
        # read for the batch; the read is the token stage's.
        next_id = iter(_uuid4_texts(2 * len(matched))).__next__
        t_next = perf_counter()
        token_s = t_next - t_batch
        try:
            for entries in matched:
                # Between two matches the time is the batch iterator's: a
                # columnar batch makes its entries before the first match,
                # in one pass, and a match is a slice of them.
                t_entries = perf_counter()
                match_id = ""
                if hook is not None:
                    try:
                        match_id = hook(entries) or ""
                    except Exception as e:
                        log.error(
                            "matchmaker matched hook error", error=str(e)
                        )
                t_hooked = perf_counter()

                if match_id:
                    outcome_key, outcome = "match_id", match_id
                else:
                    user_list = ",".join(
                        sorted(
                            [
                                f"{e.presence.user_id}:{e.presence.username}"
                                for e in entries
                            ]
                        )
                    )
                    # The token names a relayed-match rendezvous id every
                    # matched client can join (reference
                    # matchmaker.go:392-399).
                    outcome_key, outcome = "token", sign(
                        key,
                        (
                            f"{before_tid}{next_id()}{after_tid}"
                            f"{encode_basestring_ascii(user_list)}{after_uid}"
                            f"{int(now() + MATCH_TOKEN_EXPIRY_SEC)}"
                            f"{after_exp}{next_id()}{after_mid}"
                        ).encode(),
                    )
                    n_tokens += 1
                t_token = perf_counter()

                # One pass: an entry's presence dict is made once and
                # stands under both `users[i]` and the entry's own `self`;
                # `users` is one list for the whole match.
                users = []
                recipients = []
                for e in entries:
                    p = e.presence
                    presence = {
                        "user_id": p.user_id,
                        "session_id": p.session_id,
                        "username": p.username,
                    }
                    users.append({
                        "presence": presence,
                        "party_id": e.party_id,
                        "string_properties": e.string_properties,
                        "numeric_properties": e.numeric_properties,
                    })
                    body = {
                        "ticket": e.ticket,
                        "users": users,
                        "self": {"presence": presence},
                        outcome_key: outcome,
                    }
                    # Cluster: a forwarded ticket's presences carry their
                    # origin node — the cluster router ships the envelope
                    # there over the bus; single-node presences carry no
                    # node and stay local.
                    recipients.append(
                        (p.node or node, p.session_id,
                         {"matchmaker_matched": body})
                    )
                t_bodies = perf_counter()
                send_envelopes(recipients)
                n_matches += 1
                n_envelopes += len(recipients)
                materialise_s += t_entries - t_next
                hook_s += t_hooked - t_entries
                token_s += t_token - t_hooked
                envelope_s += t_bodies - t_token
                t_next = perf_counter()
                route_s += t_next - t_bodies
        finally:
            stages["publish_matches"] += n_matches
            stages["publish_route_calls"] += n_matches
            stages["publish_bulk_matches"] += n_matches if bulk else 0
            stages["publish_envelopes"] += n_envelopes
            stages["publish_tokens"] += n_tokens
            stages["publish_materialise_s"] += materialise_s
            stages["publish_hook_s"] += hook_s
            stages["publish_token_s"] += token_s
            stages["publish_envelope_s"] += envelope_s
            stages["publish_route_s"] += route_s

    on_matched.stages = stages
    return on_matched

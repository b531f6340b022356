"""Fan-in matchmaker ingest: N frontends → the owner-shard fleet.

Frontends run a `ClusterMatchmakerClient` behind the exact
LocalMatchmaker surface the pipeline, socket close path and party
registry already call: `add` validates synchronously (query syntax,
counts, per-session/party MaxTickets against the frontend's own
forwarded-ticket bookkeeping), mints the node-stamped ticket id
``<uuid>.<node>`` — the ID seam the reference threads for its
clustered edition — routes the ticket's pool/query-family key through
the epoch-versioned `ShardDirectory` (sharding.py), and forwards one
`mm.add` frame to the owning shard's current node. Removals forward
the same way; a dead owner degrades to a synchronous `ErrNotAvailable`
(the client retries), never a hang. The client RETAINS each forwarded
payload until the owner releases it: on a shard's epoch transition
(lease takeover) every pending ticket of that shard re-forwards to the
new owner with its ORIGINAL id — idempotent against the standby's
replicated shadow pool, and the closure of the replication-lag window
(an acknowledged ticket whose journal batch never shipped is re-added
by the frontend that still holds it).

On each owner, `ClusterMatchmakerIngest` feeds forwarded ops into the
real LocalMatchmaker (journaled like any local add, so a crash replays
them), refuses adds for shards it does not currently own
(``not_owner`` reject → the frontend re-routes instead of dropping),
stamps each add with the directory epoch so the peer-death sweep is
epoch-aware (a ticket re-added during a takeover must not be swept on
a stale observation), and `cluster_matched_handler` wraps the PR 4
delivery stage: matched cohorts route their envelopes back to each
ticket's origin node, notify origins so frontends release their
bookkeeping, and — when a target node is down — raise before delivery
so the PR 7 journal records the cohort `unpublished` and a restart
re-pools it."""

from __future__ import annotations

import time
import uuid

import numpy as np

from .. import overload
from .. import tracing as trace_api
from ..config import MatchmakerConfig
from ..logger import Logger
from ..matchmaker.local import (
    ErrDuplicateSession,
    ErrNotAvailable,
    ErrQueryInvalid,
    ErrTooManyTickets,
    MatchmakerError,
    ProcessBackend,
)
from ..matchmaker.query import QueryError, parse_query
from ..matchmaker.types import MatchmakerPresence
from .sharding import ShardDirectory, shard_key


# ClusterMatchmakerClient._meta entry indices.
M_SIDS, M_PARTY, M_AT, M_SHARD, M_PAYLOAD, M_REROUTES = range(6)


def _presences_to_wire(presences, node: str) -> list[dict]:
    return [
        {
            "u": p.user_id,
            "s": p.session_id,
            "n": p.username,
            "d": p.node or node,
        }
        for p in presences
    ]


def _presences_from_wire(rows, default_node: str):
    return [
        MatchmakerPresence(
            user_id=r["u"],
            session_id=r["s"],
            username=r.get("n", ""),
            node=r.get("d") or default_node,
        )
        for r in rows
    ]


class ClusterMatchmakerClient:
    """Frontend-side matchmaker: the LocalMatchmaker surface, forwarded.

    Holds only bookkeeping (ticket → session/party) so the synchronous
    error contract — ErrTooManyTickets, duplicate sessions, bad
    queries — is enforced at the socket without a bus round-trip; the
    owner re-validates authoritatively and rejects back (`mm.reject`)
    on disagreement (e.g. a session racing tickets through two
    frontends)."""

    # The seam's own bodies: no queue, no device, never attached.
    backend = ProcessBackend()

    # Re-forward budget: a ticket bounced with `not_owner` (map churn)
    # re-routes at most this many times before the client drops it —
    # a routing loop must cost one ticket, never a frame storm.
    MAX_REROUTES = 3

    def __init__(
        self,
        logger: Logger,
        config: MatchmakerConfig,
        bus,
        membership,
        node: str,
        owner: str = "",
        metrics=None,
        directory: ShardDirectory | None = None,
    ):
        self.logger = logger.with_fields(subsystem="matchmaker.cluster")
        self.config = config
        self.bus = bus
        self.membership = membership
        self.node = node
        self.metrics = metrics
        # Routing: the shared epoch-versioned directory when the plane
        # provides one; else the PR 10 single-owner degenerate map
        # (one shard named after the owner, never transitioning).
        self.directory = directory or ShardDirectory(
            node, [owner] if owner else [node], logger=logger
        )
        self.owner = owner  # compat: the single-owner deployments' target
        self.on_matched = None  # owner publishes; kept for wiring compat
        # The LocalMatchmaker surface the server and console read: no
        # interval runs here, the record holds this node's envelope
        # stages and ladder events.
        self.tracing = trace_api.Tracing()
        self.override_fn = None
        self.slo = None
        self.journal = None
        self.checkpointer = None
        self._session: dict[str, set[str]] = {}
        self._party: dict[str, set[str]] = {}
        # tid -> [sids, party, forwarded_at, shard, payload, reroutes]
        # (indexed by the M_* constants below — the takeover/reroute
        # paths mutate entries in place).
        self._meta: dict[str, list] = {}
        # Removal tombstones: a remove forwarded while its owner was
        # dying (or mid-takeover) may never have been journaled — on a
        # shard transition the tombstones re-forward to the new owner
        # so a cancelled ticket cannot resurrect out of the replicated
        # shadow pool. Bounded FIFO; idempotent at the receiver
        # (unknown-id removes are no-ops).
        self._tombstones: dict[str, str] = {}  # tid -> shard
        self.TOMBSTONE_CAP = 4096
        # Liveness valve for the local MaxTickets pre-check: a lost
        # `mm.matched`/`mm.reject` release frame (dropped bus frame,
        # owner restart) must not lock a session out of matchmaking
        # forever. Entries older than this lazily expire from the
        # LOCAL bookkeeping only — the owner stays the authoritative
        # enforcer (it re-checks and rejects back on overflow).
        # Epoch-aware: a shard transition REFRESHES its tickets' clocks
        # (they were just re-forwarded; their release frames now come
        # from the new owner, so the old owner's silence must not age
        # them out mid-takeover).
        self.bookkeeping_ttl_sec = max(
            300.0, 4.0 * config.interval_sec * config.max_intervals
        )
        self.directory.on_transition.append(self._on_shard_moved)
        self.directory.on_map_change.append(self._on_map_changed)
        bus.on("mm.matched", self._on_matched)
        bus.on("mm.reject", self._on_reject)

    # -------------------------------------------------------- lifecycle

    def start(self):
        pass  # no interval loop on frontends

    def stop(self):
        pass

    def pause(self):
        pass

    def resume(self):
        pass

    def __len__(self) -> int:
        return len(self._meta)

    @property
    def active(self):
        return self._meta  # len()-able console stand-in

    @property
    def tickets(self):
        return dict.fromkeys(self._meta)

    def _next_cohort_deadline(self):
        return None  # the owner owns delivery deadlines

    # -------------------------------------------------------------- add

    def add(
        self,
        presences,
        session_id: str,
        party_id: str,
        query: str,
        min_count: int,
        max_count: int,
        count_multiple: int = 1,
        string_properties=None,
        numeric_properties=None,
        embedding=None,
    ):
        dl = overload.current_deadline()
        if dl is not None and dl.expired():
            if self.metrics is not None:
                self.metrics.request_deadline_exceeded.labels(
                    stage="matchmaker"
                ).inc()
            raise overload.DeadlineExceeded(
                "caller deadline expired before matchmaker add"
            )
        if not presences:
            raise MatchmakerError("at least one presence required")
        if count_multiple < 1:
            raise MatchmakerError("count_multiple must be >= 1")
        if min_count < 1 or max_count < min_count:
            raise MatchmakerError("invalid min/max counts")
        if len(presences) > max_count:
            raise MatchmakerError("more presences than max_count")
        try:
            parse_query(query)
        except QueryError as e:
            raise ErrQueryInvalid(str(e)) from e
        seen: set[str] = set()
        for p in presences:
            if p.session_id in seen:
                raise ErrDuplicateSession(p.session_id)
            seen.add(p.session_id)
        self._expire_stale_bookkeeping()
        max_tickets = self.config.max_tickets
        for p in presences:
            if len(self._session.get(p.session_id, ())) >= max_tickets:
                raise ErrTooManyTickets(p.session_id)
        if party_id and len(self._party.get(party_id, ())) >= max_tickets:
            raise ErrTooManyTickets(party_id)
        shard, owner, _epoch = self.directory.route(
            shard_key(query, string_properties)
        )
        if not owner or (
            owner != self.node and not self.membership.is_up(owner)
        ):
            raise ErrNotAvailable(
                f"matchmaker owner node for shard {shard!r} unreachable"
            )

        ticket_id = f"{uuid.uuid4()}.{self.node}"
        created_at = time.time()
        payload = {
            "ticket": ticket_id,
            "presences": _presences_to_wire(presences, self.node),
            "sid": session_id,
            "pid": party_id,
            "q": query,
            "min": min_count,
            "max": max_count,
            "mult": count_multiple,
            "sp": dict(string_properties or {}),
            "np": dict(numeric_properties or {}),
            "at": created_at,
            "emb": (
                np.asarray(embedding, dtype=np.float32).tolist()
                if embedding is not None
                else None
            ),
        }
        try:
            sent = self.bus.send(owner, "mm.add", payload)
        except Exception as e:
            # An armed cluster.send fault or a writer race degrades to
            # the synchronous error contract, never a half-registered
            # ticket.
            raise ErrNotAvailable(
                f"matchmaker forward failed: {e}"
            ) from e
        if not sent:
            raise ErrNotAvailable("matchmaker forward dropped")
        for p in presences:
            self._session.setdefault(p.session_id, set()).add(ticket_id)
        if party_id:
            self._party.setdefault(party_id, set()).add(ticket_id)
        self._meta[ticket_id] = [
            [p.session_id for p in presences],
            party_id,
            time.monotonic(),
            shard,
            payload,
            0,
        ]
        if self.metrics is not None:
            self.metrics.cluster_forwards.labels(op="add").inc()
        sp = trace_api.current_span()
        if sp is not None:
            trace_api.emit_span(
                sp.trace_id, sp.span_id, "matchmaker.add",
                start_ts=created_at, end_ts=time.time(),
                ticket=ticket_id, query=query, forwarded_to=owner,
                shard=shard,
            )
        return ticket_id, created_at

    # ---------------------------------------------------------- removal

    def _expire_stale_bookkeeping(self):
        """Drop local bookkeeping entries whose release frame is long
        overdue (O(live tickets), amortized by the early-out)."""
        now = time.monotonic()
        stale = [
            tid
            for tid, m in self._meta.items()
            if now - m[M_AT] > self.bookkeeping_ttl_sec
        ]
        for tid in stale:
            self.logger.warn(
                "expiring stale forwarded-ticket bookkeeping (release"
                " frame lost?)",
                ticket=tid,
            )
            self._drop_bookkeeping(tid)

    def _drop_bookkeeping(self, ticket_id: str):
        meta = self._meta.pop(ticket_id, None)
        if meta is None:
            return
        sids, party_id = meta[M_SIDS], meta[M_PARTY]
        for sid in sids:
            tids = self._session.get(sid)
            if tids is not None:
                tids.discard(ticket_id)
                if not tids:
                    del self._session[sid]
        if party_id:
            tids = self._party.get(party_id)
            if tids is not None:
                tids.discard(ticket_id)
                if not tids:
                    del self._party[party_id]

    def _record_tombstone(self, ticket_id: str):
        """Remember a forwarded removal until well past any takeover:
        if the owner dies before the remove's journal row ships, the
        replicated shadow pool still holds the ticket — the shard
        transition re-sends these so a cancelled ticket cannot
        resurrect on the promoted owner."""
        m = self._meta.get(ticket_id)
        if m is None:
            return
        self._tombstones[ticket_id] = m[M_SHARD]
        while len(self._tombstones) > self.TOMBSTONE_CAP:
            self._tombstones.pop(next(iter(self._tombstones)))

    def _owner_for_ticket(self, ticket_id: str) -> str:
        """The ticket's shard owner, or "" (= broadcast to every
        owner) when the bookkeeping is gone — guessing one owner would
        silently drop the removal on a multi-shard fleet."""
        m = self._meta.get(ticket_id)
        if m is None:
            return ""
        return self.directory.owner_of(m[M_SHARD])[0]

    def _forward_remove(self, body: dict, owner: str | None = None):
        """Route a removal: per-ticket ops target the ticket's shard
        owner; scope ops (session_all, party_all, node) broadcast to
        every current owner — the scope may span shards."""
        targets = [owner] if owner else self.directory.owners()
        for target in targets:
            if not target:
                continue
            try:
                self.bus.send(target, "mm.remove", body)
            except Exception as e:
                # Best-effort: the owner also sweeps on session death /
                # node death; a lost remove costs one interval of a
                # ghost ticket, never a wedge.
                self.logger.warn("remove forward failed", error=str(e))
        if self.metrics is not None:
            self.metrics.cluster_forwards.labels(op="remove").inc()

    def remove_session(self, session_id: str, ticket_id: str):
        if ticket_id not in self._session.get(session_id, ()):
            raise MatchmakerError("ticket not found")
        self._forward_remove(
            {"op": "ticket", "ticket": ticket_id, "sid": session_id},
            owner=self._owner_for_ticket(ticket_id),
        )
        self._record_tombstone(ticket_id)
        self._drop_bookkeeping(ticket_id)

    def remove_session_all(self, session_id: str):
        tids = list(self._session.get(session_id, ()))
        self._forward_remove({"op": "session_all", "sid": session_id})
        for tid in tids:
            self._record_tombstone(tid)
            self._drop_bookkeeping(tid)

    def remove_party(self, party_id: str, ticket_id: str):
        if ticket_id not in self._party.get(party_id, ()):
            raise MatchmakerError("ticket not found")
        self._forward_remove(
            {"op": "party", "ticket": ticket_id, "pid": party_id},
            owner=self._owner_for_ticket(ticket_id),
        )
        self._record_tombstone(ticket_id)
        self._drop_bookkeeping(ticket_id)

    def remove_party_all(self, party_id: str):
        tids = list(self._party.get(party_id, ()))
        self._forward_remove({"op": "party_all", "pid": party_id})
        for tid in tids:
            self._record_tombstone(tid)
            self._drop_bookkeeping(tid)

    def remove(self, ticket_ids):
        by_owner: dict[str, list] = {}
        for tid in ticket_ids:
            by_owner.setdefault(
                self._owner_for_ticket(tid), []
            ).append(tid)
        for owner, tids in by_owner.items():
            self._forward_remove(
                {"op": "tickets", "tickets": tids}, owner=owner
            )
        for tid in ticket_ids:
            self._record_tombstone(tid)
            self._drop_bookkeeping(tid)

    def remove_all(self, node: str):
        if node != self.node:
            return
        tids = list(self._meta)
        self._forward_remove({"op": "node", "node": node})
        for tid in tids:
            self._drop_bookkeeping(tid)

    # ------------------------------------------------------ owner events

    def _on_matched(self, src: str, d: dict):
        """The owner matched (and routed envelopes for) these tickets:
        release the frontend's bookkeeping. The envelopes themselves
        arrive via `route` frames — this is bookkeeping-only."""
        for tid in d.get("tickets", ()):
            self._drop_bookkeeping(tid)
        if self.metrics is not None:
            self.metrics.cluster_forwards.labels(op="matched").inc()

    def _on_reject(self, src: str, d: dict):
        tid = d.get("ticket", "")
        reason = d.get("reason", "")
        meta = self._meta.get(tid)
        if reason.startswith("not_owner") and meta is not None:
            # Map churn: the targeted node no longer owns the shard.
            # Re-route through the (by now updated) directory instead
            # of dropping a live ticket — bounded, so a split map can
            # never ping-pong frames forever.
            meta[M_REROUTES] += 1
            if meta[M_REROUTES] <= self.MAX_REROUTES:
                owner = self.directory.owner_of(meta[M_SHARD])[0]
                sent = False
                if owner and owner != src:
                    meta[M_AT] = time.monotonic()
                    try:
                        sent = self.bus.send(
                            owner, "mm.add", meta[M_PAYLOAD]
                        )
                    except Exception as e:
                        # An armed cluster.send / writer race: fall
                        # through to the hold posture — the booking
                        # stays and the shard-transition re-forward
                        # (or TTL valve) covers it.
                        self.logger.warn(
                            "ticket re-route send failed; holding",
                            ticket=tid, error=str(e),
                        )
                    if sent and self.metrics is not None:
                        self.metrics.cluster_forwards.labels(
                            op="reroute"
                        ).inc()
                if not sent:
                    # Our map hasn't caught up with the takeover yet:
                    # KEEP the booking — the shard-moved re-forward
                    # (or, failing everything, the TTL valve) covers
                    # it. Dropping here would lose a live ticket to a
                    # frame race.
                    self.logger.warn(
                        "ticket bounced not_owner but the map still"
                        " points there; holding for the shard"
                        " transition",
                        ticket=tid, target=src,
                    )
                return
        self.logger.warn(
            "forwarded ticket rejected by owner",
            ticket=tid,
            reason=reason,
        )
        self._drop_bookkeeping(tid)
        if self.metrics is not None:
            self.metrics.cluster_forwards.labels(op="reject").inc()

    def _on_shard_moved(
        self, shard: str, old: str, new: str, epoch: int
    ):
        """Lease takeover observed: re-forward every pending ticket of
        the moved shard to its new owner under the ORIGINAL ticket id.
        Idempotent at the receiver (the replicated shadow pool absorbs
        duplicates via the id guard), and it closes the replication-lag
        window — a ticket acked here whose journal batch never shipped
        exists ONLY in this bookkeeping until this re-forward lands."""
        if new == self.node:
            return  # we became an owner (not a frontend concern)
        # Tombstones FIRST: a removal whose journal row never shipped
        # must not resurrect out of the replicated shadow pool. (The
        # re-forwarded adds below are for tickets still BOOKED — the
        # sets are disjoint, so ordering only matters for paranoia.)
        dead = sorted(
            tid for tid, sh in self._tombstones.items() if sh == shard
        )
        if dead:
            try:
                self.bus.send(
                    new, "mm.remove", {"op": "tickets", "tickets": dead}
                )
            except Exception:
                pass
        moved = [
            (tid, m)
            for tid, m in self._meta.items()
            if m[M_SHARD] == shard
        ]
        if not moved and not dead:
            return
        now = time.monotonic()
        sent = 0
        for tid, m in moved:
            # Epoch-aware TTL: the takeover resets the clock.
            m[M_AT] = now
            try:
                if self.bus.send(new, "mm.add", m[M_PAYLOAD]):
                    sent += 1
            except Exception:
                pass  # best-effort; the reject/re-route path covers it
        if self.metrics is not None:
            self.metrics.cluster_forwards.labels(op="reforward").inc(
                sent
            )
        self.logger.warn(
            "shard moved: re-forwarded pending tickets to new owner",
            shard=shard, old=old, new=new, epoch=epoch,
            tickets=len(moved), sent=sent, tombstones=len(dead),
        )

    def _on_map_changed(
        self, generation: int, old: list[str], new: list[str]
    ):
        """Reshard map edit observed: recompute every booked ticket's
        shard under the NEW keyspace and re-forward the ones that
        moved (idempotent at the receiver — the pre-minted-id guard
        absorbs duplicates, and a migrated copy is the same ticket).
        Rebinding `M_SHARD` here is what makes the later ownership
        transition (`_on_shard_moved`) pick these tickets up under
        their new shard id. Tombstones for retired shard ids broadcast
        to every owner — a cancelled ticket must not resurrect out of
        a migrated slice — then drop."""
        gone = set(old) - set(new)
        dead = sorted(
            tid for tid, sh in self._tombstones.items() if sh in gone
        )
        if dead:
            for owner in self.directory.owners():
                if owner and owner != self.node:
                    try:
                        self.bus.send(
                            owner,
                            "mm.remove",
                            {"op": "tickets", "tickets": dead},
                        )
                    except Exception:
                        pass
            for tid in dead:
                self._tombstones.pop(tid, None)
        now = time.monotonic()
        moved = sent = 0
        for tid, m in self._meta.items():
            p = m[M_PAYLOAD]
            shard = self.directory.shard_for_key(
                shard_key(p.get("q", "*"), p.get("sp") or {})
            )
            if shard == m[M_SHARD]:
                continue
            m[M_SHARD] = shard
            moved += 1
            owner = self.directory.owner_of(shard)[0]
            if not owner or owner == self.node:
                continue
            m[M_AT] = now  # re-forwarded: the TTL clock resets
            try:
                if self.bus.send(owner, "mm.add", p):
                    sent += 1
            except Exception:
                pass  # the reject/re-route or transition path covers it
        if self.metrics is not None and sent:
            self.metrics.cluster_forwards.labels(op="reforward").inc(
                sent
            )
        if moved or dead:
            self.logger.info(
                "shard map changed: rebooked moved tickets",
                generation=generation, moved=moved, sent=sent,
                tombstones=len(dead), retired=sorted(gone),
            )


class ClusterMatchmakerIngest:
    """Owner-side bus endpoints feeding the REAL LocalMatchmaker.

    Forwarded adds run the exact local `add` path (validation, slot
    registration, device on_add, PR 7 journal) under the origin's
    pre-minted node-stamped ticket id, so every downstream system —
    pool, journal, checkpoints, traces — sees cluster tickets as
    ordinary tickets whose presences carry a foreign node."""

    def __init__(
        self,
        matchmaker,
        bus,
        logger: Logger,
        metrics=None,
        directory: ShardDirectory | None = None,
        node: str | None = None,
    ):
        self.mm = matchmaker
        self.bus = bus
        self.logger = logger.with_fields(subsystem="matchmaker.ingest")
        self.metrics = metrics
        self.directory = directory
        self.node = node
        # tid -> directory epoch at add time: the peer-death sweep is
        # epoch-fenced (a ticket re-added during a takeover must not
        # be swept on a stale down-observation). Pruned lazily against
        # the live store.
        self._add_epoch: dict[str, int] = {}
        # Handover fence (reshard): when set, keys mid-migration bounce
        # back instead of landing in a pool slice that just parked.
        self.is_frozen = None
        bus.on("mm.add", self._on_add)
        bus.on("mm.remove", self._on_remove)

    def _owns_key(self, query: str, string_properties) -> bool:
        if self.directory is None or self.node is None:
            return True  # un-sharded rig (PR 10 compat): accept all
        _, owner, _ = self.directory.route(
            shard_key(query, string_properties)
        )
        return owner == self.node

    def _on_add(self, src: str, d: dict):
        tid = d.get("ticket", "")
        try:
            # Shape validation OUTSIDE the add call: a malformed frame
            # must reject back loudly, never be mistaken for the
            # duplicate-redelivery KeyError the dup guard raises.
            presences = _presences_from_wire(d["presences"], src)
            args = (
                d.get("sid", ""),
                d.get("pid", ""),
                d.get("q", "*"),
                int(d["min"]),
                int(d["max"]),
                int(d.get("mult", 1)),
                d.get("sp") or {},
                {k: float(v) for k, v in (d.get("np") or {}).items()},
            )
            embedding = (
                np.asarray(d["emb"], dtype=np.float32)
                if d.get("emb") is not None
                else None
            )
        except (KeyError, TypeError, ValueError) as e:
            self.bus.send(
                src,
                "mm.reject",
                {"ticket": tid, "reason": f"malformed add frame: {e}"},
            )
            return
        if not self._owns_key(d.get("q", "*"), d.get("sp") or {}):
            # Misrouted (stale map at the sender, or this node was
            # demoted): bounce it back — the frontend re-routes by its
            # updated directory instead of dropping the ticket.
            self.bus.send(
                src, "mm.reject", {"ticket": tid, "reason": "not_owner"}
            )
            return
        if self.is_frozen is not None and self.is_frozen(
            shard_key(d.get("q", "*"), d.get("sp") or {})
        ):
            # Mid-handover keyspace: the slice just parked here and is
            # being blessed to its new owner — an add landing now would
            # be silently stranded. Bounce; the frontend holds and
            # re-forwards on the ownership transition.
            self.bus.send(
                src,
                "mm.reject",
                {"ticket": tid, "reason": "not_owner:migrating"},
            )
            return
        try:
            self.mm.add(
                presences, *args,
                embedding=embedding,
                ticket_id=tid,
                created_at=d.get("at"),
            )
        except MatchmakerError as e:
            self.bus.send(
                src, "mm.reject", {"ticket": tid, "reason": str(e)}
            )
            return
        except KeyError:
            # Duplicate id (re-delivered frame / takeover re-forward of
            # a replicated ticket): already registered. Refresh the
            # epoch stamp — the re-delivery proves the origin is live
            # at the CURRENT epoch.
            pass
        if self.directory is not None:
            self._stamp_epoch(tid)

    def _stamp_epoch(self, tid: str) -> None:
        self._add_epoch[tid] = self.directory.max_epoch()
        if len(self._add_epoch) > 2 * len(self.mm.store) + 1024:
            # Lazy prune: removals don't notify the ingest, so drop
            # stamps whose tickets left the pool.
            store = self.mm.store
            self._add_epoch = {
                t: e for t, e in self._add_epoch.items() if t in store
            }

    def sweep_node(self, node: str, epoch: int | None = None) -> int:
        """Epoch-aware peer-death sweep: remove this dead frontend's
        tickets, SKIPPING any (re-)added at an epoch later than the
        down-observation — those are the new epoch's state (a takeover
        re-forward), not the dead peer's leftovers. `epoch=None` sweeps
        unconditionally (the PR 10 behavior)."""
        store = self.mm.store
        ticket_at = store.ticket_at
        tids = []
        for s in store.live_slots():
            t = ticket_at[s]
            if t is None or not any(
                e.presence.node == node for e in t.entries
            ):
                continue
            if (
                epoch is not None
                and self._add_epoch.get(t.ticket, 0) > epoch
            ):
                continue
            tids.append(t.ticket)
        if tids:
            self.mm.remove(tids)
        for tid in tids:
            self._add_epoch.pop(tid, None)
        return len(tids)

    def _on_remove(self, src: str, d: dict):
        op = d.get("op", "")
        try:
            if op == "ticket":
                self.mm.remove_session(d["sid"], d["ticket"])
            elif op == "session_all":
                self.mm.remove_session_all(d["sid"])
            elif op == "party":
                self.mm.remove_party(d["pid"], d["ticket"])
            elif op == "party_all":
                self.mm.remove_party_all(d["pid"])
            elif op == "tickets":
                self.mm.remove(d.get("tickets", ()))
            elif op == "node":
                self.mm.remove_all(d.get("node", src))
        except MatchmakerError:
            pass  # already matched/removed: the race is benign


def cluster_matched_handler(
    inner, bus, membership, node: str, logger: Logger, metrics=None,
    matchmaker=None,
):
    """Wrap the owner's `on_matched` (make_matched_handler) for the
    cluster, per-cohort: cohorts whose every origin node is UP deliver
    normally (envelopes routed back through the cluster router,
    `mm.matched` releasing frontend bookkeeping); a cohort with ANY
    down origin is HELD — raising PartialPublish after the healthy
    deliveries makes `_publish` hand only the held tickets to the PR 7
    journal as `unpublished`, so a restart re-pools exactly them. An
    interval must never hold its healthy cohorts hostage to one dead
    node, and must never re-pool a cohort whose players already saw
    the match.

    With `matchmaker` bound, each healthy cohort delivers inside a
    ``matchmaker.publish_back`` span continuing its first traced
    ticket's held trace — the outbound `route`/`mm.matched` frames
    then carry that traceparent, so the delivery frontend's dispatch
    span joins the SAME fleet trace the envelope started and the obs
    collector stitches admission → forward → pool → publish-back →
    delivery into one tree."""
    log = logger.with_fields(subsystem="matchmaker.cluster")

    def _cohort_trace(entries):
        if matchmaker is None:
            return None
        ctx_of = getattr(matchmaker, "trace_context", None)
        if ctx_of is None:
            return None
        for e in entries:
            ctx = ctx_of(e.ticket)
            if ctx is not None:
                return ctx
        return None

    def _deliver(entries):
        inner([entries])
        notify: dict[str, set[str]] = {}
        for e in entries:
            n = e.presence.node or node
            if n != node:
                notify.setdefault(n, set()).add(e.ticket)
        for n, tids in notify.items():
            try:
                # Best-effort bookkeeping release: a raise-mode
                # cluster.send must NOT escape here — the cohort's
                # players already hold their envelopes, so an escape
                # would journal the whole batch `unpublished` and
                # double-deliver after a restart (and skip every
                # later cohort this interval). A lost release frame
                # is covered by the frontend's TTL liveness valve.
                bus.send(n, "mm.matched", {"tickets": sorted(tids)})
            except Exception as e:
                log.warn(
                    "mm.matched release frame send failed (frontend"
                    " TTL valve will release the bookkeeping)",
                    peer=n, error=str(e),
                )

    def on_matched(batch):
        healthy = []
        held: set[str] = set()
        held_nodes: set[str] = set()
        for entries in batch:
            origin_nodes = {e.presence.node or node for e in entries}
            down = [
                n for n in origin_nodes
                if n != node and not membership.is_up(n)
            ]
            if down:
                held.update(e.ticket for e in entries)
                held_nodes.update(down)
            else:
                healthy.append(entries)
        for entries in healthy:
            ctx = _cohort_trace(entries)
            if ctx is not None:
                with trace_api.root_span(
                    "matchmaker.publish_back",
                    traceparent=trace_api.format_traceparent(*ctx),
                    cohort=len(entries),
                ):
                    _deliver(entries)
            else:
                _deliver(entries)
        if held:
            log.warn(
                "matched cohorts held: origin node(s) down —"
                " journaling unpublished for re-pool",
                nodes=sorted(held_nodes),
                held_tickets=len(held),
                delivered_cohorts=len(healthy),
            )
            from ..matchmaker.local import PartialPublish

            raise PartialPublish(
                held, reason=f"origin nodes down: {sorted(held_nodes)}"
            )

    # The publish stage sums are the inner handler's (local.py `_publish`).
    on_matched.stages = getattr(inner, "stages", None)
    return on_matched

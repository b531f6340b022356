"""Cluster-aware realtime layer: sharded presence + routed fan-out.

Each node OWNS its local socket sessions (the session registry stays
node-local); what clusters is the *view*: every presence write on a
node replicates to peers as a bus event, so each node's tracker holds
the union of local and remote presences under the PresenceID.node
component already embedded in every presence. Stream sends then route
per presence: local session ids deliver directly, remote ones ship a
`route` frame to the owning node — handler code (channels, matches,
parties, notifications) is unchanged, it already fans out by presence
ID.

Presence *events* are the one deliberate asymmetry: every node emits
join/leave envelopes to its OWN sessions from its replicated view, so
`route_presence_event` never crosses the bus (crossing it would
double-deliver). A node death sweeps its presences from every
survivor's view with real leave events — match and party registries
are notified through the same listeners a voluntary leave fires.
"""

from __future__ import annotations

from ..logger import Logger
from ..realtime.message_router import LocalMessageRouter
from ..realtime.session_registry import LocalSessionRegistry
from ..realtime.stream_manager import LocalStreamManager
from ..realtime.tracker import LocalTracker
from ..realtime.types import (
    Presence,
    PresenceEvent,
    PresenceID,
    PresenceMeta,
    Stream,
    StreamMode,
)


def _stream_to_wire(stream: Stream) -> dict:
    return {
        "m": int(stream.mode),
        "s": stream.subject,
        "c": stream.subcontext,
        "l": stream.label,
    }


def _stream_from_wire(d: dict) -> Stream:
    return Stream(
        mode=StreamMode(d["m"]),
        subject=d.get("s", ""),
        subcontext=d.get("c", ""),
        label=d.get("l", ""),
    )


def _presence_to_wire(p: Presence) -> dict:
    return {
        "sid": p.id.session_id,
        "uid": p.user_id,
        "st": _stream_to_wire(p.stream),
        "meta": {
            "f": p.meta.format,
            "h": p.meta.hidden,
            "p": p.meta.persistence,
            "u": p.meta.username,
            "s": p.meta.status,
        },
    }


def _presence_from_wire(node: str, d: dict) -> Presence:
    m = d.get("meta", {})
    return Presence(
        id=PresenceID(node, d["sid"]),
        stream=_stream_from_wire(d["st"]),
        user_id=d["uid"],
        meta=PresenceMeta(
            format=m.get("f", "json"),
            hidden=bool(m.get("h", False)),
            persistence=bool(m.get("p", True)),
            username=m.get("u", ""),
            status=m.get("s", ""),
        ),
    )


class ClusterTracker(LocalTracker):
    """LocalTracker + presence replication and node-death sweeps.

    Local presences live in the base double-index exactly as before
    (`_by_session` stays local-only — it backs untrack_all on socket
    close). Remote presences live in `_by_stream` (so listing, counts
    and routing see the cluster-wide view) plus a per-(node, session)
    side index that backs remote untrack_all and the death sweep."""

    def __init__(self, logger, node, metrics=None, event_queue_size=1024,
                 bus=None):
        super().__init__(logger, node, metrics, event_queue_size)
        self.bus = bus
        # (node, session_id) -> {stream: Presence} for REMOTE presences.
        self._remote: dict[tuple[str, str], dict[Stream, Presence]] = {}
        if bus is not None:
            bus.on("pr.track", self._on_remote_track)
            bus.on("pr.untrack", self._on_remote_untrack)
            bus.on("pr.untrack_all", self._on_remote_untrack_all)
            bus.on("pr.sync", self._on_remote_sync)

    # ------------------------------------------------ local + replicate

    def _replicate(self, ftype: str, body: dict) -> None:
        """Best-effort presence replication. Frames are fire-and-forget
        by DESIGN; a raise-mode `cluster.send` fault (or a bus mid-
        teardown) must cost the FRAME — a stale remote view healed by
        the next pr.sync — never turn the LOCAL presence write above it
        into an internal error. (Found by the PR 12 soak rig: an armed
        send fault was failing status updates and channel joins whose
        local work had already succeeded.)"""
        try:
            self.bus.broadcast(ftype, body)
        except Exception:
            self._repl_dropped = getattr(self, "_repl_dropped", 0) + 1

    def track(self, session_id, stream, user_id, meta,
              allow_if_first_for_session=False):
        ok, newly = super().track(
            session_id, stream, user_id, meta, allow_if_first_for_session
        )
        if ok and newly and self.bus is not None:
            p = self._by_session.get(session_id, {}).get(stream)
            if p is not None:
                self._replicate("pr.track", _presence_to_wire(p))
        return ok, newly

    def untrack(self, session_id, stream):
        existed = stream in self._by_session.get(session_id, {})
        super().untrack(session_id, stream)
        if existed and self.bus is not None:
            self._replicate(
                "pr.untrack",
                {"sid": session_id, "st": _stream_to_wire(stream)},
            )

    def untrack_all(self, session_id, reason=0):
        existed = bool(self._by_session.get(session_id))
        super().untrack_all(session_id, reason)
        if existed and self.bus is not None:
            self._replicate("pr.untrack_all", {"sid": session_id})

    def update(self, session_id, stream, user_id, meta):
        existed = stream in self._by_session.get(session_id, {})
        ok = super().update(session_id, stream, user_id, meta)
        if ok and existed and self.bus is not None:
            # Replace semantics at the receiver (leave+join pair). The
            # not-yet-tracked case fell through to track(), whose
            # override already broadcast.
            p = self._by_session.get(session_id, {}).get(stream)
            if p is not None:
                self._replicate("pr.track", _presence_to_wire(p))
        return ok

    # -------------------------------------------------- remote handlers

    def _apply_remote(self, node: str, p: Presence):
        key = (node, p.id.session_id)
        by_stream = self._remote.setdefault(key, {})
        old = by_stream.get(p.stream)
        by_stream[p.stream] = p
        self._by_stream.setdefault(p.stream, {})[p.id] = p
        self._emit(
            PresenceEvent(
                stream=p.stream,
                joins=[p],
                leaves=[old] if old is not None else [],
            )
        )

    def _on_remote_track(self, src: str, d: dict):
        if src == self.node:
            return  # self-echo guard (misconfigured peer list)
        self._apply_remote(src, _presence_from_wire(src, d))
        self._update_gauge()

    def _remove_remote(self, node: str, session_id: str, stream: Stream):
        key = (node, session_id)
        by_stream = self._remote.get(key)
        if not by_stream:
            return None
        p = by_stream.pop(stream, None)
        if p is None:
            return None
        if not by_stream:
            del self._remote[key]
        presences = self._by_stream.get(stream)
        if presences is not None:
            presences.pop(p.id, None)
            if not presences:
                del self._by_stream[stream]
        return p

    def _on_remote_untrack(self, src: str, d: dict):
        p = self._remove_remote(src, d["sid"], _stream_from_wire(d["st"]))
        if p is not None:
            self._emit(PresenceEvent(stream=p.stream, leaves=[p]))
            self._update_gauge()

    def _on_remote_untrack_all(self, src: str, d: dict):
        key = (src, d["sid"])
        by_stream = self._remote.pop(key, None)
        if not by_stream:
            return
        for stream, p in by_stream.items():
            presences = self._by_stream.get(stream)
            if presences is not None:
                presences.pop(p.id, None)
                if not presences:
                    del self._by_stream[stream]
            self._emit(PresenceEvent(stream=stream, leaves=[p]))
        self._update_gauge()

    def _on_remote_sync(self, src: str, d: dict):
        """Full-state resync from a peer (sent on every peer-up): diff
        against the current remote view — joins for new presences,
        leaves for vanished ones, no event churn for unchanged."""
        incoming = {}
        for pd in d.get("presences", ()):
            p = _presence_from_wire(src, pd)
            incoming[(p.id.session_id, p.stream)] = p
        # Leaves: anything held for src not in the snapshot.
        for (node, sid), by_stream in list(self._remote.items()):
            if node != src:
                continue
            for stream, p in list(by_stream.items()):
                if (sid, stream) not in incoming:
                    self._remove_remote(node, sid, stream)
                    self._emit(PresenceEvent(stream=stream, leaves=[p]))
        # Joins / replacements.
        for (sid, stream), p in incoming.items():
            held = self._remote.get((src, sid), {}).get(stream)
            if held is None or held != p:
                self._apply_remote(src, p)
        self._update_gauge()

    # ------------------------------------------------------- death sweep

    def sweep_node(self, node: str) -> int:
        """Remove every presence owned by a dead node, firing leave
        events locally (match/party registries + clients see the same
        leaves a voluntary disconnect fires). Returns swept count."""
        swept = 0
        per_stream: dict[Stream, list[Presence]] = {}
        for (n, sid), by_stream in list(self._remote.items()):
            if n != node:
                continue
            del self._remote[(n, sid)]
            for stream, p in by_stream.items():
                presences = self._by_stream.get(stream)
                if presences is not None:
                    presences.pop(p.id, None)
                    if not presences:
                        del self._by_stream[stream]
                per_stream.setdefault(stream, []).append(p)
                swept += 1
        for stream, leaves in per_stream.items():
            self._emit(PresenceEvent(stream=stream, leaves=leaves))
        if swept:
            self.logger.warn(
                "swept presences of dead node", node=node, count=swept
            )
            if self.metrics is not None:
                self.metrics.cluster_presence_sweeps.inc(swept)
        self._update_gauge()
        return swept

    # ----------------------------------------------------------- queries

    def local_presences(self) -> list[dict]:
        """Wire snapshot of every LOCAL presence (peer-up resync)."""
        out = []
        for by_stream in self._by_session.values():
            out.extend(_presence_to_wire(p) for p in by_stream.values())
        return out

    def count(self) -> int:
        return super().count() + sum(
            len(v) for v in self._remote.values()
        )

    def remote_count(self) -> int:
        return sum(len(v) for v in self._remote.values())


class ClusterMessageRouter(LocalMessageRouter):
    """LocalMessageRouter + cross-node routing by PresenceID.node:
    local presences deliver to local sessions, remote ones ship one
    `route` frame per owning node carrying the envelope (`send_envelopes`:
    one per remote recipient, each envelope being its own). Presence
    events stay node-local (each node emits them to its own sessions
    from its replicated tracker view)."""

    def __init__(self, logger, session_registry, tracker, metrics=None,
                 bus=None, node: str = "local"):
        super().__init__(logger, session_registry, tracker, metrics)
        self.bus = bus
        self.node = node
        self._presence_local_only = False
        if bus is not None:
            bus.on("route", self._on_route)

    def send_to_presence_ids(self, presence_ids, envelope):
        local = []
        remote: dict[str, list[str]] = {}
        for pid in presence_ids:
            if pid.node == self.node or not pid.node:
                local.append(pid)
            elif not self._presence_local_only:
                remote.setdefault(pid.node, []).append(pid.session_id)
        super().send_to_presence_ids(local, envelope)
        if self.bus is None:
            return
        for node, sids in remote.items():
            self._ship(node, sids, envelope)

    def send_envelopes(self, recipients):
        """Local recipients go to their sessions; a remote one ships its
        own `route` frame to its node (each envelope differs, so a frame
        a remote recipient)."""
        me = self.node
        get = self.sessions.get
        dropped = 0
        for node, session_id, envelope in recipients:
            if node == me or not node:
                session = get(session_id)
                if session is not None and not session.send(envelope):
                    dropped += 1
            elif self.bus is not None:
                self._ship(node, [session_id], envelope)
        if dropped and self.metrics:
            self.metrics.outgoing_dropped.inc(dropped)

    def _ship(self, node: str, sids: list[str], envelope: dict):
        """One `route` frame to `node`; a frame that did not leave counts
        its sessions as dropped."""
        try:
            ok = self.bus.send(node, "route", {"sids": sids, "env": envelope})
        except Exception as e:
            self.logger.warn(
                "cross-node route failed", node=node, error=str(e)
            )
            ok = False
        if not ok and self.metrics:
            self.metrics.outgoing_dropped.inc(len(sids))

    def route_presence_event(self, event):
        # Each node emits presence events to its OWN sessions from its
        # replicated view; forwarding them would double-deliver.
        self._presence_local_only = True
        try:
            super().route_presence_event(event)
        finally:
            self._presence_local_only = False

    def _on_route(self, src: str, d: dict):
        envelope = d.get("env") or {}
        for sid in d.get("sids", ()):
            session = self.sessions.get(sid)
            if session is None:
                continue
            if not session.send(envelope) and self.metrics:
                self.metrics.outgoing_dropped.inc()


class ClusterSessionRegistry(LocalSessionRegistry):
    """Sessions stay node-local; the cluster surface adds best-effort
    cross-node disconnect (single-session enforcement across nodes
    rides it: the node holding the older socket closes it)."""

    def __init__(self, logger: Logger, metrics=None, bus=None):
        super().__init__(logger, metrics)
        self.bus = bus
        if bus is not None:
            bus.on("sess.disconnect", self._on_disconnect)

    async def disconnect(self, session_id: str, reason: str = "") -> bool:
        if await super().disconnect(session_id, reason):
            return True
        if self.bus is not None:
            # Not local: ask every peer (ids are unique; at most one
            # node holds it). Best-effort — a down peer's sessions are
            # already gone, and a send fault costs the request only.
            try:
                self.bus.broadcast(
                    "sess.disconnect",
                    {"sid": session_id, "reason": reason},
                )
            except Exception:
                pass
        return False

    def _on_disconnect(self, src: str, d: dict):
        import asyncio

        sid = d.get("sid", "")
        if self.get(sid) is None:
            return
        asyncio.get_running_loop().create_task(
            LocalSessionRegistry.disconnect(
                self, sid, d.get("reason", "")
            )
        )


class ClusterStreamManager(LocalStreamManager):
    """Validated stream membership over the cluster view. Joins stay
    local-session-validated (a node can only join ITS sessions to a
    stream — the reference's clustered edition has the same shape);
    counts and listings read the tracker's replicated union, so a
    party/match admission check sees cluster-wide occupancy."""

    def __init__(self, logger, session_registry, tracker, bus=None):
        super().__init__(logger, session_registry, tracker)
        self.bus = bus

    def cluster_count_by_stream(self, stream: Stream) -> int:
        return self.tracker.count_by_stream(stream)

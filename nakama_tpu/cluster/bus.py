"""Cross-node message bus: length-prefixed frames over TCP/UDS.

One `ClusterBus` per node: a listener accepting inbound streams from
every peer, and one outbound link per peer (bounded queue + writer
task + per-peer `faults.CircuitBreaker` gating reconnects). The frame
protocol is deliberately dumb — 4-byte big-endian length + one codec
payload (JSON by default, msgpack when installed) carrying
``{"t": type, "s": source node, "p": traceparent, "d": body}`` — so a
frame is inspectable with `nc` and a codec mismatch fails loudly at
decode, never silently.

Failure semantics are the PR 3 degradation posture throughout: a dead
peer costs *frames* (bounded queue drops oldest, breaker decays the
reconnect rate), never memory or a wedged sender; an inbound handler
error costs that frame, never the reader. The `cluster.send` /
`cluster.recv` fault points let chaos prove it.

Tracing: `send` stamps the active span's W3C traceparent on the frame;
the receiving dispatch wraps the handler in a root span continuing
that trace — one trace id from a frontend's socket envelope to the
device-owner's pool and back.
"""

from __future__ import annotations

import asyncio
import json
import struct
import time
from typing import Any, Awaitable, Callable

from .. import faults
from .. import tracing as trace_api
from ..logger import Logger

_LEN = struct.Struct(">I")

Handler = Callable[[str, dict], Any | Awaitable[Any]]


class ClusterError(Exception):
    pass


class ClusterPeerDown(ClusterError):
    """The target node is not reachable (down peer / closed bus).
    Classified transient (OSError family) by callers that breaker it."""


def _codec(name: str):
    if name == "msgpack":
        try:
            import msgpack  # type: ignore

            return (
                lambda obj: msgpack.packb(obj, use_bin_type=True),
                lambda raw: msgpack.unpackb(raw, raw=False),
            )
        except ImportError:
            pass  # fall through: json is the always-available floor
    return (
        lambda obj: json.dumps(obj, separators=(",", ":")).encode(),
        lambda raw: json.loads(raw.decode()),
    )


def encode_frame(obj: dict, pack) -> bytes:
    payload = pack(obj)
    return _LEN.pack(len(payload)) + payload


def decode_frames(buf: bytearray, unpack, max_bytes: int):
    """Consume complete frames from `buf` (mutated in place), yielding
    decoded dicts. Raises ClusterError on an oversize frame — the
    caller drops the connection (the stream offset is unrecoverable)."""
    out = []
    while True:
        if len(buf) < _LEN.size:
            return out
        (n,) = _LEN.unpack(bytes(buf[: _LEN.size]))
        if n > max_bytes:
            raise ClusterError(f"oversize frame: {n} bytes")
        if len(buf) < _LEN.size + n:
            return out
        raw = bytes(buf[_LEN.size : _LEN.size + n])
        del buf[: _LEN.size + n]
        out.append(unpack(raw))


def _split_addr(addr: str):
    """`host:port` or `unix:/path` → ("tcp", host, port) | ("uds", path)."""
    if addr.startswith("unix:"):
        return ("uds", addr[5:], None)
    host, _, port = addr.rpartition(":")
    return ("tcp", host or "127.0.0.1", int(port))


class _PeerLink:
    """Outbound link to one peer: bounded deque + writer task. The
    breaker gates (re)connect attempts so a dead address is probed at a
    decaying rate; an open breaker drops frames instead of queueing
    into a black hole."""

    def __init__(self, bus: "ClusterBus", name: str, addr: str):
        self.bus = bus
        self.name = name
        self.addr = addr
        self.queue: list[bytes] = []
        self.wakeup = asyncio.Event()
        self.breaker = faults.CircuitBreaker(
            threshold=bus.breaker_threshold,
            cooldown_s=bus.breaker_cooldown_ms / 1000.0,
        )
        self.task: asyncio.Task | None = None
        self.writer: asyncio.StreamWriter | None = None
        self.connected = False
        self._connect_attempts = 0

    def enqueue(self, frame: bytes) -> bool:
        if len(self.queue) >= self.bus.send_queue_depth:
            # Drop-oldest: the newest frame is the one most likely to
            # still matter when the peer heals (heartbeats, sync).
            self.queue.pop(0)
            self.bus._drop("queue_full")
        self.queue.append(frame)
        self.wakeup.set()
        if self.bus.metrics is not None:
            self.bus.metrics.cluster_bus_queue_depth.labels(
                peer=self.name
            ).set(len(self.queue))
        return True

    async def run(self):
        while not self.bus._stopped:
            if self.writer is None:
                if not self.breaker.allow():
                    await asyncio.sleep(
                        min(0.2, self.breaker.base_cooldown_s)
                    )
                    continue
                try:
                    kind, host, port = _split_addr(self.addr)
                    if kind == "uds":
                        _, w = await asyncio.open_unix_connection(host)
                    else:
                        _, w = await asyncio.open_connection(host, port)
                    self.writer = w
                    self.connected = True
                    self._connect_attempts = 0
                    self.breaker.record_success()
                except Exception:
                    self.connected = False
                    self.breaker.record_failure()
                    # Paced, jittered retries: a peer that is merely
                    # booting later than us must not burn the breaker
                    # threshold in microseconds (boot-order race), and
                    # a dead address must not be hammered.
                    self._connect_attempts += 1
                    await asyncio.sleep(
                        0.02
                        + faults.jittered_backoff(
                            self._connect_attempts, 0.05, 1.0
                        )
                    )
                    continue
            if not self.queue:
                self.wakeup.clear()
                try:
                    await asyncio.wait_for(self.wakeup.wait(), 1.0)
                except asyncio.TimeoutError:
                    continue
            batch, self.queue = self.queue, []
            if self.bus.metrics is not None:
                self.bus.metrics.cluster_bus_queue_depth.labels(
                    peer=self.name
                ).set(0)
            try:
                self.writer.write(b"".join(batch))
                await self.writer.drain()
            except Exception:
                self._drop_conn()
                self.breaker.record_failure()
                # The batch is lost (frames are fire-and-forget; the
                # durable story rides the PR 7 journal above the bus).
                self.bus._drop("peer_down", n=len(batch))
        self._drop_conn()

    def _drop_conn(self):
        if self.writer is not None:
            try:
                self.writer.close()
            except Exception:
                pass
            self.writer = None
        self.connected = False


class ClusterBus:
    def __init__(
        self,
        node: str,
        bind: str,
        peers: dict[str, str],
        logger: Logger,
        metrics=None,
        *,
        send_queue_depth: int = 4096,
        max_frame_bytes: int = 4_194_304,
        breaker_threshold: int = 3,
        breaker_cooldown_ms: int = 1000,
        codec: str = "json",
    ):
        self.node = node
        self.bind = bind
        self.peers = dict(peers)
        self.logger = logger.with_fields(subsystem="cluster.bus")
        self.metrics = metrics
        self.send_queue_depth = send_queue_depth
        self.max_frame_bytes = max_frame_bytes
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_ms = breaker_cooldown_ms
        self._pack, self._unpack = _codec(codec)
        self._handlers: dict[str, Handler] = {}
        self._links: dict[str, _PeerLink] = {}
        self._server: asyncio.base_events.Server | None = None
        self._reader_tasks: set[asyncio.Task] = set()
        self._stopped = False
        self.port: int | None = None  # bound TCP port (tests use 0)
        # Called with the source node name on EVERY inbound frame —
        # membership piggybacks liveness on real traffic, so a chatty
        # peer never needs a heartbeat to stay up.
        self.frame_hook: Callable[[str], None] | None = None
        # Called with the peer name when add_peer registers one after
        # construction (membership tracks it from then on).
        self.peer_added_hook: Callable[[str], None] | None = None

    # ------------------------------------------------------------ wiring

    def on(self, frame_type: str, handler: Handler) -> None:
        """Register the handler for one frame type (sync or async;
        called as handler(src_node, body))."""
        self._handlers[frame_type] = handler

    def add_peer(self, name: str, addr: str) -> None:
        """Register a peer after start() (tests wire port-0 topologies
        this way; production uses the static config list). Membership
        learns of it through `peer_added_hook` — without that, its
        frames would be ignored (note_frame drops unknown sources) and
        the peer could never reach UP."""
        self.peers[name] = addr
        if self._server is not None and name not in self._links:
            link = _PeerLink(self, name, addr)
            self._links[name] = link
            link.task = asyncio.get_running_loop().create_task(link.run())
        if self.peer_added_hook is not None:
            self.peer_added_hook(name)

    # --------------------------------------------------------- lifecycle

    async def start(self):
        kind, host, port = _split_addr(self.bind)
        if kind == "uds":
            self._server = await asyncio.start_unix_server(
                self._accept, path=host
            )
        else:
            self._server = await asyncio.start_server(
                self._accept, host, port
            )
            self.port = self._server.sockets[0].getsockname()[1]
        for name, addr in self.peers.items():
            link = _PeerLink(self, name, addr)
            self._links[name] = link
            link.task = asyncio.get_running_loop().create_task(link.run())
        self.logger.info(
            "cluster bus listening",
            bind=self.bind,
            port=self.port,
            peers=sorted(self.peers),
        )

    async def stop(self):
        self._stopped = True
        for link in self._links.values():
            link.wakeup.set()
            if link.task is not None:
                link.task.cancel()
            link._drop_conn()
        # Readers first: since Python 3.12 `wait_closed()` waits for every
        # accepted connection to finish, and a reader parked in `read()`
        # never does — two buses stopping together then wait on each
        # other's readers forever.
        for t in list(self._reader_tasks):
            t.cancel()
        if self._server is not None:
            self._server.close()
            try:
                await self._server.wait_closed()
            except Exception:
                pass
            self._server = None

    # -------------------------------------------------------------- send

    def send(self, peer: str, frame_type: str, body: dict) -> bool:
        """Enqueue one frame for `peer`. Returns False when the frame
        was dropped (unknown peer, open breaker, armed fault) — the
        degradation posture, never an unbounded queue or a block. An
        armed raise-mode `cluster.send` propagates to the caller (the
        matchmaker proxy maps it to ErrNotAvailable; chat fan-out
        catches and counts). The frame carries the AMBIENT span's
        traceparent — the matched publish-back wraps each cohort's
        delivery in a span continuing its ticket's trace, so route
        frames land in the same fleet trace the envelope started."""
        if self._stopped:
            return False
        link = self._links.get(peer)
        if link is None:
            self._drop("peer_down")
            return False
        if faults.fire("cluster.send"):
            self._drop("fault")
            return False
        if link.breaker.state == faults.OPEN:
            self._drop("breaker_open")
            return False
        frame = {
            "t": frame_type,
            "s": self.node,
            "p": trace_api.current_traceparent() or "",
            # Send-side wall stamp: the receiver's dispatch span (and
            # the fleet collector's stitched view) read per-hop bus
            # latency off it — cross-node clocks, so the collector
            # corrects it with its offset estimates, skew shown.
            "w": time.time(),
            "d": body,
        }
        raw = encode_frame(frame, self._pack)
        if len(raw) > self.max_frame_bytes:
            self._drop("oversize")
            return False
        if self.metrics is not None:
            self.metrics.cluster_frames.labels(
                type=frame_type, direction="sent"
            ).inc()
        return link.enqueue(raw)

    def broadcast(self, frame_type: str, body: dict) -> int:
        """Send to every peer; returns how many enqueued."""
        return sum(
            1 for name in self._links if self.send(name, frame_type, body)
        )

    def peer_connected(self, peer: str) -> bool:
        link = self._links.get(peer)
        return bool(link is not None and link.connected)

    # -------------------------------------------------------------- recv

    async def _accept(self, reader: asyncio.StreamReader, writer):
        task = asyncio.current_task()
        self._reader_tasks.add(task)
        buf = bytearray()
        try:
            while not self._stopped:
                chunk = await reader.read(65536)
                if not chunk:
                    break
                buf.extend(chunk)
                try:
                    frames = decode_frames(
                        buf, self._unpack, self.max_frame_bytes
                    )
                except ClusterError as e:
                    self.logger.warn(
                        "bus stream dropped (oversize frame)",
                        error=str(e),
                    )
                    self._drop("oversize")
                    break
                except Exception as e:
                    # Codec mismatch / corrupt payload: the stream
                    # offset is unrecoverable — drop the connection,
                    # counted under its OWN reason so an operator is
                    # not pointed at max_frame_bytes.
                    self.logger.warn(
                        "bus stream dropped (bad frame)", error=str(e)
                    )
                    self._drop("bad_frame")
                    break
                for frame in frames:
                    await self._dispatch(frame)
        except (asyncio.CancelledError, Exception):
            pass
        finally:
            self._reader_tasks.discard(task)
            try:
                writer.close()
            except Exception:
                pass

    async def _dispatch(self, frame: dict):
        src = frame.get("s", "")
        ftype = frame.get("t", "")
        if self.frame_hook is not None:
            try:
                self.frame_hook(src)
            except Exception:
                pass
        try:
            if faults.fire("cluster.recv"):
                self._drop("fault")
                return
        except Exception as e:
            # An armed raise-mode recv fault costs this frame, never
            # the reader loop.
            self.logger.warn("bus recv fault", error=str(e))
            return
        handler = self._handlers.get(ftype)
        if handler is None:
            return
        if self.metrics is not None:
            self.metrics.cluster_frames.labels(
                type=ftype, direction="received"
            ).inc()
        tp = frame.get("p") or ""
        t0 = time.time()
        sent_at = frame.get("w")
        span_attrs = {"src": src}
        if sent_at is not None:
            span_attrs["bus_sent_at"] = sent_at
        try:
            if tp:
                # Continue the sender's trace: the bus hop becomes a
                # span in the SAME trace the envelope started.
                with trace_api.root_span(
                    f"cluster.{ftype}", traceparent=tp, **span_attrs
                ):
                    result = handler(src, frame.get("d") or {})
                    if asyncio.iscoroutine(result):
                        await result
            else:
                result = handler(src, frame.get("d") or {})
                if asyncio.iscoroutine(result):
                    await result
        except Exception as e:
            self.logger.error(
                "bus handler error",
                type=ftype,
                src=src,
                error=str(e),
                elapsed_ms=round((time.time() - t0) * 1000, 2),
            )

    # ------------------------------------------------------------- misc

    def _drop(self, reason: str, n: int = 1):
        if self.metrics is not None:
            self.metrics.cluster_bus_dropped.labels(reason=reason).inc(n)

    def stats(self) -> dict:
        return {
            "node": self.node,
            "peers": {
                name: {
                    "connected": link.connected,
                    "queued": len(link.queue),
                    "breaker": link.breaker.state,
                }
                for name, link in self._links.items()
            },
        }

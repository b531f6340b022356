"""The benchmark's client: the minimal session the realtime layer needs.

Registered in `server.session_registry`, it receives what a socket
session would and stamps the host clock when the `matchmaker_ticket`
ack and each `matchmaker_matched` envelope arrive. Every end-to-end
latency is read from these stamps, so it is taken on the client's side
of the router.
"""

from __future__ import annotations

import time


class BenchSession:
    __slots__ = (
        "id", "user_id", "username", "format", "spec", "in_window",
        "due_t", "ack_t", "ticket", "matched_t", "matched", "errors",
    )

    def __init__(self, seq: int, spec: dict, in_window: bool):
        self.id = f"bs{seq:08d}"
        self.user_id = f"bu{seq:08d}"
        self.username = f"bn{seq:08d}"
        self.format = "json"
        self.spec = spec
        self.in_window = in_window
        self.due_t = None  # when the add was due (open loop) or made
        self.ack_t = None  # matchmaker_ticket arrived (or mm.add returned)
        self.ticket = None
        self.matched_t = None  # the FIRST matchmaker_matched arrived
        self.matched = []  # every matchmaker_matched body received
        self.errors = []

    def send(self, envelope: dict) -> bool:
        now = time.perf_counter()
        if "matchmaker_matched" in envelope:
            if self.matched_t is None:
                self.matched_t = now
            self.matched.append(envelope["matchmaker_matched"])
        elif "matchmaker_ticket" in envelope:
            self.ack_t = now
            self.ticket = envelope["matchmaker_ticket"]["ticket"]
        elif "error" in envelope:
            self.errors.append(envelope["error"])
        return True

    async def close(self, reason: str = "", **kw) -> None:
        pass

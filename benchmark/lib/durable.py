"""The journal, read back with plain file code.

Both configurations state that every acknowledged add is journaled. The
journal is the table `matchmaker_journal` of the server's database,
which the configurations put in a file of the run's data directory. Once
the window has closed and its cohorts have drained, that file is opened
read-only by SQLite's own library, nothing of the program's, and every
ticket the clients hold an acknowledgement for has to be the `ticket`
of an `add` row. The journal drains in the background, so a row that
comes late is waited for, `wait_s` at the most; one that never comes is
an acknowledged add that a crash would lose.
"""

from __future__ import annotations

import asyncio
import os
import sqlite3
import time


def journaled_adds(path: str) -> set[str]:
    """The `ticket` of every `add` row in the database file at `path`."""
    if not os.path.isfile(path):
        return set()
    con = sqlite3.connect(f"file:{path}?mode=ro", uri=True, timeout=10.0)
    try:
        return {t for (t,) in con.execute(
            "SELECT json_extract(payload, '$.ticket')"
            " FROM matchmaker_journal WHERE op = 'add'")}
    except sqlite3.OperationalError:
        return set()  # no such table: nothing was journaled
    finally:
        con.close()


async def acked_not_durable(path: str, tickets, wait_s: float = 10.0) -> dict:
    """How many of `tickets` (acknowledged ticket ids) the journal file
    does not hold, and how long the last of them was waited for."""
    tickets = set(tickets)
    t = time.perf_counter()
    while True:
        missing = tickets - journaled_adds(path)
        waited = time.perf_counter() - t
        if not missing or waited >= wait_s:
            return dict(missing=len(missing), acked=len(tickets),
                        waited_s=round(waited, 2),
                        first=sorted(missing)[:3])
        await asyncio.sleep(0.25)  # the drain runs on this loop

"""Traffic kind `open_loop`: independent clients at a fixed rate.

Every add is one `matchmaker_add` envelope through `Pipeline.process`,
one session per ticket, timed from the moment it was DUE: an add the
server's loop kept waiting is late by what it waited. The tickets and
the arrival times (a Poisson process conditioned on its count) are drawn
from the run's seed. The generator is one task on the server's own event
loop (the chip belongs to one process) and reports how late it ran.

set-up   the server ticking: the warm-up batches (below), each taken
         out again; then the configuration's unmatchable tickets and
         arrivals of the window's own kind until `QUIET_TICKS` ticks in
         a row passed without a compile.
window   opens `PHASE` of an interval after a tick, so the pool is in
         its steady state; closes after `seconds`. Warm-up arrivals
         still waiting are part of the pool: their matches are judged
         and count in `matched_per_s`, their latencies do not count.

Warm-up. The program compiles a score program for each bucket of rows
and of columns the first time a tick falls into it, and a cold compile
stalls that tick: with open arrivals the pool would pile up behind it
into buckets the window never sees. So before the open-loop phase, each
of `WARM_TICKS` is one batch of (a tick's mean arrivals x share) offered
right after a tick, with nothing else arriving while it compiles, and
taken out of the pool after its tick. The shares are a ladder of a
factor two, from 2.2 times a tick's mean down to 0.15 of it: what a
window tick holds (mean +- 30 %, nine standard deviations of the Poisson
count), and what it holds when the host stands still for up to about
five seconds: a tick that comes late sees only the arrivals from before
the stall (0.15 to 0.7 of the mean), and the one after it sees the
backlog too (1.3 to 2.2 and over). No bucket is named: any rule that
pads rows to a power of two is covered from an eighth of the mean to
four times it. Their order keeps the largest apart, so that the slots
of four batches in a row, should the program free none meanwhile, stay
under 3.5 ticks' arrivals: the pool's high-water mark, which the program
never lowers, is not pushed far past what the window's own traffic
reaches. A window tick outside even that compiles
inside the window; `run.py` prints the count (`compiles`,
`inside_window`) and does not hide it, but it is no answer of the
program and does not decide `correct` (PERF.md section 2).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from lib.harness import say

WARM_TICKS = (2.2, 0.7, 0.35, 0.15, 1.3)  # shares of a tick's mean arrivals
QUIET_TICKS = 3  # open-loop ticks in a row without a compile
WARM_TICKS_MAX = 12  # open-loop warm-up arrivals are drawn for this many
PHASE = 0.5  # the window opens this share of an interval after a tick,
# so that every run holds the same number of ticks


async def _offer(ctx, sessions, due) -> None:
    """Issue each session's add when it is due; never sleep past one."""
    for k, (s, at) in enumerate(zip(sessions, due)):
        wait = at - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        elif k % 16 == 15:
            await asyncio.sleep(0)  # behind: still let the server run
        await ctx.add_enveloped(s, at)


def schedule(seed: int, salt: int, rate: float, seconds: float) -> np.ndarray:
    """Arrival offsets from the run's seed: rate x seconds of them,
    uniform over `seconds`, sorted."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed, 5, salt])
    return np.sort(rng.random(n) * seconds)


async def _next_tick(ctx) -> None:
    """Until one more tick has run and its cohort has been delivered."""
    n = len(ctx.ticks)
    while len(ctx.ticks) == n or ctx.backend.pipeline_depth():
        await asyncio.sleep(0.01)


async def setup(ctx) -> None:
    tr, conf = ctx.traffic, ctx.config
    params = conf["recipe_params"]
    interval = ctx.mm.config.interval_sec
    rate = tr["adds_per_s"]
    seed = ctx.args.seed

    # The window's plan is made here, so that the window only offers it.
    offsets = schedule(seed, 0, rate, ctx.args.seconds)
    ctx.plan = (
        [ctx.new_session(spec, True) for spec in
         ctx.recipe.specs([seed, 4], len(offsets), params)],
        offsets,
    )

    per_tick = rate * interval
    t_w = time.perf_counter()
    # Batches: a compile stalls the tick it falls in, and with open
    # arrivals the pool would pile up behind it into shapes the window
    # never sees. So each size of tick is reached by one batch, offered
    # right after a tick with nothing else arriving, and what it leaves
    # unmatched goes out through the public `mm.remove` (what a closing
    # session does) before the next: a tick searches its own batch and
    # nothing else. These tickets are gone before the pool's first
    # ticket is added, so the judge never hears of them.
    sizes = [int(per_tick * share) for share in WARM_TICKS]
    specs = ctx.recipe.specs([seed, 1], sum(sizes), params)
    ticks0 = len(ctx.ticks)
    batch = []
    for size in sizes:
        await _next_tick(ctx)
        ctx.mm.remove([s.ticket for s in batch if not s.matched])
        ctx.drop_sessions(batch)
        batch = [ctx.new_session(specs.pop(), False) for _ in range(size)]
        for k, s in enumerate(batch):
            await ctx.add_enveloped(s, time.perf_counter())
            if k % 16 == 15:
                await asyncio.sleep(0)
    await _next_tick(ctx)
    ctx.mm.remove([s.ticket for s in batch if not s.matched])
    ctx.drop_sessions(batch)
    if len(ctx.mm) != 0:
        raise RuntimeError(f"pool not empty after the batches: {len(ctx.mm)}")
    batch_ticks = len(ctx.ticks) - ticks0
    stuck = [ctx.new_session(spec, False) for spec in ctx.recipe.unmatchable(
        [seed, 3], conf["unmatchable"], params)]
    for s in stuck:
        await ctx.add_enveloped(s, time.perf_counter())
    warm = [ctx.new_session(spec, False) for spec in ctx.recipe.specs(
        [seed, 2], int(per_tick * WARM_TICKS_MAX), params)]
    # Then the configuration's unmatchable tickets and the window's own
    # kind of arrivals, until `QUIET_TICKS` ticks in a row went by
    # without a compile: the pool is in its steady state.
    offsets = schedule(seed, 1, rate, len(warm) / rate)
    gen = asyncio.create_task(
        _offer(ctx, warm, time.perf_counter() + offsets))
    ticks1 = len(ctx.ticks)
    seen = [(0, ctx.compiles.requests + ctx.compiles.backend)]
    quiet = QUIET_TICKS
    while not gen.done():
        await asyncio.sleep(0.02)
        n = len(ctx.ticks) - ticks1
        if n > seen[-1][0]:
            seen.append((n, ctx.compiles.requests + ctx.compiles.backend))
            if n > quiet and seen[-1][1] == seen[-1 - quiet][1]:
                break
    else:
        raise RuntimeError(f"warm-up never went quiet: {seen}")
    # Arrivals go on until the window opens, at a fixed phase of the
    # server's interval, so that every run holds the same number of
    # ticks: a host that stood still over the opening waits for the
    # next tick's.
    while True:
        opens = ctx.ticks[-1][0] + PHASE * interval
        if time.perf_counter() > opens - 0.05:
            await _next_tick(ctx)
            continue
        await asyncio.sleep(opens - time.perf_counter())
        if time.perf_counter() < opens + 0.1 or gen.done():
            break
    if gen.done():
        raise RuntimeError("warm-up arrivals ran out before the window")
    gen.cancel()
    try:
        await gen
    except asyncio.CancelledError:
        pass
    issued = [s for s in warm if s.due_t is not None]
    ctx.drop_sessions([s for s in warm if s.due_t is None])
    ctx.notes["warm"] = dict(
        seconds=round(time.perf_counter() - t_w, 2),
        batch_ticks=batch_ticks, ticks=len(ctx.ticks) - ticks0,
        adds=len(issued),
        unmatchable=len(stuck), compiles_by_tick=seen,
    )
    ctx.sessions = stuck + issued
    ctx.notes["warm"]["opens_after_tick_s"] = round(
        time.perf_counter() - ctx.ticks[-1][0], 3)
    # set-up's own adds are not the window's: start its accounts anew
    ctx.attempted = ctx.failed = 0
    ctx.add_spans.clear()
    ctx.late.clear()
    say("setup", traffic_kind="open_loop", **ctx.notes["warm"])


async def window(ctx, seconds: float) -> None:
    fresh, offsets = ctx.plan
    ctx.sessions = ctx.sessions + fresh
    ctx.open_window()
    await _offer(ctx, fresh, ctx.t0 + offsets)
    await asyncio.sleep(max(0.0, ctx.t0 + seconds - time.perf_counter()))
    ctx.close_window()
    ctx.notes["drain_s"] = round(await ctx.drain(), 3)
    ctx.mm.pause()
    ctx.grace_s = ctx.traffic["grace_intervals"] * ctx.mm.config.interval_sec
    ctx.eligible = [
        s for s in ctx.sessions
        if s.ack_t is not None and s.ack_t <= ctx.t1 - ctx.grace_s
    ]

"""Traffic kind `burst`: the whole pool waits for the first tick.

set-up   load `tickets` warm-up tickets with the matchmaker paused,
         resume, let the first full-pool pass deliver and the row-bucket
         prewarm chain finish (every shape the window dispatches has
         then run once), pause, take what that pass left unmatched out
         through the public `mm.remove` (what closing sessions do),
         and load the window's own `tickets`, from the seed.
window   `mm.resume()` opens it; no arrivals; the server's own interval
         loop ticks; it closes after `seconds`.

Both loads go through the public `mm.add(..., embedding=)`, each
presence bound to a registered benchmark session. No private name of
the program is touched.
"""

from __future__ import annotations

import asyncio
import gc
import time

from lib.harness import say


async def setup(ctx) -> None:
    mm, backend = ctx.mm, ctx.backend
    n = ctx.config["rehearse"]["tickets"] if ctx.rehearse \
        else ctx.config["tickets"]
    params = ctx.config["recipe_params"]
    mm.pause()

    t = time.perf_counter()
    warm = await ctx.add_direct(
        ctx.recipe.specs([ctx.args.seed, 1], n, params), in_window=False
    )
    load1_s = time.perf_counter() - t
    ticks0 = len(ctx.ticks)
    t = time.perf_counter()
    mm.resume()
    limit = t + ctx.traffic["warm_pass_timeout_s"]
    while len(ctx.ticks) == ticks0:
        await asyncio.sleep(0.02)
    # The first pass's cohort delivers on this loop while a thread joins
    # the cohort's worker and the prewarm chain it started.
    join = asyncio.create_task(asyncio.to_thread(backend.wait_idle, None))
    while not join.done() or backend.pipeline_depth():
        if time.perf_counter() > limit:
            raise RuntimeError("the warm-up pass did not deliver in time")
        await asyncio.sleep(0.02)
    await join
    mm.pause()
    warm_s = time.perf_counter() - t
    warm_matched = sum(1 for s in warm if s.matched)
    mm.remove([s.ticket for s in warm if not s.matched])
    if len(mm) != 0:
        raise RuntimeError(f"pool not empty after warm-up: {len(mm)}")
    ctx.drop_sessions(warm)
    del warm

    t = time.perf_counter()
    ctx.sessions = await ctx.add_direct(
        ctx.recipe.specs([ctx.args.seed, 0], n, params), in_window=True
    )
    load2_s = time.perf_counter() - t
    if len(mm) != n:
        raise RuntimeError(f"pool holds {len(mm)} after the load, not {n}")
    ctx.attempted = n
    ctx.eligible = ctx.sessions
    say("setup", traffic_kind="burst", tickets=n, load1_s=round(load1_s, 2),
        warm_pass_s=round(warm_s, 2), warm_matched=warm_matched,
        warm_ticks=len(ctx.ticks) - ticks0, load2_s=round(load2_s, 2))


async def window(ctx, seconds: float) -> None:
    gc.collect()  # set-up's garbage (200,000 specs and sessions) goes now
    ctx.open_window()
    ctx.mm.resume()
    await asyncio.sleep(max(0.0, ctx.t0 + seconds - time.perf_counter()))
    ctx.close_window()
    ctx.notes["drain_s"] = round(await ctx.drain(), 3)
    ctx.mm.pause()

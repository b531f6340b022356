"""cProfile breakdown of mm.process() at the north-star pool.

Profiling harness for the round-3 host-floor work (VERDICT r2 #1). Runs
the production pipelined path, profiles intervals after warmup, prints
cumulative top functions.
"""

import cProfile
import gc
import io
import os
import pstats
import time

import numpy as np

POOL = int(os.environ.get("BENCH_POOL", 100_000))
N_INT = int(os.environ.get("PROF_INTERVALS", 6))
PROF_FROM = int(os.environ.get("PROF_FROM", 3))

from bench import build_ticket, fill  # noqa: E402
from profile_interval import print_device_report  # noqa: E402
from nakama_tpu.config import MatchmakerConfig  # noqa: E402
from nakama_tpu.logger import test_logger  # noqa: E402
from nakama_tpu.matchmaker import LocalMatchmaker  # noqa: E402
from nakama_tpu.matchmaker.tpu import TpuBackend  # noqa: E402


def main():
    from nakama_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    rng = np.random.default_rng(42)
    cap = 1 << (POOL + POOL // 2 - 1).bit_length()
    cfg = MatchmakerConfig(
        pool_capacity=cap,
        candidates_per_ticket=32,
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        max_intervals=2,
        interval_pipelining=True,
    )
    backend = TpuBackend(cfg, test_logger(), row_block=256, col_block=2048)
    matched_total = [0]
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=backend,
        on_matched=lambda batch: matched_total.__setitem__(
            0, matched_total[0] + batch.entry_count),
    )

    t0 = time.perf_counter()
    fill(mm, rng, POOL, "w")
    print(f"fill {POOL}: {time.perf_counter()-t0:.2f}s", flush=True)

    prof = cProfile.Profile()
    for interval in range(N_INT):
        deficit = POOL - len(mm)
        if deficit:
            t = time.perf_counter()
            fill(mm, rng, deficit, f"i{interval}-")
            refill_s = time.perf_counter() - t
        else:
            refill_s = 0.0
        t = time.perf_counter()
        if interval >= PROF_FROM:
            prof.enable()
        mm.process()
        if interval >= PROF_FROM:
            prof.disable()
        total = time.perf_counter() - t
        print(
            f"interval {interval}: total={total*1000:.1f}ms"
            f" (refill {refill_s:.2f}s) crumb="
            f"{backend.tracing.recent()[-1] if backend.tracing.recent() else None}",
            flush=True,
        )
        backend.wait_idle()
        mm.store.drain()
        gc.collect()
    mm.stop()

    s = io.StringIO()
    st = pstats.Stats(prof, stream=s)
    st.sort_stats("cumulative").print_stats(40)
    print(s.getvalue())
    s = io.StringIO()
    st = pstats.Stats(prof, stream=s)
    st.sort_stats("tottime").print_stats(40)
    print(s.getvalue())
    print_device_report()


if __name__ == "__main__":
    main()

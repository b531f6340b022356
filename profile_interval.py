"""One-off stage breakdown of a matchmaker interval on the real chip.

Not part of the test suite — a profiling harness for the perf work
(VERDICT round 1 weak #2/#8). Writes a jax.profiler trace when
PROFILE_TRACE=1.

Two views per run:
- the in-process() split (kernel / flush / assemble / other-host), and
- the DELIVERY stage chain per cohort — dispatched→ready→accepted→
  published, off the tracing ledger — with event-driven collection, so
  any future delivery-gap regression names its stage from one profile
  run instead of hiding inside an end-to-end number.

`--mesh` (or PROF_MESH=1) profiles the MESH-SHARDED interval instead:
a PROF_MESH_DEVICES-way pool-sharded backend (an error when JAX reports
fewer devices; a CPU rehearsal sets JAX_PLATFORMS=cpu itself), printing
the per-interval dispatch→shard_score→gather→merge chain plus each
shard's occupancy, so a mesh-path regression names its stage from one
run.
"""

import os
import sys
import threading
import time

import numpy as np

MESH = "--mesh" in sys.argv[1:] or bool(os.environ.get("PROF_MESH"))
MESH_DEVICES = int(os.environ.get("PROF_MESH_DEVICES", 8))
POOL = int(os.environ.get("BENCH_POOL", 8192 if MESH else 100_000))

from bench import build_ticket, fill  # noqa: E402
from nakama_tpu.devobs import DEVOBS  # noqa: E402


def print_device_report():
    """Shared telemetry tables (devobs.py): kernel clocks +
    compile-watch + HBM ledger + transfer counters — identical across
    the three profiling scripts so they can't drift from the shipped
    code paths. Printed with `--device` (or PROF_DEVICE=1)."""
    if "--device" not in sys.argv[1:] and not os.environ.get(
        "PROF_DEVICE"
    ):
        return
    for line in DEVOBS.report_lines():
        print(line, flush=True)

from nakama_tpu.config import MatchmakerConfig  # noqa: E402
from nakama_tpu.logger import test_logger  # noqa: E402
from nakama_tpu.matchmaker import LocalMatchmaker  # noqa: E402
from nakama_tpu.matchmaker.tpu import TpuBackend  # noqa: E402
from nakama_tpu.matchmaker import device as dev  # noqa: E402
from nakama_tpu import native  # noqa: E402


def main():
    import jax

    from nakama_tpu.jaxenv import enable_compile_cache, require_devices

    enable_compile_cache()
    if MESH:
        require_devices(MESH_DEVICES)

    rng = np.random.default_rng(42)
    cap = 1 << (POOL + POOL // 2 - 1).bit_length()
    cfg = MatchmakerConfig(
        pool_capacity=cap,
        candidates_per_ticket=32,
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        max_intervals=2,
        mesh_devices=MESH_DEVICES if MESH else 0,
    )
    # Mesh shards are cap/n columns each; the scan block must divide one.
    col_block = min(2048, cap // MESH_DEVICES) if MESH else 2048
    backend = TpuBackend(
        cfg, test_logger(), row_block=256, col_block=col_block
    )
    # on_matched wired so the publish stage actually runs (and stamps
    # publish_lag_s on the delivery ledger).
    matched_entries = [0]
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=backend,
        on_matched=lambda batch: matched_entries.__setitem__(
            0, matched_entries[0] + batch.entry_count
        ),
    )
    ready_evt = threading.Event()
    backend.set_ready_callback(ready_evt.set)

    t0 = time.perf_counter()
    fill(mm, rng, POOL, "w")
    print(f"fill {POOL}: {time.perf_counter()-t0:.2f}s")

    # Monkeypatch-instrument the backend stages.
    times = {}

    import nakama_tpu.matchmaker.tpu as tpu_mod

    orig_topk = tpu_mod.topk_candidates
    orig_topk_big = tpu_mod.topk_candidates_big
    orig_assemble = native.assemble_arrays

    def timed_topk(*a, **kw):
        t = time.perf_counter()
        out = orig_topk(*a, **kw)
        jax.block_until_ready(out)
        times["kernel"] = times.get("kernel", 0) + time.perf_counter() - t
        return out

    def timed_topk_big(*a, **kw):
        t = time.perf_counter()
        out = orig_topk_big(*a, **kw)
        jax.block_until_ready(out)
        times["kernel"] = times.get("kernel", 0) + time.perf_counter() - t
        return out

    def timed_assemble(*a, **kw):
        t = time.perf_counter()
        out = orig_assemble(*a, **kw)
        times["assemble"] = times.get("assemble", 0) + time.perf_counter() - t
        return out

    tpu_mod.topk_candidates = timed_topk
    tpu_mod.topk_candidates_big = timed_topk_big
    tpu_mod.native.assemble_arrays = timed_assemble

    orig_flush = backend.pool.flush

    def timed_flush():
        t = time.perf_counter()
        orig_flush()
        jax.block_until_ready(backend.pool.device)
        times["flush"] = times.get("flush", 0) + time.perf_counter() - t

    backend.pool.flush = timed_flush

    for interval in range(5):
        deficit = POOL - len(mm)
        if deficit:
            t = time.perf_counter()
            fill(mm, rng, deficit, f"i{interval}-")
            refill_s = time.perf_counter() - t
        else:
            refill_s = 0.0
        times.clear()
        tl_before = len(DEVOBS.timeline)
        trace = os.environ.get("PROFILE_TRACE") and interval == 3
        if trace:
            jax.profiler.start_trace("/tmp/mm_trace")
        t = time.perf_counter()
        confirmed = mm.process()
        total = time.perf_counter() - t
        if trace:
            jax.profiler.stop_trace()
            print("trace written to /tmp/mm_trace")
        other = total - sum(times.values())
        print(
            f"interval {interval}: total={total*1000:.1f}ms "
            f"kernel={times.get('kernel',0)*1000:.1f} "
            f"flush={times.get('flush',0)*1000:.1f} "
            f"assemble={times.get('assemble',0)*1000:.1f} "
            f"other-host={other*1000:.1f} "
            f"(refill {refill_s:.2f}s, matched {sum(len(s) for s in confirmed)} entries, "
            f"hw {backend.pool.high_water}, active {len([1 for _ in confirmed])})"
        )
        # Event-driven delivery for the cohort this interval dispatched
        # (production's delivery stage): collect on the completion
        # signal, then print its per-stage chain off the ledger.
        ledger_before = len(backend.tracing.deliveries)
        settle = time.monotonic() + 120
        while backend.pipeline_depth() and time.monotonic() < settle:
            ready_evt.wait(2.0)
            ready_evt.clear()
            mm.collect_pipelined()
        for d in list(backend.tracing.deliveries)[ledger_before:]:
            print(
                "  delivery: dispatched→fetched="
                f"{d.get('fetch_lag_s', float('nan'))*1000:.1f}ms "
                f"→ready={d.get('ready_lag_s', float('nan'))*1000:.1f}ms "
                f"→collected={d.get('collect_lag_s', float('nan'))*1000:.1f}ms "
                f"→accepted={d.get('accept_lag_s', float('nan'))*1000:.1f}ms "
                f"→published={d.get('publish_lag_s', float('nan'))*1000:.1f}ms"
                + (" SLIPPED" if d.get("slipped") else "")
            )
        if MESH:
            # Per-shard mesh chain: the sharded score + ICI gather +
            # on-device merge stages off the kernel-clock timeline
            # (DEVOBS.device_call wraps both in tpu._dispatch_sharded),
            # then each shard's live occupancy.
            chain = {
                "matchmaker.shard_score": 0.0,
                "matchmaker.gather_merge": 0.0,
            }
            for kname, _ts, ms in list(DEVOBS.timeline)[tl_before:]:
                if kname in chain:
                    chain[kname] += ms
            print(
                f"  mesh chain: dispatch={total*1000:.1f}ms "
                f"→shard_score={chain['matchmaker.shard_score']:.1f}ms "
                f"→gather={backend.mesh_gather_bytes:,}B "
                f"→merge={chain['matchmaker.gather_merge']:.1f}ms "
                f"(cumulative gather {backend.mesh_gather_bytes_total:,}B)"
            )
            from nakama_tpu.parallel.mesh import describe_mesh

            d = describe_mesh(
                backend._mesh,
                backend.pool.capacity,
                pool=backend.pool.device,
                gather_bytes=backend.mesh_gather_bytes,
            )
            for row in ((d.get("mesh") or {}).get("shards") or []):
                print(
                    f"    shard dev{row['device']}:"
                    f" slots={row['slots']}"
                    f" occupied={row['occupied']}"
                    f" hbm={row['hbm_bytes']:,}B"
                )

    stats = backend.tracing.delivery_stage_stats()
    print("delivery stage stats (dispatch-relative seconds):")
    for stage, s in stats.items():
        print(
            f"  {stage}: p50={s['p50']*1000:.1f}ms "
            f"p99={s['p99']*1000:.1f}ms n={s['n']}"
        )
    print(f"published entries total: {matched_entries[0]}")
    print_device_report()
    mm.stop()


if __name__ == "__main__":
    main()

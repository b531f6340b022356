"""Per-interval span breakdown at bench cadence (north-star shape).

Not part of the suite — perf harness for the round-4 <50ms push. Prints
the backend breadcrumb spans plus the LocalMatchmaker.process() total so
the host tail outside the backend (store removal, delivery) is visible.
"""

import gc
import os
import sys
import time

import numpy as np

POOL = int(os.environ.get("BENCH_POOL", 100_000))
INTERVALS = int(os.environ.get("PROF_INTERVALS", 10))

from bench import build_ticket, fill, ticket_cfg3, ticket_cfg5  # noqa: E402
from profile_interval import print_device_report  # noqa: E402
from nakama_tpu.config import MatchmakerConfig  # noqa: E402
from nakama_tpu.logger import test_logger  # noqa: E402
from nakama_tpu.matchmaker import LocalMatchmaker  # noqa: E402
from nakama_tpu.matchmaker.tpu import TpuBackend  # noqa: E402

MAKERS = {
    "ns": (build_ticket, {}),
    "cfg3": (ticket_cfg3, {"candidates_per_ticket": 64}),
    "cfg5": (ticket_cfg5, {}),
}


def main():
    from nakama_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    which = os.environ.get("PROF_CFG", "ns")
    maker, overrides = MAKERS[which]
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
        **overrides,
    )
    backend = TpuBackend(cfg, test_logger(), row_block=256, col_block=2048)
    matched_total = [0]

    def on_matched(batch):
        matched_total[0] += batch.entry_count

    mm = LocalMatchmaker(test_logger(), cfg, backend=backend,
                         on_matched=on_matched)
    g0, g1, _ = gc.get_threshold()
    gc.set_threshold(g0, g1, 1_000_000)

    t0 = time.perf_counter()
    fill(mm, rng, POOL, "w", maker)
    print(f"fill {POOL}: {time.perf_counter()-t0:.2f}s", flush=True)

    # Fine-grained wrappers around the out-of-backend interval work.
    sub = {}

    def wrap(obj, name, key):
        orig = getattr(obj, name)

        def timed(*a, **kw):
            t = time.perf_counter()
            out = orig(*a, **kw)
            sub[key] = sub.get(key, 0.0) + time.perf_counter() - t
            return out

        setattr(obj, name, timed)

    wrap(mm.store, "remove_slots", "store_rm")
    wrap(mm.store, "deactivate", "deact")
    wrap(mm.store, "reactivate", "react")
    wrap(mm.store, "active_slots", "act_slots")
    wrap(backend, "on_remove_slots", "be_rm")
    wrap(mm.store.maps, "remove_slots", "maps_rm")

    for interval in range(INTERVALS):
        deficit = POOL - len(mm)
        if deficit > 0:
            fill(mm, rng, deficit, f"i{interval}-", maker)
        sub.clear()
        t0 = time.perf_counter()
        mm.process()
        total = (time.perf_counter() - t0) * 1000
        crumb = backend.tracing.recent(1)
        crumb = dict(crumb[0]) if crumb else {}
        crumb.pop("ts", None)
        spans = {
            k: round(v * 1000, 1)
            for k, v in crumb.items()
            if k.endswith("_s")
        }
        rest = {
            k: v for k, v in crumb.items() if not k.endswith("_s")
        }
        span_sum = sum(spans.values())
        print(
            f"interval {interval}: total={total:.1f}ms "
            f"spans={spans} span_sum={span_sum:.1f} "
            f"outside_backend={total - span_sum:.1f} "
            f"sub={ {k: round(v*1000,1) for k, v in sub.items()} } {rest}",
            flush=True,
        )
        backend.wait_idle()
        mm.store.drain()
        gc.collect()
    mm.stop()
    print(f"matched_total={matched_total[0]}")
    # PR 6 span format: per-stage delivery attribution off the Ledger,
    # monotonic ledger totals, and the kept cohort traces (each
    # interval dispatch is a real trace now — tail-sampled, so only
    # error/slow/1% survive unless TRACES is reconfigured).
    print(f"delivery_stages={backend.tracing.delivery_stage_stats()}")
    print(f"ledger_totals={backend.tracing.ledger_totals()}")
    from nakama_tpu.tracing import TRACES

    for rec in TRACES.list(5):
        trace = TRACES.get(rec["trace_id"]) or {"resourceSpans": []}
        names = [
            s["name"]
            for rs in trace["resourceSpans"]
            for ss in rs["scopeSpans"]
            for s in ss["spans"]
        ]
        print(
            f"trace {rec['trace_id'][:8]} root={rec['root']}"
            f" reason={rec['reason']} dur={rec['duration_ms']}ms"
            f" spans={names}"
        )
    if "--fleet" in sys.argv[1:] or os.environ.get("PROF_FLEET"):
        print_fleet_chains()
    print_device_report()


def print_fleet_chains(n: int = 5):
    """`--fleet`: run this process's kept traces through the fleet
    collector's stitching machinery (cluster/obs.py) and print each
    stitched delivery chain — one line per span in adjusted time
    order, cross-node hops annotated with their bus latency. Locally
    there is a single origin node and zero hops; pointed at a
    collector's store the same printer shows the cross-node chain."""
    from nakama_tpu.cluster.obs import (
        FleetTraceStore,
        TraceFragmentExporter,
    )
    from nakama_tpu.logger import test_logger
    from nakama_tpu.tracing import TRACES

    store = FleetTraceStore(capacity=256)
    exporter = TraceFragmentExporter(
        None, "local", "local", test_logger(), local_sink=store,
        max_batch=256,
    )
    while exporter.maybe_ship():
        pass
    print(f"fleet: {len(store)} stitched trace(s)")
    for summary in store.summaries(n):
        print(
            f"fleet trace {summary['trace_id'][:8]}"
            f" root={summary['root']}"
            f" nodes={','.join(summary['nodes'])}"
            f" stitched={summary['stitched']}"
            f" extent={summary['extent_ms']}ms"
        )
        for line in store.delivery_chain(summary["trace_id"]):
            print(f"  {line}")


if __name__ == "__main__":
    main()

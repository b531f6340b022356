"""The trace reduction on a small recorded trace.

`PLANES` is a hand-made recording in the form `trace.read_planes`
gives (one device, its `XLA Ops` and `XLA Modules` lines, seconds);
`test_read_planes_finds_the_sync_annotation` records a real (CPU)
profile and reads it back through `jax.profiler.ProfileData`.
"""

import pytest

from lib import trace

PLANES = [
    {"name": "/host:CPU", "lines": {"python": [("bench.sync", 99.5, 99.5)]}},
    {"name": "/device:TPU:0", "lines": {
        "XLA Modules": [
            ("jit_topk_candidates_big(123)", 102.0, 102.5),
            ("jit_scatter(7)", 103.0, 103.1),
            ("jit_topk_candidates_big(123)", 106.0, 106.05),
        ],
        "XLA Ops": [
            ("fusion.1", 102.0, 102.1),
            ("_stage1_kernel.1", 102.1, 102.4),
            ("fusion.2", 102.35, 102.5),   # overlaps the kernel's tail
            ("scatter.3", 103.0, 103.1),
            ("_stage1_kernel.1", 106.0, 106.04),
            ("fusion.2", 106.04, 106.05),
            ("before.window", 90.0, 91.0),
        ],
    }},
]
HOST = [("process", 101.0, 101.9), ("fetch", 102.5, 102.9),
        ("publish", 103.2, 105.9)]


def test_union_and_gaps():
    total, merged = trace.union_seconds([(0, 2), (1, 3), (5, 6)])
    assert total == 4 and merged == [(0, 3), (5, 6)]
    assert trace.gaps(merged, 0, 10) == [(3, 5), (6, 10)]
    assert trace.gaps(merged, 1, 5.5) == [(3, 5)]
    assert trace.gaps([], 0, 1) == [(0, 1)]


def test_reduce_busy_idle_programs_and_labels():
    out = trace.reduce_planes(PLANES, (100.0, 110.0), HOST)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(10.0)
    # busy: [102.0, 102.5] + [103.0, 103.1] + [106.0, 106.05]
    assert out["busy_s"] == pytest.approx(0.65)
    runs = sorted((a, b) for n, a, b in out["programs"]
                  if "topk_candidates_big" in n)
    assert runs[0] == (102.0, 102.5) and len(runs) == 2
    # per-operation device time sums over the window only
    assert out["op_s"]["_stage1_kernel.1"] == pytest.approx(0.34)
    assert "before.window" not in out["op_s"]
    assert out["breakdown"]["device_ops"][0][0] == "_stage1_kernel.1"
    labels = {k.split("@")[0]: v for k, v in out["breakdown"]["idle_gaps"]}
    assert labels["publish"] == pytest.approx(2.9)   # 103.1 -> 106.0
    assert labels["process"] == pytest.approx(2.0)   # 100.0 -> 102.0
    assert out["idle_by_label"]["idle-until-tick"] == pytest.approx(3.95)
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_no_device_operation_gives_nothing_not_zero():
    assert trace.reduce_planes(PLANES[:1], (100.0, 110.0), HOST) is None
    assert trace.reduce_planes(PLANES, (200.0, 210.0), HOST) is None


def test_read_planes_finds_the_sync_annotation(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.sync"):
        pass
    jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.read_planes(str(tmp_path))
    assert trace.find_event(planes, "bench.sync") is not None
    assert trace.find_event(planes, "no.such.event") is None

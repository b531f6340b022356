"""Adding a configuration, a traffic mix and a per-layer metric needs
only new files and new entries: a throwaway one of each, in a copy of
the benchmark in a temporary directory, resolved by name and run."""

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_new_cell_from_files_alone(tmp_path):
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    config = json.loads((bench_dir / "configs" / "duel1k.json").read_text())
    config["name"] = "duel_wide"
    config["recipe_params"]["rank_window"] = 300
    (bench_dir / "configs" / "duel_wide.json").write_text(json.dumps(config))
    mix = json.loads((bench_dir / "traffic" / "steady.json").read_text())
    mix["adds_per_s"] = 200
    mix["grace_intervals"] = 1.5  # a 5 s window: tickets of its first 2 s
    (bench_dir / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (bench_dir / "layer_metrics" / "process_host_ms.p95.json").write_text(
        json.dumps({"reader": "span_p95", "args": {"scale": 1000.0}}))
    (bench_dir / "readers" / "span_p95.py").write_text(
        "from lib.stats import percentile\n\n\n"
        "def read(ctx, args):\n"
        "    xs = [d for t, d in ctx.ticks if ctx.t0 <= t <= ctx.t1]\n"
        "    return percentile(xs, 95) * args['scale'] if xs else None\n")

    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cell = "duel_wide.trickle"
    bench["configs"].append({
        "name": "duel_wide", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/duel_wide.json"})
    bench["workloads"].append({
        "name": cell, "config": "duel_wide", "traffic": "trickle",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "duel1k.steady" in m["workloads"]:
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "process_host_ms.p95", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "interval host",
        "moves": "add_to_matched_p95_ms", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for trace, want in ((0, "add_to_matched_p95_ms"),
                        (1, "process_host_ms.p95")):
        out = subprocess.run(
            [sys.executable, str(bench_dir / "run.py"), "--workload", cell,
             "--seed", "31", "--seconds", "5", "--trace", str(trace),
             "--rehearse", "1"],
            env=env, cwd=tmp_path, capture_output=True, text=True,
            timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["correct"] is True, line
        assert line["attempted"] == 1000  # 200 adds/s x 5 s
        assert want in line["metrics"], line["metrics"]
    # no file that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())

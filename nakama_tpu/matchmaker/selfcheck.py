"""Chip-executed correctness assertions (VERDICT r3 #7).

Every kernel-correctness test in `tests/` runs Pallas interpret mode on
the CPU mesh; before this module, real-Mosaic lowering was only ever
exercised by the bench, where a miscompile would surface as a silent
throughput/number regression, not a failure. `run_chip_selfcheck()`
executes the same parity assertions ON THE REAL DEVICE:

- small-pool exact kernel: match-for-match parity with the CPU oracle,
- two-stage MXU kernel (big path): every formed match exactly valid
  (term/range/session checks re-verified in f64 on host) with coverage
  no worse than the oracle's,
- device pairing (sync 1v1 path): validity + coverage,

and is invoked both by the `@pytest.mark.tpu` tier
(`NAKAMA_TPU_TESTS=1 pytest -m tpu`) and by bench.py at startup, so
every bench run on hardware asserts correctness before it reports
numbers.
"""

from __future__ import annotations

import re

import numpy as np

from ..config import MatchmakerConfig
from ..logger import test_logger
from .local import CpuBackend, LocalMatchmaker
from .tpu import TpuBackend
from .types import MatchmakerPresence


_TERM = re.compile(r"\+properties\.(\w+):(>=|<=)?(\S+)")


def _specs(rng, n):
    out = []
    for i in range(n):
        mode = int(rng.integers(0, 3))
        rank = int(rng.integers(0, 100))
        out.append(
            dict(
                query=(
                    f"+properties.mode:m{mode}"
                    f" +properties.rank:>={max(0, rank - 25)}"
                    f" +properties.rank:<={rank + 25}"
                ),
                strs={"mode": f"m{mode}"},
                nums={"rank": float(rank)},
            )
        )
    return out


def _run(mm, specs, intervals):
    """Add `specs`, run `intervals`; returns (matched batches, info) with
    info[ticket id] = (query, min_count, max_count) for `validate_match`."""
    matched = []
    info = {}
    mm.on_matched = matched.append
    for i, s in enumerate(specs):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        tid, _ = mm.add(
            [p], p.session_id, "", s["query"], 2, 2, 1, s["strs"],
            s["nums"],
        )
        info[tid] = (s["query"], 2, 2)
    for _ in range(intervals):
        mm.process()
    if mm.backend.pipeline_depth():
        mm.backend.wait_idle(30)
        mm.process()  # collect any pipelined tail
    mm.stop()
    return matched, info


def query_accepts(query: str, strs: dict, nums: dict) -> bool:
    """Does `query` accept a ticket with these properties? Exact (f64,
    whole strings) and independent of the system's own parser, compiler
    and kernels: it reads only the must-terms the selfcheck and
    `chip_smoke.py` recipes write (`+properties.f:term`,
    `+properties.f:>=x`, `+properties.f:<=x`) and refuses any other."""
    terms = _TERM.findall(query)
    if len(terms) != len(query.split()):
        raise ValueError(f"query outside the validator's grammar: {query}")
    for field, op, value in terms:
        if op:
            x = nums.get(field)
            if x is None:
                return False
            if (op == ">=" and not x >= float(value)) or (
                op == "<=" and not x <= float(value)
            ):
                return False
        elif strs.get(field) != value:
            return False
    return True


def validate_match(entries, info, rev: bool, label="") -> None:
    """Host re-check of one formed match: sessions distinct, the size
    inside every member's [min_count, max_count], and some member (the
    active ticket that searched) whose query accepts every other member
    — mutually when `rev`. Raises AssertionError."""
    sessions = [e.presence.session_id for e in entries]
    assert len(set(sessions)) == len(sessions), (label, "session twice")
    props = {
        e.ticket: (e.string_properties, e.numeric_properties)
        for e in entries
    }
    size = len(entries)
    for tid in props:
        _, lo, hi = info[tid]
        assert lo <= size <= hi, (label, "size", size, lo, hi)

    def accepts(a, b):
        return query_accepts(info[a][0], *props[b])

    def searched(a):
        return all(
            accepts(a, b) and (not rev or accepts(b, a))
            for b in props
            if b != a
        )

    assert any(searched(a) for a in props), (
        label, "no member's query accepts the rest", sorted(props),
    )


def _validate(matched, info, label):
    total = 0
    for batch in matched:
        for entry_set in batch:
            assert len(entry_set) == 2, (label, "match size")
            validate_match(entry_set, info, rev=True, label=label)
            total += 2
    return total


def _pairs(matched):
    return sorted(
        tuple(sorted(e.presence.user_id for e in s))
        for batch in matched
        for s in batch
    )


def _cpu_matches(specs, intervals=2):
    mm = LocalMatchmaker(
        test_logger(),
        MatchmakerConfig(max_intervals=2, backend="cpu"),
        backend=CpuBackend(),
    )
    return _run(mm, specs, intervals)


def exact_parity(n: int, seed: int, **widths) -> int:
    """Small-pool exact kernel against the CPU oracle on `n` seeded 1v1
    tickets: the same matches, pair for pair. Synchronous intervals
    (parity needs same-interval delivery) and a candidate width of the
    whole pool (the oracle searches all of it); `widths` override the
    shipped schema widths. Returns the number of matches."""
    specs = _specs(np.random.default_rng(seed), n)
    cpu, _ = _cpu_matches(specs)
    cap = 1 << (n - 1).bit_length()
    cfg = MatchmakerConfig(
        pool_capacity=cap, candidates_per_ticket=cap, max_intervals=2,
        big_pool_threshold=2 * cap, interval_pipelining=False, **widths,
    )
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=TpuBackend(cfg, test_logger())
    )
    dev, _ = _run(mm, specs, 2)
    assert _pairs(dev) == _pairs(cpu), "small kernel != oracle"
    return len(_pairs(dev))


def run_chip_selfcheck(log=print) -> dict:
    """Run the device-path parity checks on the current default JAX
    device. Raises AssertionError on any violation; returns a summary
    dict."""
    results = {}

    # 1. Small-pool exact kernel: match-for-match oracle parity.
    results["small_exact_parity"] = exact_parity(
        96, 7, numeric_fields=8, string_fields=8, max_constraints=8
    )
    log(f"selfcheck small kernel: {results['small_exact_parity']} matches,"
        " exact oracle parity")

    # 2. Big (two-stage MXU) kernel + native assembler at the shipped
    # default widths: exact validity + oracle coverage. One ticket that
    # is no pair (a trio in a mode of its own: it never matches) pins
    # the assembler path — a pure-1v1 pool takes the pairing handshake.
    rng = np.random.default_rng(11)
    specs = _specs(rng, 600)
    cpu_total = _validate(*_cpu_matches(specs), "oracle")
    cfg = MatchmakerConfig(
        pool_capacity=1024, max_intervals=2, big_pool_threshold=256,
        interval_pipelining=True,
    )
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=TpuBackend(
            cfg, test_logger(), big_row_block=256, big_col_block=256,
        )
    )
    trio = MatchmakerPresence(user_id="trio", session_id="trio")
    mm.add(
        [trio], trio.session_id, "", "+properties.mode:trio", 3, 3, 1,
        {"mode": "trio"}, {},
    )
    dev_total = _validate(*_run(mm, specs, 3), "big")
    assert dev_total >= cpu_total - 4, (dev_total, cpu_total)
    results["big_valid_entries"] = dev_total
    log(f"selfcheck big kernel: {dev_total} valid entries"
        f" (oracle {cpu_total})")

    # 3. Device pairing (sync 1v1): validity + coverage.
    cfg = MatchmakerConfig(
        pool_capacity=1024, candidates_per_ticket=64, numeric_fields=8,
        string_fields=8, max_constraints=8, max_intervals=2,
        big_pool_threshold=256, interval_pipelining=False,
    )
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=TpuBackend(
            cfg, test_logger(), big_row_block=256, big_col_block=256,
        )
    )
    pair_total = _validate(*_run(mm, specs, 2), "pairs")
    assert pair_total >= cpu_total - 8, (pair_total, cpu_total)
    results["pairing_valid_entries"] = pair_total
    log(f"selfcheck device pairing: {pair_total} valid entries"
        f" (oracle {cpu_total})")

    # 4. Device pairing under PIPELINED intervals — the shipped default
    # for pure-1v1 big pools: validity + coverage through the queued
    # dispatch→collect flow (gen/alive/sel staleness masks included).
    cfg = MatchmakerConfig(
        pool_capacity=1024, candidates_per_ticket=64, numeric_fields=8,
        string_fields=8, max_constraints=8, max_intervals=2,
        big_pool_threshold=256, interval_pipelining=True,
    )
    mm = LocalMatchmaker(
        test_logger(), cfg, backend=TpuBackend(
            cfg, test_logger(), big_row_block=256, big_col_block=256,
        )
    )
    pipe_total = _validate(*_run(mm, specs, 3), "pairs-pipelined")
    assert pipe_total >= cpu_total - 8, (pipe_total, cpu_total)
    results["pairing_pipelined_valid_entries"] = pipe_total
    log(f"selfcheck pipelined device pairing: {pipe_total} valid entries"
        f" (oracle {cpu_total})")
    return results

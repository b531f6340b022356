"""`chip_smoke.py`'s control flow on CPU, and what keeps a host from
passing for the chip: backend selection, launchers, the compile cache.

The rehearsal runs the script's own phases in this process at a tiny
pool (`--rehearse`: interpret-mode Pallas, the backend handed to the
server as tests do). It says nothing of the chip; it keeps the script
from rotting between chip runs.
"""

import json
import os

import pytest

import chip_smoke
from nakama_tpu import faults, jaxenv
from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker.local import CpuBackend, _select_backend


@pytest.fixture
def smoke(capsys):
    """Run chip_smoke.main in-process; undo what it sets process-wide."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from nakama_tpu.devobs import DEVOBS

    cache_dir = jax.config.jax_compilation_cache_dir
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations

    def run(*argv):
        rc = chip_smoke.main(list(argv))
        lines = [
            json.loads(ln)
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")
        ]
        return rc, lines

    yield run
    faults.disarm()
    DEVOBS.reset()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_include_full_tracebacks_in_locations", tracebacks)
    compilation_cache.reset_cache()


def test_rehearsal_runs_every_phase(smoke):
    rc, lines = smoke("--rehearse", "600")
    assert rc == 0, lines[-1]
    assert [ln["phase"] for ln in lines[:-1]] == [
        "device", "native", "matchmaker", "parity", "matchmaker_rev",
        "leaderboard", "shutdown",
    ]
    assert all(ln["ok"] for ln in lines)
    last = lines[-1]
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"  # only under --rehearse
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    main, rev = by_phase["matchmaker"], by_phase["matchmaker_rev"]
    for ln, mutual, judged in ((main, False, 5), (rev, True, 2)):
        assert ln["platform"] == "cpu" and ln["interpret"] is True
        assert ln["kernel"]["with_embedding"] and ln["kernel"]["rev"] is mutual
        assert ln["judged_intervals"] >= judged
        assert ln["matches_validated"] > 0
        assert ln["breaker"] == "closed" and ln["cohorts_slipped"] == 0
        assert ln["recompiles_after_warmup"] == 0
        # What the server was given beside the shipped defaults is said.
        assert ln["overrides"]["recovery.checkpoint_interval_sec"] == 3600
        assert ln["overrides"]["matchmaker.rev_precision"] is mutual
        assert ln["checkpoint_at_full_pool"]["tickets"] == 600
        assert ln["kernel"]["row_block"] == 128  # the tile stage 1 ran
        assert ln["widths"] == dict(
            fn=24, fs=16, constraints=16, k=64, emb_dims=16,
            pool_capacity=1024,
        )
    assert main["via_pipeline"] == 32 and main["via_ws"] == 4
    assert main["ws_matched"] >= 1 and main["modeled_matched"] >= 1
    assert by_phase["parity"]["identical"] and by_phase["parity"]["matches"]
    assert by_phase["leaderboard"]["equal"]
    assert by_phase["leaderboard"]["fallbacks"] == 0


def test_mesh_rehearsal_where_column_extents_differ(smoke):
    """`--mesh N` runs the mesh phase and its single-device comparison
    and nothing else. At 500 tickets the single device scores half the
    column blocks the mesh does and keeps twice the winners a block (as
    at the real size: 112 blocks against 128), so the two match sets
    differ; the designed cross-shard pairs must all match on both."""
    rc, lines = smoke("--mesh", "4", "--rehearse", "500")
    assert rc == 0, lines[-1]
    assert [ln["phase"] for ln in lines[:-1]] == [
        "device", "native", "mesh", "mesh",
    ]
    assert set(lines[-1]) == {"ok", "device"} and lines[-1]["ok"]
    for ln, mutual in zip(lines[2:4], (False, True)):
        assert ln["platform"] == "cpu" and ln["rev_precision"] is mutual
        mesh, single = ln["kernel"], ln["single_kernel"]
        assert mesh["kernel"] == "topk_candidates_big_sharded/4"
        assert single["kernel"] == "topk_candidates_big"
        assert mesh["with_embedding"] and mesh["rev"] is mutual
        assert (mesh["n_cols"], single["n_cols"]) == (1024, 512)
        assert (mesh["winners_per_block"],
                single["winners_per_block"]) == (1, 2)
        assert ln["designed_pairs"] == ln["cross_shard_pairs"] == 64
        assert ln["designed_pairs_matched"] == 64
        assert ln["designed_pairs_matched_single"] == 64
        assert [shape[0] for _, shape in ln["pool_shards"]] == [256] * 4
        assert len({dev for dev, _ in ln["pool_shards"]}) == 4
        assert all(ln["pool_ledger"][f"matchmaker.pool.dev{i}"] > 0
                   for i in range(4))
        assert ln["mesh_recompiles_after_warmup"] == 0
        assert ln["a_pads"][0] == ln["a_pads"][-1]


def test_no_chip_no_result(smoke):
    """Without --rehearse the script never carries on without a TPU."""
    rc, lines = smoke()
    assert rc != 0
    assert lines == [lines[0]] and lines[0]["ok"] is False
    assert "needs a tpu device" in lines[0]["error"]


def test_kernel_failure_fails_the_smoke(smoke):
    """A dispatch the device refuses is served by the ladder (matches
    still flow) — and must fail the smoke, not pass as rc 0."""
    faults.arm("device.dispatch", "raise")
    rc, lines = smoke("--rehearse", "600")
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert "device path degraded" in lines[-1]["error"]
    assert [ln["phase"] for ln in lines[:-1]] == ["device", "native"]


# ------------------------------------------------------ backend selection


def _cfg(backend):
    return MatchmakerConfig(backend=backend, pool_capacity=64)


def test_backend_tpu_on_cpu_host_raises():
    with pytest.raises(RuntimeError, match='"tpu" but the default JAX'):
        _select_backend(_cfg("tpu"), quiet_logger(), None)


def test_backend_auto_propagates_broken_jax(monkeypatch):
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        _select_backend(_cfg("auto"), quiet_logger(), None)
    # The oracle asked for by name never touches JAX.
    assert isinstance(
        _select_backend(_cfg("cpu"), quiet_logger(), None), CpuBackend
    )


def test_backend_auto_on_cpu_host_is_the_oracle():
    assert isinstance(
        _select_backend(_cfg("auto"), quiet_logger(), None), CpuBackend
    )


# -------------------------------------------------- launchers, the cache


def test_compile_cache_env_wins(monkeypatch, smoke):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(jaxenv.CACHE_ENV, "/somewhere/else")
    assert jaxenv.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # no dir set
    # Either way kernels key on their own source lines, not the caller's.
    assert jax.config.jax_include_full_tracebacks_in_locations is False


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, smoke):
    import jax

    monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert jaxenv.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_require_devices_errors_instead_of_substituting():
    import jax

    have = len(jax.devices())
    assert len(jaxenv.require_devices(have)) == have
    with pytest.raises(SystemExit, match=f"need {have + 1} devices"):
        jaxenv.require_devices(have + 1)


# ------------------------------------------- a refused program is named


def test_refused_program_is_logged_with_kernel_and_shapes():
    """The breaker ladder counts every device failure; one the compiler
    or the allocator raises is also logged at ERROR with the kernel and
    its full shapes, so a refusal cannot hide behind 'matches still
    flow'. Device weather is not."""
    import io
    import logging

    from nakama_tpu.logger import Logger
    from nakama_tpu.matchmaker import LocalMatchmaker
    from nakama_tpu.matchmaker.tpu import TpuBackend

    buf = io.StringIO()
    log = Logger(level=logging.INFO, fmt="json", streams=[buf])
    cfg = _cfg("tpu")
    backend = TpuBackend(cfg, log)
    LocalMatchmaker(log, cfg, backend=backend)  # binds store and record
    assert '"pallas_interpret": true' in buf.getvalue()  # said at INFO
    backend._dispatching = backend._variant(
        "topk_candidates_big", 131072, 131072, 1024, 1024, True, False, True
    )
    backend._note_backend_failure(
        "dispatch",
        RuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"
            " ... Scoped allocation with size 19.57M and limit 16.00M"
        ),
        {},
    )
    refused = [
        json.loads(ln) for ln in buf.getvalue().splitlines()
        if "program refused" in ln
    ]
    assert len(refused) == 1 and refused[0]["level"] == "error"
    assert refused[0]["kernel"] == "topk_candidates_big"
    assert (refused[0]["a_pad"], refused[0]["fn"], refused[0]["rev"]) == (
        131072, backend.fn, True,
    )
    # The tile stage 1 really runs (halved from the configured 1024 to
    # fit VMEM at shipped widths), not the configured one.
    assert refused[0]["row_block"] == 512 and refused[0]["col_block"] == 1024
    assert refused[0]["winners_per_block"] == 1
    assert backend.device_path_faults()  # and it fails smoke and bench
    backend._note_backend_failure("collect", OSError("link reset"), {})
    assert buf.getvalue().count("program refused") == 1
    # A cohort collected late fails as ITS dispatch, not the newest one.
    backend._note_backend_failure(
        "collect", RuntimeError("RESOURCE_EXHAUSTED: out of memory"), {},
        variant=backend._variant(
            "topk_candidates_big", 8192, 131072, 1024, 1024, False, False,
            True,
        ),
    )
    late = json.loads(buf.getvalue().splitlines()[-1])
    assert (late["stage"], late["a_pad"]) == ("collect", 8192)

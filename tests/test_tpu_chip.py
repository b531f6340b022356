"""Chip-executed parity tier (VERDICT r3 #7): runs the selfcheck's
kernel/oracle parity assertions under REAL Mosaic lowering. Skipped in
the default CPU-forced run; on the chip machine (through the chip tool,
after `python chip_smoke.py` has passed) execute with:

    NAKAMA_TPU_TESTS=1 python -m pytest tests/test_tpu_chip.py -m tpu

bench.py invokes the same selfcheck before reporting numbers, so every
hardware bench run asserts correctness first. The big-kernel leg runs at
the shipped default widths.
"""

import pytest


@pytest.mark.tpu
def test_chip_selfcheck_parity():
    import jax

    # The chip tier never skips on CPU: asked for by name
    # (NAKAMA_TPU_TESTS=1 -m tpu), a host without the chip fails.
    assert jax.devices()[0].platform == "tpu", jax.devices()
    from nakama_tpu.matchmaker.selfcheck import run_chip_selfcheck

    results = run_chip_selfcheck(log=lambda *a: None)
    assert results["small_exact_parity"] > 20
    assert results["big_valid_entries"] > 400
    assert results["pairing_valid_entries"] > 400

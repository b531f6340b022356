"""The pairing rounds' compaction (`device2.pair_partners`): each round
gathers availability for its open rows only, walked in steps of
`PAIR_CHUNK` rows. Held here to a frozen copy of the dense rounds (every
row, every round: the program as commit 641ab22 had it): partner vector
and counters equal on lists of every awkward kind, at shapes where the
rows a round runs at really change from round to round; the rows each
round ran at are the ladder's; one executable serves every history of
one shape.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nakama_tpu.matchmaker import device2
from nakama_tpu.matchmaker.device2 import _mix, pair_ladder, pair_partners

STEP = device2.PAIR_CHUNK


@functools.partial(jax.jit, static_argnames=("cap", "rounds"))
def dense_pair_partners(cand, active_slots, *, cap, rounds=8):
    """Frozen: `pair_partners` of commit 641ab22, every [A, k] cell
    gathered in every round. Beside `formed` the scan returns the rows
    open at the start of each round."""
    a = cand.shape[0]
    i32 = jnp.int32
    rows = jnp.arange(a, dtype=i32)
    big = jnp.int32(2**31 - 1)
    valid_row = active_slots >= 0
    slot_of_row = jnp.maximum(active_slots, 0)
    # Pad rows (active_slots == -1) must not scatter: an index of
    # slot_of_row=0 would clobber slot 0's real owner and let the same
    # pair report from both sides (duplicate slots downstream).
    row_of_slot = (
        jnp.full((cap,), -1, i32)
        .at[jnp.where(valid_row, slot_of_row, cap)]
        .set(rows, mode="drop")
    )
    cand_safe = jnp.maximum(cand, 0)
    # 2654435761 (Knuth) wrapped to int32 — jnp int32 math must not see a
    # Python int above 2^31.
    row_mix = (_mix(rows * jnp.int32(-1640531527) + 97) & 0x7FFFFFFF).astype(
        i32
    )

    def round_fn(state, r):
        avail_slot, partner = state
        # A row is open while it neither formed a pair (partner set) nor
        # had its own slot taken by an accepted proposal.
        row_open = valid_row & (partner < 0) & avail_slot[slot_of_row]
        cand_ok = (cand >= 0) & avail_slot[cand_safe] & row_open[:, None]
        navail = jnp.sum(cand_ok, axis=1).astype(i32)
        has = navail > 0
        j = jnp.where(
            has & (r > 0), (row_mix * r) % jnp.maximum(navail, 1), 0
        )
        csum = jnp.cumsum(cand_ok, axis=1)
        first = jnp.argmax(csum == (j + 1)[:, None], axis=1)
        prop = jnp.where(has, jnp.take_along_axis(
            cand, first[:, None], axis=1)[:, 0], -1)
        prop_safe = jnp.maximum(prop, 0)

        # Acceptance: oldest proposer (min row index) per slot, one
        # scatter-min + one gather. (A sort-based formulation was tried
        # and measured SLOWER: two [A] lax.sorts cost more than one
        # scatter on this chip.)
        win = (
            jnp.full((cap,), big, i32)
            .at[jnp.where(prop >= 0, prop, cap + 1)]
            .min(rows, mode="drop")
        )
        pwin = (prop >= 0) & (win[prop_safe] == rows)

        trow = jnp.where(prop >= 0, row_of_slot[prop_safe], -1)
        t_is_row = trow >= 0
        t_safe = jnp.maximum(trow, 0)
        t_pwin = pwin[t_safe] & t_is_row
        t_prop = jnp.where(t_is_row, prop[t_safe], -1)
        mutual = t_is_row & (t_prop == slot_of_row)
        ok_t = (~t_is_row) | (~t_pwin) | (mutual & (rows < trow))
        form = pwin & ok_t

        partner = jnp.where(form, prop, partner)
        # ONE fused availability scatter: both sides of every formed pair.
        taken = jnp.concatenate(
            [
                jnp.where(form, slot_of_row, cap + 1),
                jnp.where(form, prop_safe, cap + 1),
            ]
        )
        avail_slot = avail_slot.at[taken].set(False, mode="drop")
        return (avail_slot, partner), (
            jnp.sum(form, dtype=i32), jnp.sum(row_open, dtype=i32))

    init = (
        jnp.ones((cap,), dtype=bool),
        jnp.full((a,), -1, i32),
    )
    (_, partner), (formed, n_open) = jax.lax.scan(
        round_fn, init, jnp.arange(rounds, dtype=i32)
    )
    listed = jnp.count_nonzero(cand >= 0).astype(i32)
    return partner, formed[None], listed[None], n_open[None]


def _lists(rng, a, k, cap, valid, fill):
    """`valid` rows (a boolean mask or a count, the head) on distinct
    slots of `cap`; `fill(i, slot)` gives row i's list."""
    mask = np.zeros(a, bool)
    if isinstance(valid, int):
        mask[:valid] = True
    else:
        mask[:] = valid
    active = np.full(a, -1, np.int32)
    active[mask] = rng.permutation(cap)[: int(mask.sum())]
    cand = np.full((a, k), -1, np.int32)
    for i in np.nonzero(mask)[0]:
        c = np.asarray(fill(i, active[i]), np.int32)[:k]
        cand[i, : len(c)] = np.where(c == active[i], -1, c)
    return cand, active


def _case_overlap(rng):
    """Equal-score pools: every row lists the same 8 slots first, in the
    same order, so round 0 forms one pair; the rounds after it live on
    the diffusion."""
    a, cap = 4 * STEP, 4 * STEP
    same = rng.permutation(cap)[:8]
    return _lists(
        rng, a, 16, cap, a - 100,
        lambda i, s: np.concatenate([same, rng.integers(0, cap, 8)])), cap


def _case_pad_rows(rng):
    """Pad rows among the live ones and most of the tail."""
    a, cap = 4 * STEP, 8 * STEP
    valid = rng.random(a) < 0.4
    return _lists(rng, a, 8, cap, valid,
                  lambda i, s: rng.integers(0, cap, 8)), cap


def _case_passive_slots(rng):
    """Lists reach slots that are no row: they accept, never propose."""
    a, cap = 4 * STEP, 16 * STEP
    return _lists(rng, a, 8, cap, a,
                  lambda i, s: rng.integers(0, cap, 8)), cap


def _case_holes(rng):
    """-1 anywhere in a list, and rows whose list is empty."""
    a, cap = 4 * STEP, 4 * STEP

    def fill(i, s):
        c = rng.integers(0, cap, 12)
        c[rng.random(12) < (0.5 if i % 7 else 1.0)] = -1
        return c
    return _lists(rng, a, 12, cap, a - 3, fill), cap


def _case_a_round_forms_nothing(rng):
    """Two rows in three list each other, the third lists the first:
    round 0 pairs the mutual ones, and every later round finds the rest
    open and forms nothing."""
    a, cap = 2 * STEP, 2 * STEP
    active = np.arange(a, dtype=np.int32)
    cand = np.full((a, 4), -1, np.int32)
    trio = np.arange(a) // 3 * 3
    cand[:, 0] = np.where(np.arange(a) % 3 == 0, trio + 1, trio)
    cand[cand >= a] = -1
    return (cand, active), cap


def _case_below_the_floor(rng):
    """Fewer rows than one step: the ladder is `a` alone."""
    a, cap = 512, 1024
    return _lists(rng, a, 16, cap, a - 37,
                  lambda i, s: rng.choice(cap, rng.integers(0, 17),
                                          replace=False)), cap


def _case_on_a_ladder_edge(rng):
    """Exactly two steps of rows open in round 0."""
    a, cap = 4 * STEP, 4 * STEP
    return _lists(rng, a, 8, cap, 2 * STEP,
                  lambda i, s: rng.integers(0, cap, 8)), cap


def _case_one_past_the_edge(rng):
    a, cap = 4 * STEP, 4 * STEP
    return _lists(rng, a, 8, cap, 2 * STEP + 1,
                  lambda i, s: rng.integers(0, cap, 8)), cap


def _case_every_row_open(rng):
    """No pad row: round 0 runs the ladder's top."""
    a, cap = 2 * STEP, 4 * STEP
    return _lists(rng, a, 8, cap, a,
                  lambda i, s: rng.integers(0, cap, 8)), cap


def _case_no_row(rng):
    """A warm-up's fully masked pass: nothing open, nothing run."""
    a, cap = 2 * STEP, 2 * STEP
    return (np.full((a, 8), -1, np.int32), np.full(a, -1, np.int32)), cap


def _case_rows_no_multiple_of_the_step(rng):
    """`a` = 1.5 steps: the ladder's top is the first multiple past it."""
    a, cap = STEP + STEP // 2, 2 * STEP
    return _lists(rng, a, 8, cap, a - 5,
                  lambda i, s: rng.integers(0, cap, 8)), cap


CASES = {
    name[len("_case_"):]: fn for name, fn in sorted(globals().items())
    if name.startswith("_case_")
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    (cand, active), cap = CASES[request.param](np.random.default_rng(32))
    got = pair_partners(jnp.asarray(cand), jnp.asarray(active), cap=cap)
    want = dense_pair_partners(
        jnp.asarray(cand), jnp.asarray(active), cap=cap)
    return (request.param, cand.shape[0],
            [np.asarray(x) for x in got], [np.asarray(x) for x in want])


def test_partners_and_counters_are_the_dense_rounds(case):
    _, _, (partner, formed, listed, ran), (d_partner, d_formed, d_listed,
                                           _) = case
    assert partner.dtype == d_partner.dtype == np.int32
    assert partner.tobytes() == d_partner.tobytes()
    assert formed.tolist() == d_formed.tolist()
    assert listed.tolist() == d_listed.tolist()
    assert formed.shape == ran.shape == (1, 8) and ran.dtype == np.int32


def test_each_round_ran_the_smallest_ladder_size_that_holds_its_open_rows(
        case):
    name, a, (_, _, _, ran), (_, _, _, n_open) = case
    ladder = pair_ladder(a)
    for rows, opened in zip(ran[0].tolist(), n_open[0].tolist()):
        fits = [size for size in ladder if size >= opened]
        assert rows == (min(fits) if opened else 0), (name, rows, opened)
    assert ran[0].max() <= ladder[-1] < a + STEP


def test_the_cases_really_move_along_the_ladder(case):
    """What each case is for, read off the rows its rounds ran at."""
    name, a, (partner, formed, _, ran), (_, _, _, n_open) = case
    ran, n_open = ran[0].tolist(), n_open[0].tolist()
    if name == "below_the_floor":
        assert pair_ladder(a) == (a,) and set(ran) <= {0, a}
    elif name == "on_a_ladder_edge":
        assert n_open[0] == ran[0] == 2 * STEP
    elif name == "one_past_the_edge":
        assert n_open[0] == 2 * STEP + 1 and ran[0] == 3 * STEP
    elif name == "every_row_open":
        assert ran[0] == a == pair_ladder(a)[-1]
    elif name == "no_row":
        assert ran == [0] * 8 and (partner == -1).all()
    elif name == "a_round_forms_nothing":
        assert formed[0, 0] > 0 and formed[0, 1:].tolist() == [0] * 7
        assert ran[1] == ran[7] == STEP and n_open[7] > 0
    elif name == "rows_no_multiple_of_the_step":
        assert pair_ladder(a) == (STEP, 2 * STEP) and ran[0] == 2 * STEP
    else:
        assert len(set(ran)) >= 3 and ran[0] > ran[-1]  # it switched


def test_one_executable_whatever_the_open_row_history():
    """The rows a round runs at are the device's count, not a shape:
    lists that close quickly and lists that never close share one
    compiled program."""
    rng = np.random.default_rng(5)
    a, cap, k = 2 * STEP + 256, 4 * STEP, 4  # a shape no other test uses
    quick = _lists(rng, a, k, cap, a, lambda i, s: rng.integers(0, cap, k))
    never = (np.full((a, k), -1, np.int32), quick[1])
    before = pair_partners._cache_size()
    rans = []
    for cand, active in (quick, never, quick):
        out = pair_partners(jnp.asarray(cand), jnp.asarray(active), cap=cap)
        rans.append(np.asarray(out[3])[0].tolist())
    assert pair_partners._cache_size() == before + 1
    assert rans[0] == rans[2] != rans[1]
    assert rans[1] == [pair_ladder(a)[-1]] * 8 and rans[0][-1] < rans[0][0]

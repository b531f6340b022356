"""`MatchBatch` (matchmaker/types.py): one interval's matches as columns.

A columnar batch makes its matches in one pass over its columns on the
first entry access, and every match is a slice of one flat entry list.
Pinned here against the slow way (a match's entry list built ticket by
ticket from `offsets` and `slots`): iteration, indexing, slices, length,
equality, `entry_count` and `tickets(i)`; that the store's deferred
snapshot is resolved once and only when entries are asked for; and that
a columnar batch keeps no list a match.
"""

import numpy as np
import pytest

from nakama_tpu.matchmaker import MatchmakerPresence
from nakama_tpu.matchmaker.types import (
    MatchBatch,
    MatchmakerEntry,
    MatchmakerTicket,
)

POOL = 64

# name -> (entries of pool ticket i, the matches' sizes in tickets)
CASES = {
    "solo_twos": (lambda i: 1, [2] * 20),
    "solo_tens": (lambda i: 1, [10] * 6),
    "ragged_3_4": (lambda i: 1, [3, 4, 4, 3, 3, 4, 3]),
    "parties_1_5": (lambda i: 1 + (i * 7) % 5, [2, 3, 1, 4, 2, 5, 1, 3]),
    "parties_and_solos": (
        lambda i: 1 if i % 3 else 2 + i % 4, [2, 2, 3, 2, 4, 2]
    ),
    "empty": (lambda i: 1, []),
}


def _ticket(i, n_entries):
    entries = [
        MatchmakerEntry(
            ticket=f"t{i}",
            presence=MatchmakerPresence(
                user_id=f"u{i}.{j}", session_id=f"s{i}.{j}",
                username=f"n{i}.{j}",
            ),
            party_id="" if n_entries == 1 else f"p{i}",
        )
        for j in range(n_entries)
    ]
    return MatchmakerTicket(
        ticket=f"t{i}", query="*", min_count=2, max_count=10,
        count_multiple=1, session_id=entries[0].presence.session_id,
        party_id=entries[0].party_id, entries=entries,
        string_properties={}, numeric_properties={}, created_at=0.0,
    )


class _Columns:
    """A pool's slot-indexed columns and one batch's matches over them,
    with the entry lists built the slow way beside."""

    def __init__(self, case, with_counts=True):
        n_entries, sizes = CASES[case]
        self.ticket_at = np.empty(POOL, dtype=object)
        for i in range(POOL):
            self.ticket_at[i] = _ticket(i, n_entries(i))
        self.counts = np.array(
            [n_entries(i) for i in range(POOL)], dtype=np.int32
        )
        rng = np.random.default_rng(len(sizes))
        self.slots = rng.permutation(POOL)[: sum(sizes)].astype(np.int32)
        self.offsets = np.zeros(len(sizes) + 1, dtype=np.int64)
        np.cumsum(sizes, out=self.offsets[1:])
        self.resolved = 0
        self.with_counts = with_counts
        self.expected = []
        for lo, hi in zip(self.offsets[:-1], self.offsets[1:]):
            entries = []
            for slot in self.slots[lo:hi]:
                entries.extend(self.ticket_at[slot].entries)
            self.expected.append(entries)

    def resolve(self):
        self.resolved += 1
        return self.ticket_at[self.slots]

    def batch(self):
        """As the interval path makes it: the snapshot deferred."""
        batch = MatchBatch(
            self.offsets, self.slots,
            counts=self.counts if self.with_counts else None,
        )
        batch.bind_tickets(self.resolve)
        return batch


@pytest.fixture(params=list(CASES))
def columns(request):
    return _Columns(request.param)


def _same_objects(got, expected):
    return len(got) == len(expected) and all(
        len(g) == len(e) and all(a is b for a, b in zip(g, e))
        for g, e in zip(got, expected)
    )


# ------------------------------------------------------------ the matches


def test_iteration_is_the_entry_lists_built_ticket_by_ticket(columns):
    batch = columns.batch()
    assert _same_objects(list(batch), columns.expected)
    # and again: a second pass serves the same flat list
    assert _same_objects(list(batch), columns.expected)
    assert len(batch) == len(columns.expected)
    assert bool(batch) == bool(columns.expected)


def test_index_negative_index_and_slice_agree_with_iteration(columns):
    batch, expected = columns.batch(), columns.expected
    n = len(expected)
    for i in range(n):
        assert _same_objects([batch[i]], [expected[i]])
        assert _same_objects([batch[i - n]], [expected[i - n]])
    for bad in (n, -n - 1):
        with pytest.raises(IndexError):
            batch[bad]
    for cut in (slice(None), slice(1, None, 2), slice(-3, None),
                slice(None, None, -1), slice(2, 2)):
        assert _same_objects(batch[cut], expected[cut])


def test_equality_and_entry_count(columns):
    batch, expected = columns.batch(), columns.expected
    assert batch == expected
    assert batch == columns.batch()
    assert batch == MatchBatch.from_lists(expected)
    assert not batch == expected + [[]]
    if expected:
        assert not batch == expected[:-1]
    assert batch.entry_count == sum(len(m) for m in expected)


def test_tickets_of_a_match_are_its_slots_tickets(columns):
    batch = columns.batch()
    for i, (lo, hi) in enumerate(
        zip(columns.offsets[:-1], columns.offsets[1:])
    ):
        assert batch.tickets(i) == [
            columns.ticket_at[s] for s in columns.slots[lo:hi]
        ]


@pytest.mark.parametrize("case", list(CASES))
def test_without_counts_the_entries_say_how_many(case):
    columns = _Columns(case, with_counts=False)
    batch = columns.batch()
    assert _same_objects(list(batch), columns.expected)
    assert batch.entry_count == sum(len(m) for m in columns.expected)


# ------------------------------------------- the snapshot and what is kept


@pytest.mark.parametrize(
    "access",
    ["iterate", "index", "slice", "equal", "tickets"],
)
def test_snapshot_is_resolved_once_and_only_for_entries(columns, access):
    batch = columns.batch()
    # The columns alone answer these.
    assert len(batch) == len(columns.expected)
    assert batch.entry_count == sum(len(m) for m in columns.expected)
    assert bool(batch) == bool(columns.expected)
    assert columns.resolved == 0
    first = {
        "iterate": lambda: list(batch),
        "index": lambda: batch[0] if columns.expected else list(batch),
        "slice": lambda: batch[:2] if columns.expected else list(batch),
        "equal": lambda: batch == columns.expected,
        "tickets": lambda: (
            batch.tickets(0) if columns.expected else list(batch)
        ),
    }[access]
    first()
    assert columns.resolved == 1
    list(batch), batch[:], batch == columns.expected
    if columns.expected:
        batch[-1], batch.tickets(0)
    assert columns.resolved == 1


def test_an_eager_snapshot_needs_no_resolver(columns):
    batch = MatchBatch(
        columns.offsets, columns.slots, columns.ticket_at, columns.counts
    )
    batch.bind_tickets(columns.resolve)  # the bound snapshot stands
    assert _same_objects(list(batch), columns.expected)
    assert columns.resolved == 0


def test_a_columnar_batch_keeps_no_list_a_match(columns):
    batch = columns.batch()
    list(batch), batch[:], batch == columns.expected
    assert not batch._cache
    flat, bounds = batch._columns()
    assert (flat, bounds) == (batch._flat, batch._bounds)
    assert len(bounds) == len(columns.expected) + 1
    assert _same_objects([flat], [sum(columns.expected, [])])


def test_solo_pool_bounds_are_the_offsets_themselves():
    columns = _Columns("ragged_3_4")
    _, bounds = columns.batch()._columns()
    assert bounds == columns.offsets.tolist()
    assert all(type(b) is int for b in bounds)


def test_party_bounds_are_the_running_entry_count_at_the_offsets():
    columns = _Columns("parties_1_5")
    _, bounds = columns.batch()._columns()
    ends = np.concatenate(([0], np.cumsum(columns.counts[columns.slots])))
    assert bounds == ends[columns.offsets].tolist()
    assert bounds != columns.offsets.tolist()


# ---------------------------------------------------------- from_lists


def test_from_lists_serves_the_lists_it_was_given():
    expected = _Columns("parties_1_5").expected
    batch = MatchBatch.from_lists(expected)
    assert all(a is b for a, b in zip(batch, expected))
    assert len(batch) == len(expected) and batch
    n = len(expected)
    assert all(batch[i] is expected[i] for i in range(-n, n))
    assert batch[1:5:2] == expected[1:5:2]
    with pytest.raises(IndexError):
        batch[n]
    assert batch == expected and batch == MatchBatch.from_lists(expected)
    assert batch.entry_count == sum(len(m) for m in expected)
    with pytest.raises(ValueError, match="no slot data"):
        batch.tickets(0)


def test_from_lists_copies_the_outer_list_and_may_be_empty():
    matches = [[object()], [object(), object()]]
    batch = MatchBatch.from_lists(matches)
    matches.append([object()])
    assert len(batch) == 2
    empty = MatchBatch.from_lists([])
    assert len(empty) == 0 and not empty and list(empty) == []
    assert empty.entry_count == 0 and empty == []

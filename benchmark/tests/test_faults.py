"""The rest of a run with the timed path broken underneath: `correct`
has to come out false, once for each fault a cell can have.

Skips the harness's look for a chip (`--rehearse 1`: the configuration's
tiny size, the interpreting backend) and drives everything else of
`run.py`: set-up, window, the judge, the last line. A sound run first,
so that a false `correct` below is the fault's doing.

  unchanged   a step that returns its state unchanged: process() is a
              no-op inside the window
  half        half of the batch left out: every second formed match is
              dropped where it is produced (never published)
  altered     an answer altered where it is produced: one member's
              properties swapped for another ticket's in what is published
  unranked    the embedding left out where the tickets are scored: the
              matches are valid and as many, of whoever is oldest
  unjournaled the journal switched off: adds are acknowledged and no row
              of them reaches the database file
"""

import copy
import json

import pytest

import run


def last_line(capsys, argv, sabotage=None):
    assert run.main(argv, sabotage=sabotage) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def burst(seed):
    return ["--workload", "ranked100k.burst", "--seed", str(seed),
            "--seconds", "6", "--trace", "0", "--rehearse", "1"]


def in_window_only(ctx, broken, sound):
    """`broken` once the window is open (set-up stays sound, as a change
    to the program would be warmed up too, but its effect is judged on
    what the window produced)."""
    def call(*a, **kw):
        return (broken if ctx.t0 is not None else sound)(*a, **kw)
    return call


def unchanged(ctx):
    ctx.mm.process = in_window_only(ctx, lambda: None, ctx.mm.process)


def half(ctx):
    publish = ctx.mm.on_matched
    ctx.mm.on_matched = in_window_only(
        ctx, lambda batch: publish(list(batch)[::2]), publish)


def altered(ctx):
    publish = ctx.mm.on_matched

    def swap(batch):
        batch = [list(entries) for entries in batch]
        if len(batch) >= 2:
            e = copy.copy(batch[0][0])
            e.numeric_properties = dict(batch[1][0].numeric_properties,
                                        rank=-5.0)
            e.string_properties = {"mode": "other"}
            batch[0][0] = e
        publish(batch)

    ctx.mm.on_matched = in_window_only(ctx, swap, publish)


def unranked(ctx):
    add = ctx.mm.add

    def add_without_embedding(*a, embedding=None, **kw):
        return add(*a, **kw)

    ctx.mm.add = add_without_embedding


def unjournaled(ctx):
    ctx.server.recovery.journal.enabled = False


def test_sound_run_is_correct(capsys):
    line = last_line(capsys, burst(21))
    assert line["correct"] is True
    assert list(line)[-1] == "checks" and line["device"]["platform"] == "cpu"
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert set(line["metrics"]) == {"tick_to_matched_p95_ms",
                                    "matched_per_s", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    (unchanged, "yield_shortfall"),
    (half, "yield_shortfall"),
    (altered, "ingest_mismatch"),
    (unranked, "similarity_shortfall"),
    (unjournaled, "acked_not_durable"),
])
def test_fault_reads_not_correct(capsys, fault, number):
    line = last_line(capsys, burst(22), sabotage=fault)
    assert line["correct"] is False
    check = line["checks"][number]
    assert check["value"] > check["limit"], line["checks"]


def test_steady_cell_sound_then_altered(capsys):
    argv = ["--workload", "duel1k.steady", "--seed", "23", "--seconds", "5",
            "--trace", "0", "--rehearse", "1"]
    line = last_line(capsys, argv)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] == 2500
    line = last_line(capsys, argv, sabotage=altered)
    assert line["correct"] is False

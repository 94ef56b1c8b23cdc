"""The bytes one MapReduce round must move, and the device program's share
of its HBM roofline in a job cell.

The count is of the work the round must do, from the job's rows and the
plan's emissions; it never reads the program's static capacities (the bin
``cap``, the route caps), so a faster reduce cannot leave it stale:

* read every input row once: ``4 · rows · arity`` (int32 cells);
* each emitted tuple, its cells and its reducer id, written and read once
  on each side of the exchange: ``4 · 4 · (arity + 1) · emissions``.
"""
from __future__ import annotations

from .harness import Run
from .roofline import roofline_share


def round_bytes(rows: int, arity: int, emissions: int) -> int:
    """One relation's bytes in one round."""
    return 4 * rows * arity + 16 * (arity + 1) * emissions


def shuffle_roofline(run: Run) -> float | None:
    """The window jobs' bytes over the chips' HBM bandwidth, over the
    device time of the window summed over the chips (``busy_s`` is its
    mean): in a job cell the device runs nothing but the jobs' programs.
    None unless every window job counted its emissions on the program's
    ``shuffle.fetch`` span."""
    t = run.trace
    jobs = run.window()
    if t is None or t.busy_s <= 0 or not jobs:
        return None
    counted = {
        e["args"]["batch"] for e in run.spans
        if e.get("name") == "shuffle.fetch" and "comm_tuples" in e.get("args", {})
    }
    if any(b.report is None or b.index not in counted for b in jobs):
        return None
    moved = 0
    for b in jobs:
        rows = run.batch_rows(b.index)
        moved += sum(
            round_bytes(len(rows[nm]), run.arity[nm], b.report.comm_tuples[nm])
            for nm in run.arity
        )
    chips = run.cell.chips
    return roofline_share(moved, t.busy_s * chips, run.peaks["hbm_bytes_per_s"])

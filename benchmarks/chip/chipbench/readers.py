"""Arithmetic the metric readers in ``metrics/`` share.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

from . import xtrace
from .harness import INGEST_KERNEL, Run
from .roofline import ingest_bytes, roofline_share


def served(run: Run):
    return [b for b in run.window() if b.report is not None]


def rows_per_s(run: Run) -> float | None:
    done = served(run)
    if not done:
        return None
    return sum(b.rows for b in done) / (max(b.done for b in done) - run.window_start)


def span_ms_per_batch(run: Run, names: tuple[str, ...]) -> float | None:
    """Milliseconds per window batch in the engine's spans of ``names``."""
    window = {b.index for b in run.window()}
    if not run.spans or not window:
        return None
    total_us = sum(
        e.get("dur", 0.0)
        for e in run.spans
        if e.get("name") in names and e.get("args", {}).get("batch") in window
    )
    return total_us / 1e3 / len(window)


def idle_pct(run: Run) -> float | None:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def kernel_ms(run: Run) -> float | None:
    t = run.trace
    if t is None or not run.window():
        return None
    ops = xtrace.kernel_ops(t.ops, INGEST_KERNEL)
    if not ops:
        return None
    return sum(o.dur for o in ops) / 1e6 / len(run.window())


def ingest_roofline(run: Run) -> float | None:
    """The fused ingest's share of its HBM roofline over the window's
    batches that did not replan: one kernel call per relation, of known
    rows and emissions."""
    t = run.trace
    if t is None:
        return None
    steady = [
        b for b in served(run)
        if not getattr(b.report, "replanned", True) and not getattr(b.report, "migrated_tuples", 1)
    ]
    spans = {s.stats.get("batch"): s for s in t.spans if s.name == "ingest"}
    steady = [b for b in steady if b.index in spans]
    calls = xtrace.within(
        xtrace.kernel_ops(t.ops, INGEST_KERNEL), [spans[b.index] for b in steady]
    )
    moved = seconds = 0.0
    for b, ops in zip(steady, calls):
        comm = b.report.comm_tuples
        if len(ops) != len(run.arity) or set(comm) != set(run.arity):
            continue
        rows = {nm: len(r) for nm, r in run.batch_rows(b.index).items()}
        moved += sum(
            ingest_bytes(rows[nm], run.arity[nm], comm[nm], run.sketch_cells)
            for nm in run.arity
        )
        seconds += sum(o.dur for o in ops) / 1e9
    if seconds <= 0:
        return None
    return roofline_share(moved, seconds, run.peaks["hbm_bytes_per_s"])


def comm_per_row(run: Run) -> float | None:
    done = served(run)
    rows = sum(b.rows for b in done)
    if not rows:
        return None
    return sum(sum(b.report.comm_tuples.values()) for b in done) / rows


def imbalance(run: Run) -> float | None:
    done = served(run)
    if not done:
        return None
    payload = getattr(done[-1].report, "obs", None) or {}
    skew = payload.get("skew") or {}
    return skew.get("imbalance")

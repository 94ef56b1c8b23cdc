"""The readers of the engine's span tree (``chipbench/spans.py`` and the six
metrics on it) on a hand-made run: each reads what it should, and finds
nothing in a program whose spans lack the names, counts or parent ids."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, spec  # noqa: E402


def span(name, batch, seq, parent, ts, dur, **counts):
    args = {"batch": batch, "span_id": f"{batch}:{seq}", "parent": parent, **counts}
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur), "args": args}


def make_run(spans, batches=((0, "fill", 900), (1, "window", 1000), (2, "window", 3000))):
    made = []
    for index, phase, rows in batches:
        b = harness.Batch(index, phase, rows, start=float(index), done=index + 1.0)
        b.report = types.SimpleNamespace(comm_tuples={}, obs=None)
        made.append(b)
    return harness.Run(
        cell=None, setup_s=1.0, window_start=1.0, batches=made, spans=list(spans),
        trace=None, peaks={}, arity={"R": 2, "S": 2}, sketch_cells=8, pool={},
    )


def batch_tree(b, t0, migrate_scatter=False):
    """One batch: ``ingest`` [t0, t0+1000) µs with its phases."""
    out = [
        span("ingest", b, 1, None, t0, 1000),
        span("route.fused", b, 2, f"{b}:1", t0 + 10, 100, h2d_bytes=8000 * b, d2h_bytes=18000 * b),
        span("route.fetch", b, 3, f"{b}:2", t0 + 60, 40),
        span("sketch.update", b, 4, f"{b}:1", t0 + 120, 500),
        span("sketch.cms", b, 5, f"{b}:4", t0 + 120, 20),
        span("sketch.candidates", b, 6, f"{b}:4", t0 + 140, 470),
        span("join.delta", b, 7, f"{b}:1", t0 + 640, 300),
        span("join.scatter", b, 8, f"{b}:7", t0 + 700, 50),
        span("join.scatter", b, 9, f"{b}:7", t0 + 800, 30),
    ]
    if migrate_scatter:  # a scatter outside join.delta is not the metric's
        out += [
            span("replan.migrate", b, 10, f"{b}:1", t0 + 950, 40),
            span("join.scatter", b, 11, f"{b}:10", t0 + 955, 30),
            span("route.fetch", b, 12, f"{b}:10", t0 + 990, 5),
        ]
    return out


def read(name, run):
    return spec.load_reader(name)(run)


def test_leaf_span_ms_per_window_batch():
    run = make_run(batch_tree(0, 0) + batch_tree(1, 2000, migrate_scatter=True) + batch_tree(2, 4000))
    assert read("sketch.candidates_ms", run) == pytest.approx(0.470)
    # join.scatter inside join.delta only: 80 µs a batch; migrate's is left out
    assert read("join.scatter_ms", run) == pytest.approx(0.080)
    assert read("route.fetch_ms", run) == pytest.approx(0.040)


def test_transfer_bytes_per_window_row():
    run = make_run(batch_tree(0, 0) + batch_tree(1, 2000) + batch_tree(2, 4000))
    # batch 1: 8,000 / 18,000 B over 1,000 rows; batch 2: 16,000 / 36,000 over 3,000
    assert read("route.h2d_bytes_per_row", run) == pytest.approx(24000 / 4000)
    assert read("route.d2h_bytes_per_row", run) == pytest.approx(54000 / 4000)


def test_ingest_self_ms_subtracts_the_union_of_overlapping_children():
    spans = [
        span("ingest", 1, 1, None, 0, 1000),
        span("a", 1, 2, "1:1", 100, 300),  # [100, 400)
        span("b", 1, 3, "1:1", 300, 200),  # [300, 500): overlaps a
        span("c", 1, 4, "1:1", 900, 300),  # [900, 1200): clipped to 1000
        span("a.child", 1, 5, "1:2", 100, 300),  # a grandchild counts once, through a
        span("ingest", 2, 1, None, 2000, 1000),
        span("d", 2, 2, "2:1", 2000, 1000),  # all of it covered
    ]
    # batch 1: 1000 - (400 + 100) = 500 µs; batch 2: 0
    assert read("ingest.self_ms", make_run(spans)) == pytest.approx(0.25)


@pytest.mark.parametrize(
    "name",
    ["sketch.candidates_ms", "join.scatter_ms", "route.fetch_ms",
     "route.h2d_bytes_per_row", "route.d2h_bytes_per_row", "ingest.self_ms"],
)
def test_a_program_without_the_spans_reads_nothing(name):
    # the spans a program without leaf spans, counts or parent ids emits
    old = [
        {"name": n, "ph": "X", "ts": 0.0, "dur": 10.0, "args": {"batch": 1, "span_id": f"1.{i}"}}
        for i, n in enumerate(("ingest", "route.fused", "sketch.update", "join.delta"), 1)
    ]
    assert read(name, make_run(old)) is None
    assert read(name, make_run([])) is None

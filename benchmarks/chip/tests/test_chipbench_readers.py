"""The metric readers on a hand-made run: each reads what it should, and a
reader with nothing to read returns None."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, readers, spec  # noqa: E402
from chipbench.roofline import ingest_bytes  # noqa: E402
from chipbench.xtrace import Op, Span  # noqa: E402


def report(comm, replanned=False, skew=None):
    return types.SimpleNamespace(
        comm_tuples=comm, replanned=replanned, migrated_tuples=0,
        obs={"skew": skew} if skew else None,
    )


def batch(index, start, done, rows=(1000, 100), comm=(1000, 200), phase="window", **kw):
    b = harness.Batch(index, phase, sum(rows), start=start, done=done)
    b.report = report({"R": comm[0], "S": comm[1]}, **kw)
    return b


def make_run(batches, spans=(), trace=None):
    pool = {b.index: {"R": np.zeros((1000, 2)), "S": np.zeros((100, 2))} for b in batches}
    return harness.Run(
        cell=None, setup_s=12.5, window_start=10.0, batches=list(batches),
        spans=list(spans), trace=trace, peaks={"hbm_bytes_per_s": 1e9},
        arity={"R": 2, "S": 2}, sketch_cells=8, pool=pool,
    )


def test_rows_per_s_is_all_window_rows_over_the_window():
    run = make_run([
        batch(0, 1.0, 2.0, phase="fill"),
        batch(1, 10.0, 11.0),
        batch(2, 11.0, 14.0),
    ])
    assert readers.rows_per_s(run) == pytest.approx(2 * 1100 / 4.0)
    assert readers.rows_per_s(make_run([batch(0, 1.0, 2.0, phase="fill")])) is None


def test_span_ms_per_batch_counts_window_batches_only():
    spans = [
        {"name": "join.delta", "dur": 3000.0, "args": {"batch": 1}},
        {"name": "join.delta", "dur": 5000.0, "args": {"batch": 2}},
        {"name": "join.delta", "dur": 9000.0, "args": {"batch": 0}},  # warm-up
        {"name": "drift.check", "dur": 7000.0, "args": {"batch": 1}},
    ]
    run = make_run([batch(0, 1, 2, phase="fill"), batch(1, 10, 11), batch(2, 11, 12)], spans)
    assert readers.span_ms_per_batch(run, ("join.delta",)) == pytest.approx(4.0)
    assert readers.span_ms_per_batch(run, ("join.delta", "drift.check")) == pytest.approx(7.5)
    assert readers.span_ms_per_batch(make_run([batch(1, 10, 11)]), ("join.delta",)) is None


def test_comm_per_row_and_imbalance():
    run = make_run([batch(1, 10, 11), batch(2, 11, 12, skew={"imbalance": 1.25})])
    assert readers.comm_per_row(run) == pytest.approx(2 * 1200 / 2200)
    assert readers.imbalance(run) == 1.25
    assert readers.imbalance(make_run([batch(1, 10, 11)])) is None


def test_idle_and_kernel_time_from_the_trace():
    trace = harness.TraceView(
        ops=[Op(harness.INGEST_KERNEL, 100, 2e6), Op("fusion", 3e6, 1e6),
             Op(harness.INGEST_KERNEL, 5e6, 4e6)],
        spans=[], lo=0, hi=1e9, busy_s=0.007, window_s=1.0,
    )
    run = make_run([batch(1, 10, 11), batch(2, 11, 12)], trace=trace)
    assert readers.idle_pct(run) == pytest.approx(99.3)
    assert readers.kernel_ms(run) == pytest.approx(3.0)
    assert readers.idle_pct(make_run([batch(1, 10, 11)])) is None


def test_roofline_counts_steady_batches_with_one_call_per_relation():
    # batch 1: two kernel calls in its span; batch 2 replanned; batch 3 has one call
    ops = [Op(harness.INGEST_KERNEL, 10, 1000), Op(harness.INGEST_KERNEL, 20, 1000),
           Op(harness.INGEST_KERNEL, 110, 500), Op(harness.INGEST_KERNEL, 120, 500),
           Op(harness.INGEST_KERNEL, 210, 700)]
    spans = [Span("ingest", 0, 100, {"batch": 1}), Span("ingest", 100, 100, {"batch": 2}),
             Span("ingest", 200, 100, {"batch": 3})]
    trace = harness.TraceView(ops=ops, spans=spans, lo=0, hi=300, busy_s=0, window_s=1)
    run = make_run(
        [batch(1, 10, 11), batch(2, 11, 12, replanned=True), batch(3, 12, 13)], trace=trace
    )
    moved = ingest_bytes(1000, 2, 1000, 8) + ingest_bytes(100, 2, 200, 8)
    expected = 100.0 * (moved / 1e9) / 2e-6
    assert readers.ingest_roofline(run) == pytest.approx(expected)


@pytest.mark.parametrize("name", [m["name"] for m in spec.load_benchmark()["per_layer"]])
def test_per_layer_reader_finds_nothing_in_an_untraced_run(name):
    run = make_run([batch(1, 10, 11)])
    value = spec.load_reader(name)(run)
    # only a reader of program counters reads an untraced run
    source = {m["name"]: m["source"] for m in spec.load_benchmark()["per_layer"]}[name]
    assert value is None or source == "program_counter"

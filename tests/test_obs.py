"""Observability layer tests (DESIGN.md §10): tracer semantics, metrics
determinism, per-tenant series isolation, and SkewScope exactness.

The contracts, in the order the acceptance criteria state them:

  * spans nest and order correctly, and a disabled tracer hands every
    call site the same shared no-op span — zero allocation on the fused
    hot path;
  * ``MetricsRegistry.snapshot()`` is bit-deterministic for counters and
    gauges under seeded streams (wall time lives only in histograms);
  * tenants sharing one registry stay isolated series-wise: a fault in
    tenant B never touches tenant A's series;
  * SkewScope's per-reducer tuple counts equal the distributed shuffle
    oracle's ``reducer_loads`` bit-for-bit on a seeded Zipf batch.
"""
from __future__ import annotations

import glob
import json
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import two_way
from repro.mapreduce.shuffle import run_distributed
from repro.obs import (
    NULL_OBS,
    NULL_SPAN,
    MetricsRegistry,
    Observability,
    ObsPolicy,
    Tracer,
)
from repro.kernels import fused_ingest_dense
from repro.mapreduce.keys import static_route_table
from repro.stream import (
    MultiQueryEngine,
    RetentionPolicy,
    StreamConfig,
    StreamingJoinEngine,
    TenancyPolicy,
    TenantSpec,
)
from repro.testing.faults import FaultInjector, FaultSpec

pytestmark = pytest.mark.obs

ALL_ON = ObsPolicy(trace=True, metrics=True, skewscope=True)


def _zipf_batch(rng, n_r=900, n_s=250, domain=2500, a=1.7, shift=0):
    b_r = ((rng.zipf(a, n_r) - 1) + shift) % domain
    b_s = ((rng.zipf(a, n_s) - 1) + shift) % domain
    r = np.stack([rng.integers(0, domain, n_r), b_r], 1).astype(np.int64)
    s = np.stack([b_s, rng.integers(0, domain, n_s)], 1).astype(np.int64)
    return {"R": r, "S": s}


def _run_engine(n_batches=6, policy=ALL_ON, shift_at=3):
    rng = np.random.default_rng(7)
    eng = StreamingJoinEngine(
        two_way(), StreamConfig(q=100, decay=0.5, load_factor=2.0, obs=policy)
    )
    for i in range(n_batches):
        eng.ingest(_zipf_batch(rng, shift=0 if i < shift_at else 1100, a=1.5))
    return eng


# ---- tracer ----------------------------------------------------------------


def test_span_nesting_and_ordering():
    fake = [0]

    def clock():
        fake[0] += 1000  # 1µs per call, fully deterministic
        return fake[0]

    tr = Tracer(enabled=True, clock_ns=clock)
    tr.set_batch(0)
    with tr.span("outer"):
        assert tr.depth == 1
        with tr.span("inner", args={"k": 1}):
            assert tr.depth == 2
        tr.instant("mark")
    assert tr.depth == 0

    events = tr.to_chrome()["traceEvents"]
    by_name = {e["name"]: e for e in events}
    inner, outer = by_name["inner"], by_name["outer"]
    # completion events are emitted on exit: inner closes before outer
    assert events.index(inner) < events.index(outer)
    # the child interval lies strictly inside the parent's
    assert outer["ts"] < inner["ts"]
    assert inner["ts"] + inner["dur"] < outer["ts"] + outer["dur"]
    assert inner["args"]["k"] == 1
    # span ids are batch-scoped and sequential
    assert outer["args"]["span_id"] == "0:1"
    assert inner["args"]["span_id"] == "0:2"
    assert by_name["mark"]["ph"] == "i"


def test_disabled_tracer_is_allocation_free():
    tr = Tracer(enabled=False)
    # every call site gets the SAME shared no-op span object — nothing is
    # allocated on the hot path when tracing is off
    s1 = tr.span("ingest", args=None)
    s2 = tr.span("route", args=None)
    assert s1 is s2 is NULL_SPAN
    with s1:
        pass
    tr.instant("nothing")
    assert tr.to_chrome()["traceEvents"] == []
    # the NULL_OBS facade rides the same path
    assert NULL_OBS.span("x") is NULL_SPAN


def test_engine_trace_covers_batch_lifecycle(tmp_path):
    eng = _run_engine()
    names = eng.obs.tracer.span_names()
    for expected in (
        "ingest", "sketch.update", "route", "join.delta", "drift.check",
        "retention.expire", "replan", "replan.solve", "replan.migrate",
    ):
        assert expected in names, f"missing span {expected!r}: {names}"
    # every non-root event nests inside some ingest interval
    events = eng.obs.tracer.to_chrome()["traceEvents"]
    roots = [e for e in events if e["name"] == "ingest"]
    for e in events:
        if e["name"] == "ingest" or e["ph"] != "X":
            continue
        assert any(
            r["ts"] <= e["ts"] and e["ts"] + e["dur"] <= r["ts"] + r["dur"]
            for r in roots
        ), f"span {e['name']} is not nested inside an ingest span"
    # the dump is Chrome/Perfetto trace-event JSON
    out = tmp_path / "trace.json"
    eng.obs.tracer.dump(str(out))
    doc = json.loads(out.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == len(events)


# ---- metrics ---------------------------------------------------------------


def test_metrics_snapshot_determinism():
    a = _run_engine().obs.metrics.snapshot()
    b = _run_engine().obs.metrics.snapshot()
    # counters and gauges are bit-stable under the seeded stream; wall
    # time lives only in histogram sums, so compare bucket counts too
    assert a["counters"] == b["counters"]
    assert a["gauges"] == b["gauges"]
    assert set(a["histograms"]) == set(b["histograms"])
    for key in a["histograms"]:
        assert a["histograms"][key]["count"] == b["histograms"][key]["count"]
    # the replan trigger is a labeled counter series
    replans = {k: v for k, v in a["counters"].items()
               if k.startswith("stream_replan_total")}
    assert 'stream_replan_total{trigger="initial"}' in replans
    assert sum(replans.values()) >= 2  # initial install + the drift replan


def test_prometheus_dump_is_well_formed():
    reg = MetricsRegistry()
    reg.counter("stream_shed_rows_total", tenant="q1", rel="R").inc(3)
    reg.gauge("stream_hosts_alive").set(7)
    reg.histogram("stream_batch_seconds", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.to_prometheus()
    assert "# TYPE stream_shed_rows_total counter" in text
    assert 'stream_shed_rows_total{rel="R",tenant="q1"} 3' in text
    assert "stream_hosts_alive 7" in text
    assert 'stream_batch_seconds_bucket{le="0.1"} 1' in text
    assert 'stream_batch_seconds_bucket{le="+Inf"} 1' in text
    assert "stream_batch_seconds_count 1" in text


def test_disabled_registry_returns_null_instruments():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("anything", tenant="x")
    assert c is reg.gauge("other") is reg.histogram("third")
    c.inc(5)
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---- per-tenant isolation --------------------------------------------------


def test_tenant_label_isolation():
    query = two_way()
    cfg = StreamConfig(q=100, decay=0.5, load_factor=2.0)
    mq = MultiQueryEngine(
        [TenantSpec(f"q{i}", query, cfg) for i in range(2)],
        TenancyPolicy(obs=ObsPolicy(metrics=True)),
    )
    inj = FaultInjector(
        [FaultSpec(kind="poison_rows", target="tenant", tenant="q1",
                   batch=2, poison="nan")]
    )
    mq.arm_faults(inj)
    rng = np.random.default_rng(11)
    for _ in range(5):
        mq.ingest(_zipf_batch(rng))
    inj.assert_all_resolved()

    counters = mq.obs.metrics.snapshot()["counters"]
    # the poison pill tripped q1's breaker — and ONLY q1's series
    trips = {k: v for k, v in counters.items()
             if k.startswith("tenancy_breaker_transitions_total")}
    assert trips, "breaker transition was not recorded"
    assert all('tenant="q1"' in k for k in trips), trips
    # q0's per-tenant series are untouched by its neighbor's fault: it
    # ingested every batch, q1 skipped its quarantine window
    assert counters['stream_batches_total{tenant="q0"}'] == 5
    assert counters['stream_batches_total{tenant="q1"}'] < 5


# ---- skewscope -------------------------------------------------------------


def test_skewscope_matches_distributed_oracle():
    """Per-reducer tuple counts == the shuffle oracle's reducer_loads,
    bit-for-bit, on a seeded Zipf batch (the acceptance contract)."""
    rng = np.random.default_rng(3)
    batch = _zipf_batch(rng, n_r=1200, n_s=300, a=1.6)
    query = two_way()
    eng = StreamingJoinEngine(
        query,
        StreamConfig(q=100, decay=0.5, load_factor=2.0,
                     obs=ObsPolicy(skewscope=True)),
    )
    eng.ingest(batch)

    # generous caps: the contract needs a lossless oracle shuffle
    res = run_distributed(query, batch, eng.plan,
                          cap_factor=12.0, route_cap_factor=12.0)
    assert res.overflow == 0, "oracle shuffle overflowed — raise caps"

    skew = eng.obs.skew
    got = skew.tuples_per_reducer()
    want = np.asarray(res.reducer_loads, dtype=np.int64)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # and the engine's own load accounting agrees with both
    np.testing.assert_array_equal(np.asarray(eng._loads, dtype=np.int64), got)

    snap = eng.skew_report()
    assert snap.total_tuples == int(want.sum())
    assert snap.max_tuples == int(want.max())
    assert snap.imbalance == pytest.approx(want.max() / want.mean())
    assert 0.0 <= snap.hh_hit_rate <= 1.0
    # the retained window is the whole stream here: the decayed CMS
    # estimate is exact on every audited heavy hitter
    for err in snap.cms_error.values():
        assert err == pytest.approx(0.0, abs=1e-9)


def test_skew_report_surfaces_in_batch_report():
    eng = _run_engine(n_batches=4)
    rep = eng.reports[-1]
    assert rep.obs is not None
    assert rep.obs["skew"]["total_reducers"] == eng.plan.total_reducers
    assert set(rep.obs) == {"skew"}
    # the metrics stay in the engine's registry, not in every report
    assert eng.obs.metrics.snapshot()["counters"]["stream_batches_total"] == 4
    # drift decision surfaces trigger + observed/threshold on the report
    replanned = [r for r in eng.reports if r.replanned and r.batch > 0]
    for r in replanned:
        assert r.drift_trigger in {"overload", "comm", "faded_pin"}
        assert r.drift_observed > r.drift_threshold > 0.0


# ---- leaf spans, parent ids, counts and the profiler mirror ----------------

# every leaf span and the spans it may open inside (None: a root span)
LEAF_PARENTS = {
    "sketch.cms": {"sketch.update"},
    "sketch.candidates": {"sketch.update"},
    "sketch.snapshot": {"ingest"},
    "report": {"ingest"},
    "obs.skewscope": {"ingest"},
    "join.probe": {"join.delta"},
    "join.index_append": {"join.delta"},
    "join.scatter": {"join.delta", "replan.migrate"},
    "retention.retract": {"retention.expire"},
    "retention.compact": {"retention.expire"},
    "route.fetch": {"route.fused", "replan.compile", "replan.migrate", "route"},
}


def _fused_engine(n_batches=5, policy=ObsPolicy(trace=True, skewscope=True)):
    rng = np.random.default_rng(5)
    eng = StreamingJoinEngine(
        two_way(),
        StreamConfig(
            q=60, decay=0.5, load_factor=2.0, fused_ingest=True, obs=policy,
            retention=RetentionPolicy(window_batches=2),
        ),
    )
    batches = [_zipf_batch(rng, n_r=300, n_s=80) for _ in range(n_batches)]
    for b in batches:
        eng.ingest(b)
    return eng, batches


def test_parent_ids_and_counts_reach_the_events():
    tr = Tracer(enabled=True)
    tr.set_batch(3)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            inner.set(h2d_bytes=10)
            inner.set(d2h_bytes=20)
        tr.instant("mark")
        outer.set(rows=5)
    by_name = {e["name"]: e["args"] for e in tr.events}
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["parent"] == by_name["outer"]["span_id"] == "3:1"
    assert by_name["mark"]["parent"] == "3:1"
    assert (by_name["inner"]["h2d_bytes"], by_name["inner"]["d2h_bytes"]) == (10, 20)
    assert by_name["outer"]["rows"] == 5


def test_null_span_set_allocates_nothing():
    tr = Tracer(enabled=False)

    def plain():
        with tr.span("route.fused"):
            pass

    def counted():
        with tr.span("route.fused") as span:
            span.set(h2d_bytes=1, d2h_bytes=2)

    peaks = []
    for hot in (plain, counted):
        hot()
        tracemalloc.start()
        for _ in range(1000):
            hot()
        current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        peaks.append(peak)
        assert current < 256  # nothing accumulates across a thousand calls
    assert peaks[1] == peaks[0]
    assert NULL_SPAN.__slots__ == ()


def test_fused_stream_emits_every_leaf_span_inside_its_parent():
    eng, _ = _fused_engine()
    events = [e for e in eng.obs.tracer.events if e["ph"] == "X"]
    names = {e["args"]["span_id"]: e["name"] for e in events}
    seen = set()
    for e in events:
        if e["name"] in LEAF_PARENTS:
            parent = names.get(e["args"]["parent"])
            assert parent in LEAF_PARENTS[e["name"]], (e["name"], parent)
            seen.add(e["name"])
    assert seen == set(LEAF_PARENTS)
    # one root ingest per batch, and every other span's parent is known
    assert [e["args"]["batch"] for e in events if e["name"] == "ingest"] == list(range(5))
    for e in events:
        assert e["args"]["parent"] is None or e["args"]["parent"] in names


def test_route_fused_counts_the_padded_outputs_it_fetches():
    eng, batches = _fused_engine()
    assert not any(r.replanned for r in eng.reports[1:])  # one plan throughout
    last = [
        e for e in eng.obs.tracer.events
        if e["name"] == "route.fused" and e["args"]["batch"] == len(batches) - 1
    ]
    assert len(last) == 1
    h2d = d2h = 0
    for rel in eng.query.relations:
        rows = batches[-1][rel.name].astype(np.int32)
        enc, _, k_pad = eng._dense_routes(rel, static_route_table(eng.plan, rel))
        outs = fused_ingest_dense(
            jnp.asarray(rows), enc,
            sketch_cols=tuple(c for _, c in eng._sketch_cols[rel.name]),
            seeds=eng.tracker.seeds, width=eng.config.sketch_width, k_pad=k_pad,
            block=eng.config.fused_block, double_buffer=eng.config.fused_double_buffer,
        )
        h2d += rows.nbytes + sum(a.nbytes for a in enc.values())
        d2h += sum(np.asarray(o).nbytes for o in outs)
    assert last[0]["args"]["h2d_bytes"] == h2d
    assert last[0]["args"]["d2h_bytes"] == d2h
    # padded: more than the real emissions' 8 B (id + rank) each
    assert d2h > 8 * sum(eng.reports[-1].comm_tuples.values())


def test_spans_appear_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, _ = _fused_engine(n_batches=3)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True))[-1]
    mirrored, other = [], set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs/"):
                    mirrored.append((e.name, dict(e.stats)))
                else:
                    other.add(e.name)
    names = {n for n, _ in mirrored}
    assert {f"obs/{n}" for n in eng.obs.tracer.span_names()} <= names
    assert {"obs/ingest", "obs/sketch.candidates", "obs/route.fetch"} <= names
    for name, stats in mirrored:
        assert stats["batch"] in range(3), (name, stats)
        assert isinstance(stats["span_id"], str) and stats["span_id"].startswith(f"{stats['batch']}:")
    # the mirror never takes the names a caller's own annotations use
    assert not {"ingest", "window"} & other

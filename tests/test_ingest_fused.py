"""Fused ingest pipeline (DESIGN.md §7): kernel vs oracle, fused engine vs
baseline engine — all comparisons bit-for-bit.

The fused path is an *optimization*, never a semantic: every test here
asserts exact integer equality against the unfused implementation that
remains in the tree (``kernels.ref.fused_ingest_ref`` at kernel level,
``StreamConfig(fused_ingest=False)`` at engine level).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import plan_shares_skew, two_way, three_way_paper
from repro.kernels import fused_ingest
from repro.kernels.ingest_fused import fused_ingest_pallas, overlap_profile
from repro.kernels.ref import fused_ingest_ref
from repro.mapreduce.keys import map_phase, static_route_table
from repro.mapreduce.local_join import (
    LocalJoinSpec,
    local_join_count_checksum,
)
from repro.stream import StreamConfig, StreamingJoinEngine
from repro.stream.delta import SortedDeltaIndex


def _zipf_batch(rng, shift, n_r=400, n_s=150, domain=2000, a=1.6):
    b_r = ((rng.zipf(a, n_r) - 1) + shift) % domain
    b_s = ((rng.zipf(a, n_s) - 1) + shift) % domain
    r = np.stack([rng.integers(0, domain, n_r), b_r], 1).astype(np.int64)
    s = np.stack([b_s, rng.integers(0, domain, n_s)], 1).astype(np.int64)
    return {"R": r, "S": s}


def _skewed_plan(query, rng, q=60):
    """A plan with pinned heavy hitters so pins/excludes are exercised."""
    data = {
        r.name: rng.integers(0, 50, size=(600, r.arity)).astype(np.int64)
        for r in query.relations
    }
    # make one value heavy on the first shared column of each relation
    for r in query.relations:
        data[r.name][: 300, -1] = 7
    return plan_shares_skew(query, data, q=q)


# ------------------------------------------------------------- kernel parity
@pytest.mark.parametrize("n", [1, 7, 257, 1000])
@pytest.mark.parametrize("double_buffer", [False, True])
def test_kernel_matches_ref_two_way(n, double_buffer):
    rng = np.random.default_rng(n + double_buffer)
    query = two_way()
    plan = _skewed_plan(query, rng)
    rel = query.relations[0]
    routes = static_route_table(plan, rel)
    rows = jnp.asarray(
        rng.integers(0, 60, size=(n, rel.arity)).astype(np.int32)
    )
    seeds = (11, 222, 3333)
    got = fused_ingest_pallas(
        rows,
        routes=routes,
        sketch_cols=(1,),
        seeds=seeds,
        width=256,
        num_reducers=plan.total_reducers,
        double_buffer=double_buffer,
        interpret=True,
    )
    want = fused_ingest_ref(
        rows,
        routes=routes,
        sketch_cols=(1,),
        seeds=seeds,
        width=256,
        num_reducers=plan.total_reducers,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("double_buffer", [False, True])
def test_kernel_matches_ref_three_way(double_buffer):
    rng = np.random.default_rng(3)
    query = three_way_paper()
    plan = _skewed_plan(query, rng)
    for rel in query.relations:
        routes = static_route_table(plan, rel)
        rows = jnp.asarray(
            rng.integers(0, 60, size=(333, rel.arity)).astype(np.int32)
        )
        got = fused_ingest_pallas(
            rows,
            routes=routes,
            num_reducers=plan.total_reducers,
            double_buffer=double_buffer,
            interpret=True,
        )
        want = fused_ingest_ref(
            rows, routes=routes, num_reducers=plan.total_reducers
        )
        for g, w in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_kernel_dest_matches_map_phase():
    """The kernel's destination block IS the map phase, column layout and
    all — the property the engine's emission ordering relies on."""
    rng = np.random.default_rng(9)
    query = two_way()
    plan = _skewed_plan(query, rng)
    for rel in query.relations:
        rows = jnp.asarray(
            rng.integers(0, 60, size=(500, rel.arity)).astype(np.int32)
        )
        dest, _, _, _ = fused_ingest(
            rows,
            routes=static_route_table(plan, rel),
            num_reducers=plan.total_reducers,
        )
        np.testing.assert_array_equal(
            np.asarray(dest), np.asarray(map_phase(plan, rel, rows))
        )


def test_kernel_sketch_only_and_route_only_modes():
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(0, 1000, size=(300, 2)).astype(np.int32))
    seeds = (5, 55)
    # sketch-only: no routes -> dest/rank/counts are None
    dest, rank, counts, cms = fused_ingest(
        rows, sketch_cols=(0, 1), seeds=seeds, width=128
    )
    assert dest is None and rank is None and counts is None
    _, _, _, cms_ref = fused_ingest_ref(
        rows, sketch_cols=(0, 1), seeds=seeds, width=128
    )
    np.testing.assert_array_equal(np.asarray(cms), np.asarray(cms_ref))
    # route-only: no sketch_cols -> cms is None
    query = two_way()
    plan = plan_shares_skew(
        query, {"R": np.asarray(rows), "S": np.asarray(rows)}, q=60
    )
    routes = static_route_table(plan, query.relations[0])
    _, _, _, cms2 = fused_ingest(
        rows, routes=routes, num_reducers=plan.total_reducers
    )
    assert cms2 is None


def test_kernel_counts_are_destination_histogram():
    rng = np.random.default_rng(2)
    query = two_way()
    plan = _skewed_plan(query, rng)
    rel = query.relations[0]
    rows = jnp.asarray(rng.integers(0, 60, size=(700, 2)).astype(np.int32))
    dest, rank, counts, _ = fused_ingest(
        rows,
        routes=static_route_table(plan, rel),
        num_reducers=plan.total_reducers,
    )
    flat = np.asarray(dest).reshape(-1)
    want = np.bincount(flat[flat >= 0], minlength=plan.total_reducers)
    np.testing.assert_array_equal(np.asarray(counts), want)
    # ranks are a permutation of 0..count-1 within each destination
    rk = np.asarray(rank).reshape(-1)
    for d in np.unique(flat[flat >= 0]):
        got = np.sort(rk[flat == d])
        np.testing.assert_array_equal(got, np.arange(got.size))


def test_overlap_profile_roofline_sanity():
    p = overlap_profile(
        n_rows=1500, arity=2, route_w=8, num_reducers=32,
        n_sketch_cols=1, depth=4, width=2048, device_kind="TPU v5 lite",
    )
    assert p["bound"] in ("dma", "compute")
    assert p["overlapped_us"] <= p["serial_us"]
    assert 1.0 <= p["overlap_speedup"] <= 2.0
    assert p["bytes_in"] > 0 and p["vpu_ops"] > 0


def test_overlap_profile_refuses_unknown_device_kind():
    """The peaks table has no default: a kind it does not list raises."""
    with pytest.raises(ValueError, match="no peak figures"):
        overlap_profile(
            n_rows=1500, arity=2, route_w=8, num_reducers=32,
            n_sketch_cols=1, depth=4, width=2048, device_kind="cpu",
        )


# ------------------------------------------------- sorted delta index parity
def test_sorted_delta_index_matches_einsum_term():
    """probe() reproduces one einsum telescoping term bit-for-bit."""
    rng = np.random.default_rng(0)
    spec = LocalJoinSpec.from_query(two_way())
    assert SortedDeltaIndex.eligible(spec)
    k, cap_l, cap_r = 13, 64, 32
    for trial in range(5):
        def emissions(n):
            dest = rng.integers(0, k, size=n).astype(np.int32)
            rows = rng.integers(0, 30, size=(n, 2)).astype(np.int32)
            return dest, rows

        dl, rl = emissions(500)
        dr, rr = emissions(200)
        idx = SortedDeltaIndex(spec)
        idx.rebuild("R", dl, rl)
        cnt, chk = idx.probe("R", "S", dr, rr)

        # einsum reference over the same emissions, binned
        def to_bins(dest, rows, cap):
            bins = np.zeros((k, cap, 2), np.int32)
            valid = np.zeros((k, cap), bool)
            order = np.argsort(dest, kind="stable")
            ds, rs = dest[order], rows[order]
            first = np.searchsorted(ds, ds, side="left")
            rank = np.arange(ds.size) - first
            bins[ds, rank] = rs
            valid[ds, rank] = True
            return jnp.asarray(bins), jnp.asarray(valid)

        bl, vl = to_bins(dl, rl, cap_l)
        br, vr = to_bins(dr, rr, cap_r)
        want_cnt, want_chk = local_join_count_checksum(
            spec, {"R": bl, "S": br}, {"R": vl, "S": vr}
        )
        assert (cnt, chk) == (int(want_cnt), int(want_chk))


def test_sorted_delta_index_append_equals_rebuild():
    rng = np.random.default_rng(5)
    spec = LocalJoinSpec.from_query(two_way())
    idx_a = SortedDeltaIndex(spec)
    idx_b = SortedDeltaIndex(spec)
    dests, rowss = [], []
    for _ in range(4):
        dest = rng.integers(0, 9, size=120).astype(np.int32)
        rows = rng.integers(0, 40, size=(120, 2)).astype(np.int32)
        dests.append(dest)
        rowss.append(rows)
        idx_a.append("R", dest, rows)
    idx_b.rebuild("R", np.concatenate(dests), np.concatenate(rowss))
    np.testing.assert_array_equal(
        idx_a._keys_by_rel["R"], idx_b._keys_by_rel["R"]
    )
    # weights may be permuted within equal keys, but group sums (the only
    # thing probe reads) must match; keys equal => same group boundaries
    pd = rng.integers(0, 9, size=60).astype(np.int32)
    pr = rng.integers(0, 40, size=(60, 2)).astype(np.int32)
    assert idx_a.probe("R", "S", pd, pr) == idx_b.probe("R", "S", pd, pr)


def test_sorted_delta_index_rejects_multiway():
    spec = LocalJoinSpec.from_query(three_way_paper())
    assert not SortedDeltaIndex.eligible(spec)
    with pytest.raises(ValueError):
        SortedDeltaIndex(spec)


# ----------------------------------------------------------- engine parity
def _run_pair(query, batches, **cfg_kw):
    cfg = dict(q=60, decay=0.5, load_factor=2.0)
    cfg.update(cfg_kw)
    base = StreamingJoinEngine(query, StreamConfig(**cfg))
    fused = StreamingJoinEngine(
        query, StreamConfig(fused_ingest=True, **cfg)
    )
    reports = []
    for batch in batches:
        rb = base.ingest(batch)
        rf = fused.ingest(batch)
        reports.append((rb, rf))
    return base, fused, reports


def test_engine_fused_parity_on_drifting_zipf():
    """The headline invariant: fused ingest is bit-identical to the
    baseline — per-batch reports, Count-Min tables, and the packed
    per-reducer buffers — across drift, replans, and migration."""
    rng = np.random.default_rng(0)
    batches = [
        _zipf_batch(rng, shift=0 if i < 3 else 900, a=2.0 if i < 3 else 1.4)
        for i in range(6)
    ]
    base, fused, reports = _run_pair(two_way(), batches)
    assert any(rb.replanned for rb, _ in reports[1:]), "stream must drift"
    for i, (rb, rf) in enumerate(reports):
        assert rb == rf, f"batch {i} reports diverge"
    # packed per-reducer buffers: same bins, validity, occupancy
    for nm in ("R", "S"):
        b0, v0, o0 = base._state[nm]
        b1, v1, o1 = fused._state[nm]
        np.testing.assert_array_equal(o0, o1)
        np.testing.assert_array_equal(v0, v1)
        np.testing.assert_array_equal(b0, b1)
    # sketch tables: bit-for-bit (integer increments exact in float64)
    for key in base.tracker._cms:
        np.testing.assert_array_equal(
            base.tracker._cms[key].table, fused.tracker._cms[key].table
        )
    assert base.tracker._ss.keys() == fused.tracker._ss.keys()
    for a in base.tracker._ss:
        assert base.tracker._ss[a].counts == fused.tracker._ss[a].counts
    assert fused.fused_batches == len(batches), "fused path silently skipped"


def test_engine_fused_parity_three_way():
    """n-way queries keep the einsum delta path under fused routing; the
    cumulative fingerprint still matches the baseline exactly."""
    rng = np.random.default_rng(1)
    query = three_way_paper()
    batches = []
    for i in range(3):
        b = ((rng.zipf(1.6, 250) - 1) + (0 if i < 2 else 400)) % 1000
        c = rng.integers(0, 1000, 250)
        batches.append(
            {
                "R": np.stack([rng.integers(0, 1000, 250), b], 1),
                "S": np.stack([b, rng.integers(0, 1000, 250), c], 1),
                "T": np.stack([c, rng.integers(0, 1000, 250)], 1),
            }
        )
    base, fused, reports = _run_pair(query, batches, q=40)
    for i, (rb, rf) in enumerate(reports):
        assert rb == rf, f"batch {i} reports diverge"
    assert fused.fused_batches == len(batches)


def test_engine_fused_empty_and_lopsided_batches():
    rng = np.random.default_rng(2)
    full = _zipf_batch(rng, 0)
    empty = {"R": np.empty((0, 2), np.int64), "S": np.empty((0, 2), np.int64)}
    lopsided = {"R": full["R"], "S": np.empty((0, 2), np.int64)}
    base, fused, reports = _run_pair(two_way(), [full, empty, lopsided])
    for i, (rb, rf) in enumerate(reports):
        assert rb == rf, f"batch {i} reports diverge"
    assert fused.fused_batches == 3


def test_property_total_comm_invariant_under_fusion():
    """Property sweep (seeded, no external dependency): across random
    stream shapes, drift points, and engine knobs, fusion never changes
    ``BatchReport.total_comm`` — the shuffle volume the paper's cost model
    optimizes is untouched by how fast the pass runs."""
    rng = np.random.default_rng(1234)
    for trial in range(4):
        n_r = int(rng.integers(50, 400))
        n_s = int(rng.integers(20, 200))
        domain = int(rng.integers(200, 3000))
        a = float(rng.uniform(1.3, 2.2))
        shift = int(rng.integers(0, domain))
        n_batches = int(rng.integers(2, 5))
        q = float(rng.choice([30, 60, 120]))
        batches = [
            _zipf_batch(
                rng,
                shift=0 if i < n_batches // 2 else shift,
                n_r=n_r,
                n_s=n_s,
                domain=domain,
                a=a,
            )
            for i in range(n_batches)
        ]
        base, fused, reports = _run_pair(two_way(), batches, q=q)
        for i, (rb, rf) in enumerate(reports):
            assert rb.total_comm == rf.total_comm, (
                f"trial {trial} batch {i}: comm diverged "
                f"({rb.total_comm} != {rf.total_comm})"
            )
            assert rb.comm_tuples == rf.comm_tuples


# ------------------------------------------- dense (dynamic-operand) routes
@pytest.mark.parametrize("double_buffer", [False, True])
def test_dense_kernel_matches_static_variant(double_buffer):
    """The dense route encoding is bit-identical to the static-route
    kernel on dest/rank/counts/cms, including pins and excludes."""
    from repro.kernels.ingest_fused import (
        dense_route_encoding,
        fused_ingest_dense_pallas,
        route_width,
    )

    rng = np.random.default_rng(17)
    for query in (two_way(), three_way_paper()):
        plan = _skewed_plan(query, rng)
        seeds = (11, 222, 3333)
        for rel in query.relations:
            routes = static_route_table(plan, rel)
            n = 311
            rows = jnp.asarray(
                rng.integers(0, 60, size=(n, rel.arity)).astype(np.int32)
            )
            d1, r1, c1, m1 = fused_ingest_pallas(
                rows, routes=routes, sketch_cols=(rel.arity - 1,),
                seeds=seeds, width=128,
                num_reducers=plan.total_reducers,
                block=128, double_buffer=double_buffer,
            )
            w = route_width(routes)
            wp = 1 << max(0, int(w - 1).bit_length())
            k_pad = max(-(-plan.total_reducers // 128) * 128, 128)
            enc = dense_route_encoding(routes, rel.arity, wp, max_values=8)
            d2, r2, c2, m2 = fused_ingest_dense_pallas(
                rows, enc, sketch_cols=(rel.arity - 1,),
                seeds=seeds, width=128, k_pad=k_pad,
                block=128, double_buffer=double_buffer,
            )
            np.testing.assert_array_equal(d1, np.asarray(d2)[:n, :w])
            np.testing.assert_array_equal(r1, np.asarray(r2)[:n, :w])
            np.testing.assert_array_equal(
                c1, np.asarray(c2)[: plan.total_reducers]
            )
            np.testing.assert_array_equal(m1, m2)


def test_dense_kernel_reuses_executable_across_replans():
    """The whole point of the dense encoding: two DIFFERENT route tables
    whose padded shapes agree must hit ONE compiled executable (the
    static-route kernel recompiles per plan — the replan ingest spike)."""
    from repro.kernels.ingest_fused import (
        dense_route_encoding,
        route_width,
    )
    from repro.kernels.ops import fused_ingest_dense

    rng = np.random.default_rng(23)
    query = two_way()
    rel = query.relations[0]
    plans = []
    for hot in (7, 31):
        data = {
            r.name: rng.integers(0, 50, size=(600, r.arity)).astype(np.int64)
            for r in query.relations
        }
        for r in query.relations:
            data[r.name][:300, -1] = hot
        plans.append(plan_shares_skew(query, data, q=60))
    tables = [static_route_table(p, rel) for p in plans]
    assert tables[0] != tables[1], "need genuinely different route tables"
    wp = max(
        1 << max(0, int(route_width(t) - 1).bit_length()) for t in tables
    )
    rows = jnp.asarray(
        rng.integers(0, 60, size=(200, rel.arity)).astype(np.int32)
    )
    before = fused_ingest_dense._cache_size()
    for t in tables:
        enc = dense_route_encoding(t, rel.arity, wp, max_values=8)
        fused_ingest_dense(
            rows, enc, sketch_cols=(1,), seeds=(11, 22), width=128,
            k_pad=128, block=128, double_buffer=False,
        )[0].block_until_ready()
    assert fused_ingest_dense._cache_size() - before <= 1, (
        "a second route table with identical padded shapes recompiled"
    )


def test_engine_dynamic_routes_bit_identical_to_static():
    """StreamConfig(fused_dynamic_routes=True) — the default — must be
    bit-identical to the static-route fused engine across drift/replans."""
    rng = np.random.default_rng(29)
    batches = [
        _zipf_batch(rng, shift=0 if i < 3 else 900, a=2.0 if i < 3 else 1.4)
        for i in range(6)
    ]
    cfg = dict(q=60, decay=0.5, load_factor=2.0, fused_ingest=True)
    static = StreamingJoinEngine(
        two_way(), StreamConfig(fused_dynamic_routes=False, **cfg)
    )
    dyn = StreamingJoinEngine(
        two_way(), StreamConfig(fused_dynamic_routes=True, **cfg)
    )
    for i, batch in enumerate(batches):
        rs = static.ingest(batch)
        rd = dyn.ingest(batch)
        assert rs == rd, f"batch {i} reports diverge"
    assert any(r.replanned for r in dyn.reports[1:]), "stream must drift"
    for nm in ("R", "S"):
        for a, b in zip(static._state[nm], dyn._state[nm]):
            np.testing.assert_array_equal(a, b)
    for key in static.tracker._cms:
        np.testing.assert_array_equal(
            static.tracker._cms[key].table, dyn.tracker._cms[key].table
        )

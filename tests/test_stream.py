"""Streaming subsystem tests: sketches, drift monitor, micro-batch engine.

The load-bearing invariant: after any prefix of micro-batches — through
heavy-hitter drift, replans, and state migration — the engine's cumulative
(count, checksum) equals the batch pipeline on the concatenated input.
"""
import numpy as np
import pytest

from repro.core import plan_with_hh, three_way_paper, two_way
from repro.core.heavy_hitters import CountMinSketch, exact_heavy_hitters
from repro.data import paper_2way, paper_3way
from repro.mapreduce import oracle_join, run_join
from repro.stream import (
    DecayingCountMin,
    DriftMonitor,
    SpaceSaving,
    StreamConfig,
    StreamHHTracker,
    StreamingJoinEngine,
)


def _zipf_batch(rng, shift, n_r=1200, n_s=300, domain=3000, a=1.6):
    """2-way batch whose Zipf-heavy B values sit at ``shift`` (mod domain)."""
    b_r = ((rng.zipf(a, n_r) - 1) + shift) % domain
    b_s = ((rng.zipf(a, n_s) - 1) + shift) % domain
    r = np.stack([rng.integers(0, domain, n_r), b_r], 1).astype(np.int64)
    s = np.stack([b_s, rng.integers(0, domain, n_s)], 1).astype(np.int64)
    return {"R": r, "S": s}


# --------------------------------------------------------- CountMinSketch
def test_cms_merge_associative():
    rng = np.random.default_rng(0)
    keys = [rng.integers(0, 10_000, size=2_000) for _ in range(3)]
    sketches = []
    for k in keys:
        s = CountMinSketch(width=512, depth=4, seed=7)
        s.update(k)
        sketches.append(s)
    a, b, c = sketches
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    np.testing.assert_array_equal(left.table, right.table)
    assert left.total == right.total == sum(k.size for k in keys)
    # merged == single sketch over the concatenation
    whole = CountMinSketch(width=512, depth=4, seed=7)
    whole.update(np.concatenate(keys))
    np.testing.assert_array_equal(left.table, whole.table)


def test_cms_merge_rejects_mismatched_seeds():
    a = CountMinSketch(width=64, depth=3, seed=0)
    b = CountMinSketch(width=64, depth=3, seed=1)
    with pytest.raises(ValueError):
        a.merge(b)


def test_cms_overcount_bound():
    """Estimates never undercount, and err <= eps*N with prob >= 1-delta.

    width/depth from ``from_error``; failure probability per query is
    delta = exp(-depth), so over m queries expect <= m*delta violations —
    with the seeds fixed here there are none.
    """
    eps, delta = 0.01, 0.01
    cms = CountMinSketch.from_error(eps, delta, seed=3)
    assert cms.width >= int(np.e / eps)
    rng = np.random.default_rng(4)
    keys = (rng.zipf(1.4, size=50_000) - 1) % 5_000
    cms.update(keys)
    vals, counts = np.unique(keys, return_counts=True)
    est = cms.estimate(vals)
    assert np.all(est >= counts), "count-min must never undercount"
    violations = np.sum(est - counts > eps * keys.size)
    assert violations <= max(1, int(delta * vals.size))


def test_cms_heavy_hitters_agree_with_exact_on_zipf():
    rng = np.random.default_rng(5)
    col = (rng.zipf(1.5, size=30_000) - 1) % 10_000
    threshold = 300
    exact_vals, _ = exact_heavy_hitters(col, threshold)
    cms = CountMinSketch(width=8192, depth=5, seed=1)
    cms.update(col)
    got_vals, got_counts = cms.heavy_hitters(np.unique(col), threshold)
    # CMS overcounts, so its HH set is a superset of the exact set...
    assert set(exact_vals.tolist()) <= set(got_vals.tolist())
    # ...and with a wide sketch the sets coincide
    assert set(got_vals.tolist()) == set(exact_vals.tolist())
    # estimated counts upper-bound the true ones
    true = {v: c for v, c in zip(*np.unique(col, return_counts=True))}
    for v, c in zip(got_vals.tolist(), got_counts.tolist()):
        assert c >= true[v]


# ------------------------------------------------------- decaying sketches
def test_decaying_cms_matches_kernel_and_forgets():
    rng = np.random.default_rng(6)
    cms = DecayingCountMin(width=256, depth=4, seed=2, decay=0.5)
    batch1 = rng.integers(0, 1000, size=500)
    cms.step()
    cms.update(batch1)
    est1 = float(cms.estimate(np.array([batch1[0]]))[0])
    assert est1 >= 1
    # ten empty batches: counts decay toward zero
    for _ in range(10):
        cms.step()
    est2 = float(cms.estimate(np.array([batch1[0]]))[0])
    assert est2 <= est1 / 500


def test_decaying_cms_absorb_matches_update():
    import jax.numpy as jnp

    from repro.kernels import cms_update

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 5000, size=1111).astype(np.int64)
    host = DecayingCountMin(width=512, depth=3, seed=9, decay=1.0)
    host.update(keys)
    dev = DecayingCountMin(width=512, depth=3, seed=9, decay=1.0)
    delta = np.asarray(cms_update(jnp.asarray(keys, jnp.int32), dev.seeds, dev.width))
    dev.absorb(delta.astype(np.float64), keys.size)
    np.testing.assert_array_equal(host.table, dev.table)


def test_space_saving_retains_heavy_values():
    rng = np.random.default_rng(8)
    stream = (rng.zipf(1.3, size=20_000) - 1) % 2_000
    ss = SpaceSaving(capacity=32)
    ss.update(stream)
    vals, counts = np.unique(stream, return_counts=True)
    guaranteed = vals[counts > stream.size / 32]
    got, est = ss.candidates()
    assert set(guaranteed.tolist()) <= set(got.tolist())
    true = dict(zip(vals.tolist(), counts.tolist()))
    for v, c in zip(got.tolist(), est.tolist()):
        assert c >= true.get(v, 0)  # overestimates only


def _per_value_update(ss, keys):
    """``SpaceSaving.update`` as it was before equal-count runs were folded
    together: one O(capacity) victim scan per newcomer.  The oracle."""
    keys = np.asarray(keys, dtype=np.int64)
    if keys.size == 0:
        return
    vals, cnts = np.unique(keys, return_counts=True)
    # largest first so evictions never displace a bigger newcomer
    order = np.argsort(-cnts, kind="stable")
    for v, c in zip(vals[order].tolist(), cnts[order].tolist()):
        if v in ss.counts:
            ss.counts[v] += c
        elif len(ss.counts) < ss.capacity:
            ss.counts[v] = float(c)
            ss.errors[v] = 0.0
        else:
            victim = min(ss.counts, key=ss.counts.__getitem__)
            floor = ss.counts.pop(victim)
            ss.errors.pop(victim)
            ss.counts[v] = floor + c
            ss.errors[v] = floor


def _assert_same_summary(got, want):
    assert list(got.counts.items()) == list(want.counts.items())
    assert list(got.errors.items()) == list(want.errors.items())
    for g, w in zip(got.candidates(), want.candidates()):
        np.testing.assert_array_equal(g, w)


def _paper_hh_column(rng, n, domain=1 << 16):
    return np.where(rng.random(n) < 0.1, 7, rng.integers(0, domain, n))


def _ss_stream(case):
    """(capacity, decay, state to start from or None, batches) per case."""
    rng = np.random.default_rng(31)
    if case == "not_full":  # fills 64 counters mid-run, then evicts
        return 64, 0.5, None, [rng.integers(0, 1000, 40), np.arange(5000, 5200)]
    if case == "capacity_1":
        return 1, 0.5, None, [rng.integers(0, 300, 400) for _ in range(4)]
    if case == "equal_counts":
        return 16, 0.5, None, [np.repeat(rng.permutation(800)[:300], 3)] * 3
    if case == "monitored_at_run_start":
        # 5 goes before its turn (1 evicts it), 35 is still there at its turn
        start = {
            "values": np.array([5, 35, 900, 901]),
            "counts": np.array([0.5, 1000.0, 0.25, 0.75]),
            "errors": np.array([0.0, 3.0, 0.0, 0.5]),
        }
        return 4, 1.0, start, [np.arange(64)]
    if case == "tied_with_the_rest":
        # 3 and 4 cycle up one a newcomer; after 32 newcomers 20 ties the
        # pushed 20 and, being older, goes first: 102 is the 34th's victim
        start = {
            "values": np.array([100, 101, 102]),
            "counts": np.array([3.0, 4.0, 20.0]),
            "errors": np.zeros(3),
        }
        return 3, 1.0, start, [np.arange(34)]
    if case == "decay_rounding":  # 64 batches, counts near 1e5 at decay 0.5
        heavy = np.repeat([11, 12, 13], [50_000, 30_000, 20_000])
        return 32, 0.5, None, [
            np.concatenate([heavy, rng.integers(0, 1 << 16, 1500)]) for _ in range(64)
        ]
    if case == "paper_hh_shaped":  # 10 % on one value, the rest over 2^16
        return 64, 0.5, None, [
            _paper_hh_column(rng, n) for _ in range(6) for n in (12_500, 1_250)
        ]
    if case == "empty_batch":
        return 8, 0.5, None, [
            rng.integers(0, 5000, 300), np.empty(0, np.int64), rng.integers(0, 5000, 300)
        ]
    raise ValueError(case)


SS_CASES = [
    "not_full", "capacity_1", "equal_counts", "monitored_at_run_start",
    "tied_with_the_rest", "decay_rounding", "paper_hh_shaped", "empty_batch",
]


@pytest.mark.parametrize("case", SS_CASES)
def test_space_saving_update_is_bit_identical_to_the_per_value_loop(case):
    capacity, decay, start, batches = _ss_stream(case)
    got, want = SpaceSaving(capacity), SpaceSaving(capacity)
    if start is not None:
        got.load_state_dict(start)
        want.load_state_dict(start)
    run_evictions = 0
    for keys in batches:
        got.decay(decay)
        want.decay(decay)
        distinct, evictions, on_run_path = got.update(keys)
        _per_value_update(want, keys)
        _assert_same_summary(got, want)
        assert distinct == np.unique(keys).size
        assert on_run_path <= evictions <= distinct
        run_evictions += on_run_path
    assert run_evictions > 0  # every case takes the run path somewhere
    if case == "monitored_at_run_start":
        assert got.counts[35] == 1001.0
    if case == "tied_with_the_rest":
        assert 102 not in got.counts


def test_space_saving_checkpoint_mid_stream_resumes_bit_identically():
    rng = np.random.default_rng(32)
    batches = [_paper_hh_column(rng, 6_000) for _ in range(8)]
    straight, resumed = SpaceSaving(64), None
    for i, keys in enumerate(batches):
        if i == 4:
            resumed = SpaceSaving(64)
            resumed.load_state_dict(straight.state_dict())
        for ss in (straight, resumed) if resumed else (straight,):
            ss.decay(0.5)
            ss.update(keys)
        if resumed:
            _assert_same_summary(resumed, straight)


def test_tracker_follows_drift():
    rng = np.random.default_rng(9)
    tracker = StreamHHTracker(two_way(), decay=0.5, seed=0)
    for _ in range(4):
        tracker.observe(_zipf_batch(rng, 0))
    hh0 = set(tracker.hh_values(threshold=100).get("B", ()).tolist())
    assert 0 in hh0  # zipf mode at shift 0
    for _ in range(4):
        tracker.observe(_zipf_batch(rng, 1000))
    hh1 = tracker.hh_values(threshold=100)["B"].tolist()
    assert 1000 in hh1  # the new mode took over
    assert 1000 == hh1[0]  # and leads by rate


# ------------------------------------------------------------ drift monitor
def test_drift_monitor_fires_on_unpinned_heavy_value():
    rng = np.random.default_rng(10)
    batch0 = _zipf_batch(rng, 0)
    tracker = StreamHHTracker(two_way(), decay=0.5)
    tracker.observe(batch0)
    snap = tracker.snapshot(threshold=100)
    plan = plan_with_hh(two_way(), batch0, 120, {a: s.values for a, s in snap.items()})
    mon = DriftMonitor(q=120, load_factor=2.0, cooldown=0)
    mon.install(plan, two_way(), batch0)
    # same distribution: no drift
    batch1 = _zipf_batch(rng, 0)
    tracker.observe(batch1)
    d = mon.check(plan, two_way(), batch1, tracker.snapshot(threshold=100))
    assert not d.replan
    # shifted distribution: the new mode is unpinned -> overload predicted
    for _ in range(3):
        shifted = _zipf_batch(rng, 1500)
        tracker.observe(shifted)
    d = mon.check(plan, two_way(), shifted, tracker.snapshot(threshold=100))
    assert d.replan and "overload" in d.reason


def test_drift_monitor_fires_on_faded_pin():
    """A pinned HH whose live rate collapsed triggers wasted-replication
    drift even though neither overload nor comm-increase fires."""
    rng = np.random.default_rng(17)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=120, decay=0.5, load_factor=3.0))
    for _ in range(2):
        eng.ingest(_zipf_batch(rng, 0, a=1.8))  # pins the zipf mode
    assert eng.plan.hh_values  # something got pinned
    uniform = lambda: {
        "R": rng.integers(0, 3000, (1200, 2)).astype(np.int64),
        "S": rng.integers(0, 3000, (300, 2)).astype(np.int64),
    }
    for _ in range(4):  # skew vanishes entirely
        eng.ingest(uniform())
    assert any("faded pin" in r.drift_reason for r in eng.reports if r.replanned)
    count, checksum, _, _ = oracle_join(q, eng.history_data())
    assert (eng.total_count, eng.total_checksum) == (count, checksum)


def test_plan_with_hh_trims_rich_hh_set_instead_of_raising():
    from repro.core import make_query

    query = make_query(
        {"R": ("A", "B"), "S": ("B", "C"), "T": ("C", "D"), "U": ("D", "A")}
    )
    rng = np.random.default_rng(18)
    data = {
        r.name: rng.integers(0, 100, (200, 2)).astype(np.int64)
        for r in query.relations
    }
    hh = {a: np.arange(8, dtype=np.int64) for a in ("A", "B", "C", "D")}
    plan = plan_with_hh(query, data, q=100, hh_values=hh)  # 9^4 combos untrimmed
    assert 0 < len(plan.residuals) <= 1024


# ---------------------------------------------------------------- engine
def test_engine_matches_oracle_static_stream():
    rng = np.random.default_rng(11)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=150))
    for _ in range(4):
        rep = eng.ingest(paper_2way(rng, n_r=800, n_s=200, domain=1200))
        # prefix invariant: cumulative totals match the concatenated input
        count, checksum, _, _ = oracle_join(q, eng.history_data())
        assert (rep.total_count, rep.total_checksum) == (count, checksum)
    assert eng.replan_count == 0


def test_engine_3way_matches_batch_run_join():
    rng = np.random.default_rng(12)
    q3 = three_way_paper()
    eng = StreamingJoinEngine(q3, StreamConfig(q=100, hh_threshold=30))
    for _ in range(3):
        eng.ingest(paper_3way(rng, n=250, domain=250))
    cat = eng.history_data()
    from repro.core import plan_shares_skew

    plan = plan_shares_skew(q3, cat, q=300)
    res = run_join(q3, cat, plan, cap_factor=4.0)
    assert res.overflow == 0
    assert (eng.total_count, eng.total_checksum) == (res.count, res.checksum)


def test_engine_drift_replan_and_correctness():
    """Zipf exponent (2.0 -> 1.4) + location shift mid-run: >=1 drift replan
    fires and the cumulative fingerprint matches the concatenated oracle."""
    rng = np.random.default_rng(13)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=120, decay=0.5, load_factor=2.0))
    for _ in range(3):
        eng.ingest(_zipf_batch(rng, 0, n_r=900, n_s=220, domain=2000, a=2.0))
    for _ in range(3):
        eng.ingest(_zipf_batch(rng, 700, n_r=900, n_s=220, domain=2000, a=1.4))
    assert eng.replan_count >= 1
    assert any("overload" in r.drift_reason for r in eng.reports if r.replanned)
    count, checksum, _, _ = oracle_join(q, eng.history_data())
    assert (eng.total_count, eng.total_checksum) == (count, checksum)


def test_engine_comm_within_factor_of_exact_replan_oracle():
    """Cumulative new-tuple shuffle volume stays within 1.25x of an oracle
    that replans every batch from exact heavy hitters."""
    from repro.core import plan_shares_skew
    from repro.mapreduce import predicted_comm

    rng = np.random.default_rng(14)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=120, decay=0.5, load_factor=2.0))
    oracle_comm = 0
    batches = [_zipf_batch(rng, 0, a=2.0) for _ in range(3)] + [
        _zipf_batch(rng, 1000, a=1.4) for _ in range(3)
    ]
    for b in batches:
        eng.ingest(b)
        oracle_plan = plan_shares_skew(q, b, q=120)
        oracle_comm += sum(predicted_comm(oracle_plan).values())
    assert eng.replan_count >= 1
    assert eng.cumulative_comm <= 1.25 * oracle_comm, (
        eng.cumulative_comm,
        oracle_comm,
    )


def test_engine_empty_and_lopsided_batches():
    rng = np.random.default_rng(15)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=100))
    eng.ingest(
        {
            "R": np.zeros((0, 2), dtype=np.int64),
            "S": rng.integers(0, 100, (50, 2)).astype(np.int64),
        }
    )
    assert eng.total_count == 0
    eng.ingest(
        {
            "R": rng.integers(0, 100, (80, 2)).astype(np.int64),
            "S": np.zeros((0, 2), dtype=np.int64),
        }
    )
    # R tuples must join with the PREVIOUS batch's S tuples
    count, checksum, _, _ = oracle_join(q, eng.history_data())
    assert (eng.total_count, eng.total_checksum) == (count, checksum)
    assert count > 0


def test_engine_recovers_from_empty_first_batch():
    """A plan installed against an empty first batch (1-reducer degenerate
    grid, zero comm baseline) must be replaced once real traffic arrives —
    the comm-drift trigger fires even with a zero baseline."""
    rng = np.random.default_rng(19)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=100, cooldown=0))
    empty = {
        "R": np.zeros((0, 2), dtype=np.int64),
        "S": np.zeros((0, 2), dtype=np.int64),
    }
    eng.ingest(empty)
    assert eng.plan.total_reducers == 1  # degenerate plan, nothing to size for
    for _ in range(3):
        eng.ingest(
            {
                "R": rng.integers(0, 2000, (600, 2)).astype(np.int64),
                "S": rng.integers(0, 2000, (150, 2)).astype(np.int64),
            }
        )
    assert any("comm" in r.drift_reason for r in eng.reports if r.replanned)
    assert eng.plan.total_reducers > 1
    count, checksum, _, _ = oracle_join(q, eng.history_data())
    assert (eng.total_count, eng.total_checksum) == (count, checksum)


def test_engine_distributed_recompute_agrees():
    rng = np.random.default_rng(16)
    q = two_way()
    eng = StreamingJoinEngine(q, StreamConfig(q=150))
    for _ in range(2):
        eng.ingest(paper_2way(rng, n_r=500, n_s=150, domain=900))
    res = eng.recompute_distributed(cap_factor=8.0, route_cap_factor=8.0)
    assert res.overflow == 0
    assert (res.count, res.checksum) == (eng.total_count, eng.total_checksum)

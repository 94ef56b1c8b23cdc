"""Integration tests: the JAX MapReduce join engine vs the host oracle."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    make_query,
    plan_plain_shares,
    plan_shares_skew,
    three_way_paper,
    triangle,
    two_way,
)
from repro.data import paper_2way, paper_3way, random_join_data
from repro.mapreduce import (
    keyed_join_fingerprint,
    naive_two_way,
    oracle_join,
    predicted_comm,
    run_join,
)


def _check(query, data, plan, cap_factor=4.0):
    res = run_join(query, data, plan, cap_factor=cap_factor)
    count, checksum, _, _ = oracle_join(query, data)
    assert res.overflow == 0, f"capacity overflow: {res.overflow}"
    assert res.count == count
    assert res.checksum == checksum
    return res


# ------------------------------------------------------------- correctness
def test_2way_skewed_matches_oracle():
    data = paper_2way(np.random.default_rng(0), n_r=3000, n_s=600, domain=2000)
    plan = plan_shares_skew(two_way(), data, q=200)
    assert len(plan.residuals) == 2
    res = _check(two_way(), data, plan)
    assert res.count > 0


def test_2way_comm_matches_prediction():
    data = paper_2way(np.random.default_rng(1), n_r=3000, n_s=600, domain=2000)
    plan = plan_shares_skew(two_way(), data, q=200)
    res = run_join(two_way(), data, plan, cap_factor=4.0)
    # measured shuffle == the cost model, exactly (deterministic routing)
    assert res.comm_tuples == predicted_comm(plan)
    assert res.total_comm == sum(predicted_comm(plan).values())


def test_3way_paper_query_matches_oracle():
    data = paper_3way(np.random.default_rng(2), n=500, domain=300)
    plan = plan_shares_skew(three_way_paper(), data, q=150)
    res = _check(three_way_paper(), data, plan)
    assert res.count > 0


def test_triangle_matches_oracle():
    rng = np.random.default_rng(3)
    data = random_join_data(rng, triangle(), n_per_relation=300, domain=40)
    plan = plan_shares_skew(triangle(), data, q=200)
    _check(triangle(), data, plan)


def test_no_skew_single_residual():
    rng = np.random.default_rng(4)
    q = two_way()
    data = random_join_data(rng, q, n_per_relation=1000, domain=5000)
    plan = plan_shares_skew(q, data, q=300)
    assert len(plan.residuals) == 1
    _check(q, data, plan)


def test_plain_shares_correct_but_skewed():
    # Shares (no HH handling) still computes the right answer; its max load
    # explodes under skew — exactly the paper's Figure 3 observation.
    data = paper_2way(np.random.default_rng(5), n_r=3000, n_s=600, domain=2000)
    plain = plan_plain_shares(two_way(), data, k=64)
    res = run_join(two_way(), data, plain, cap_factor=40.0)
    count, checksum, _, _ = oracle_join(two_way(), data)
    assert res.overflow == 0
    assert (res.count, res.checksum) == (count, checksum)
    skew_plan = plan_shares_skew(two_way(), data, q=200)
    res_skew = run_join(two_way(), data, skew_plan, cap_factor=4.0)
    assert res_skew.load_imbalance < res.load_imbalance


def test_empty_relation():
    q = two_way()
    data = {
        "R": np.zeros((0, 2), dtype=np.int64),
        "S": np.array([[1, 2], [3, 4]], dtype=np.int64),
    }
    plan = plan_shares_skew(q, data, q=100)
    res = run_join(q, data, plan)
    assert res.count == 0


def test_all_tuples_one_value():
    # 100% skew (§9.3: "we only include tuples with one HH")
    q = two_way()
    n = 400
    rng = np.random.default_rng(6)
    data = {
        "R": np.stack([rng.integers(0, 1000, n), np.full(n, 7)], 1).astype(np.int64),
        "S": np.stack([np.full(n, 7), rng.integers(0, 1000, n)], 1).astype(np.int64),
    }
    plan = plan_shares_skew(q, data, q=100)
    res = _check(q, data, plan, cap_factor=6.0)
    assert res.count == n * n  # full cartesian product on B=7
    # Example 2's rectangle: load spread across reducers, none holds r+s
    assert res.max_load < 2 * n


# ------------------------------------------------------------ naive baseline
def test_naive_costs_more_than_shares_skew():
    # NB: for k <= r/s the optimal rectangle degenerates to x=k, y=1 — i.e.
    # the naive partition-broadcast IS optimal there and costs tie.  q=100
    # forces k > r_hh/s_hh (= 10), where 2*sqrt(krs) < r + k*s strictly.
    data = paper_2way(np.random.default_rng(7), n_r=20000, n_s=2000, domain=30000)
    plan = plan_shares_skew(two_way(), data, q=100)
    res = run_join(two_way(), data, plan, cap_factor=4.0)
    hh_res = next(r for r in plan.residuals if r.combo.pinned)
    k = hh_res.num_reducers
    stats = naive_two_way(
        data["R"], data["S"], np.array([7]), k_hh=k,
        k_ord=max(1, plan.total_reducers - k),
    )
    assert res.total_comm < stats.comm_tuples


# ----------------------------------------------------- per-key reference
def _zipf_data(query, rng, a, n=300, domain=400):
    """Each relation's join columns Zipf(a)-skewed, the others uniform."""
    shared = {
        x for r in query.relations for s in query.relations if r is not s
        for x in set(r.attrs) & set(s.attrs)
    }
    return {
        r.name: np.stack(
            [
                (rng.zipf(a, n) - 1) % domain if x in shared
                else rng.integers(0, domain, n)
                for x in r.attrs
            ],
            axis=1,
        ).astype(np.int64)
        for r in query.relations
    }


@pytest.mark.parametrize("query", [two_way(), three_way_paper()], ids=str)
@pytest.mark.parametrize("a", [1.3, 1.7, 2.2])
@pytest.mark.parametrize("seed", [0, 1])
def test_keyed_join_fingerprint_matches_oracle(query, a, seed):
    data = _zipf_data(query, np.random.default_rng(seed), a)
    count, checksum, _, _ = oracle_join(query, data)
    assert keyed_join_fingerprint(query, data) == (count, checksum)


def test_keyed_join_fingerprint_refuses_cycles():
    data = _zipf_data(triangle(), np.random.default_rng(0), 1.5)
    with pytest.raises(ValueError, match="star"):
        keyed_join_fingerprint(triangle(), data)


# ------------------------------------------------------- distributed shuffle
_DISTRIBUTED_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.core import plan_shares_skew, two_way, three_way_paper
from repro.data import paper_2way, paper_3way
from repro.mapreduce import oracle_join, run_distributed

data = paper_2way(np.random.default_rng(0), n_r=3000, n_s=600, domain=2000)
plan = plan_shares_skew(two_way(), data, q=200)
res = run_distributed(two_way(), data, plan, cap_factor=4.0, route_cap_factor=4.0)
count, checksum, _, _ = oracle_join(two_way(), data)
assert res.overflow == 0, res.overflow
assert res.count == count, (res.count, count)
assert res.checksum == checksum, (res.checksum, checksum)

data3 = paper_3way(np.random.default_rng(2), n=400, domain=300)
plan3 = plan_shares_skew(three_way_paper(), data3, q=150)
res3 = run_distributed(three_way_paper(), data3, plan3, cap_factor=4.0, route_cap_factor=4.0)
c3, s3, _, _ = oracle_join(three_way_paper(), data3)
assert res3.overflow == 0
assert (res3.count, res3.checksum) == (c3, s3), ((res3.count, res3.checksum), (c3, s3))
print("DISTRIBUTED_OK")
"""


def test_distributed_shuffle_8_devices():
    """Real all_to_all over 8 host devices, in a subprocess so the main
    test process keeps its single-device view."""
    proc = subprocess.run(
        [sys.executable, "-c", _DISTRIBUTED_SNIPPET],
        capture_output=True,
        text=True,
        timeout=600,
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": "cpu",
        },
        cwd=Path(__file__).resolve().parents[1],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DISTRIBUTED_OK" in proc.stdout


def test_distributed_single_device_matches_oracle():
    data = paper_2way(np.random.default_rng(8), n_r=2000, n_s=400, domain=1500)
    plan = plan_shares_skew(two_way(), data, q=200)
    from repro.mapreduce import run_distributed

    res = run_distributed(two_way(), data, plan, cap_factor=4.0)
    count, checksum, _, _ = oracle_join(two_way(), data)
    assert res.overflow == 0
    assert (res.count, res.checksum) == (count, checksum)


# the batch entry with its tracing off, defaulted and on, on n host devices:
# the plan, the result and the span names of each, as JSON
_OBS_SNIPPET = r"""
import json, os, sys
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
import jax
import numpy as np
from repro.core import plan_shares_skew, two_way
from repro.data import paper_2way
from repro.mapreduce import run_distributed
from repro.obs import Observability, ObsPolicy

assert len(jax.devices()) == n, jax.devices()
data = paper_2way(np.random.default_rng(5), n_r=3000, n_s=600, domain=2000)
out = {}
for mode in ("default", "off", "on"):
    kw = {} if mode == "default" else {"obs": Observability(ObsPolicy(trace=mode == "on"))}
    plan = plan_shares_skew(two_way(), data, q=200, **kw)
    res = run_distributed(two_way(), data, plan, cap_factor=4.0, route_cap_factor=4.0, **kw)
    out[mode] = {
        "plan": [
            plan.describe(),
            [sorted(r.solution.int_shares.items()) for r in plan.residuals],
            [r.reducer_offset for r in plan.residuals],
            {a: v.tolist() for a, v in plan.hh_values.items()},
        ],
        "result": [res.count, res.checksum, res.comm_tuples, res.reducer_loads.tolist(), res.overflow],
        "spans": kw["obs"].tracer.span_names() if mode == "on" else [],
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def obs_modes():
    """Device count -> the snippet's output, one subprocess per count (the
    host device count is fixed when XLA starts)."""
    done = {}

    def get(n_devices: int) -> dict:
        if n_devices not in done:
            proc = subprocess.run(
                [sys.executable, "-c", _OBS_SNIPPET, str(n_devices)],
                capture_output=True,
                text=True,
                timeout=600,
                env={
                    "PYTHONPATH": "src",
                    "PATH": "/usr/bin:/bin:/usr/local/bin",
                    "JAX_PLATFORMS": "cpu",
                },
                cwd=Path(__file__).resolve().parents[1],
            )
            assert proc.returncode == 0, proc.stderr[-3000:]
            done[n_devices] = json.loads(proc.stdout.splitlines()[-1])
        return done[n_devices]

    return get


@pytest.mark.parametrize("n_devices", [1, 4])
def test_batch_entry_is_bit_identical_with_tracing_on_and_off(obs_modes, n_devices):
    """``plan_shares_skew`` and ``run_distributed`` give the same plan and
    the same ``JoinResult`` with ``obs`` defaulted, off and tracing, and
    the traced run opens the planner's and the shuffle's spans."""
    out = obs_modes(n_devices)
    assert out["on"]["plan"] == out["off"]["plan"] == out["default"]["plan"]
    assert out["on"]["result"] == out["off"]["result"] == out["default"]["result"]
    data = paper_2way(np.random.default_rng(5), n_r=3000, n_s=600, domain=2000)
    count, checksum, _, _ = oracle_join(two_way(), data)
    assert out["on"]["result"][:2] == [count, checksum]
    assert out["on"]["result"][-1] == 0
    assert out["on"]["spans"] == [
        "plan.detect", "plan.solve",
        "shuffle.stage", "shuffle.lower", "shuffle.execute", "shuffle.fetch",
    ]


def test_speculative_join_matches_plain():
    """Over-decomposed reduce with speculative re-execution returns exactly
    the same (count, checksum, comm) as the monolithic run."""
    from repro.mapreduce import run_join_speculative

    data = paper_3way(np.random.default_rng(9), n=400, domain=300)
    plan = plan_shares_skew(three_way_paper(), data, q=120)
    base = run_join(three_way_paper(), data, plan, cap_factor=4.0)
    spec = run_join_speculative(
        three_way_paper(), data, plan, cap_factor=4.0, n_shards=3
    )
    assert spec.count == base.count
    assert spec.checksum == base.checksum
    assert spec.comm_tuples == base.comm_tuples
    assert spec.overflow == 0

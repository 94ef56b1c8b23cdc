"""The cell ``batch_2way.four_chip`` on the CPU.

Its committed configuration, with ``batch_rows`` and ``q`` scaled down by
one factor, reads correct through the job path on one device and on four,
and its control ``no_exchange`` reads not correct on four.  The cell's
per-layer readers read a traced run and find nothing in an untraced one.
The round's byte count is checked by hand.  At the committed size every
job of a seed's pool plans to the warm-up job's reducers, bin capacity and
route capacities, so no job in the window compiles a program of its own.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
SRC = HERE.parents[1] / "src"
sys.path.insert(0, str(HERE))

from chipbench import harness, job, job_roofline, spec  # noqa: E402
from chipbench.xtrace import Op  # noqa: E402

CELL = "batch_2way.four_chip"
# 40,000 R rows and q 312.5: the committed q per R row, so the plan keeps
# its shape (177 reducers) at a size the CPU runs in seconds.  The route
# caps spread the plan's emissions evenly over the pairs of devices, and the
# heavy hitter's reducers sit on two of four, so their headroom is
# statistical and shrinks with the rows: over 60 seeded jobs the fullest
# send buffer filled 0.97 of its cap at this size, 1.28 at 10,000 R rows and
# 0.86 (20 jobs) at the committed size
SCALE = 25
FAKE_TPU = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite", memory_stats=lambda: {})
READERS = [m["name"] for m in spec.load_benchmark()["per_layer"] if CELL in m["workloads"]]
DEVICE_TRACE = {
    m["name"] for m in spec.load_benchmark()["per_layer"]
    if CELL in m["workloads"] and m["source"] == "device_trace"
}
# a device trace for the CPU's traced run, which has none: 1 s of window,
# each chip busy for half of it, two all-to-all operations of 2 ms on the
# first chip
TRACE = harness.TraceView(
    ops=[Op("all-to-all", 0, 2e6), Op("fusion", 3e6, 5e6), Op("all-to-all", 9e6, 2e6)],
    spans=[], lo=0, hi=1e9, busy_s=0.5, window_s=1.0,
)


def small_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    c = cell.config
    return dataclasses.replace(
        cell, config=dict(c, batch_rows=c["batch_rows"] // SCALE, q=c["q"] / SCALE)
    )


def run_case(case: str, n_devices: int, seconds: float = 0.6) -> dict:
    """One run of the scaled cell on ``n_devices`` CPU devices: ``sound``
    (untraced), ``traced``, or the control ``no_exchange``; the result
    object, with what the cell's readers find in the run (``read``) and,
    for ``traced``, in the run given ``TRACE`` (``read_with_trace``), and
    what the roofline should read there."""
    import jax

    cpus = jax.devices()[:n_devices]
    assert len(cpus) == n_devices, cpus

    def make_program(cell, devices, settings, trace):
        if case == "no_exchange":
            return spec.load_control("no_exchange")(cell, cpus, settings, trace)
        return job.BatchJoin(cell, cpus, settings, trace)

    runs = []
    result = harness.result

    def keep(run, *args, **kwargs):
        runs.append(run)
        return result(run, *args, **kwargs)

    harness.result = keep
    try:
        out = job.run_job_cell(
            small_cell(), 2**31 + 11, seconds, case == "traced",
            devices=[FAKE_TPU] * n_devices, t_process=time.perf_counter(),
            make_program=make_program,
        )
    finally:
        harness.result = result
    (run,) = runs
    out["read"] = {name: spec.load_reader(name)(run) for name in READERS}
    if case == "traced":
        traced = dataclasses.replace(run, trace=TRACE)
        out["read_with_trace"] = {name: spec.load_reader(name)(traced) for name in READERS}
        moved = sum(
            job_roofline.round_bytes(len(rows), 2, b.report.comm_tuples[nm])
            for b in run.window()
            for nm, rows in run.batch_rows(b.index).items()
        )
        out["roofline_expected"] = 100.0 * (moved / 819e9) / (TRACE.busy_s * run.cell.chips)
        out["window_jobs"] = len(run.window())
    return out


_CASES = r"""
import json, sys
tests, n, cases = sys.argv[1], int(sys.argv[2]), sys.argv[3:]
sys.path.insert(0, tests)
import test_chipbench_batch_2way as t
sys.path.insert(0, str(t.SRC))
for case in cases:
    print(json.dumps({"case": case, "out": t.run_case(case, n)}), flush=True)
"""


@pytest.fixture(scope="module")
def results():
    """Case -> result object, on one CPU device and on four, each device
    count in one process of its own."""
    cases = {1: ["sound"], 4: ["sound", "traced", "no_exchange"]}
    done = {}

    def get(n_devices: int) -> dict:
        if n_devices not in done:
            env = dict(
                os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
            )
            proc = subprocess.run(
                [sys.executable, "-c", _CASES, str(Path(__file__).parent), str(n_devices),
                 *cases[n_devices]],
                capture_output=True, text=True, timeout=900, env=env, cwd=HERE.parents[1],
            )
            assert proc.returncode == 0, proc.stderr[-3000:]
            lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
            done[n_devices] = {x["case"]: x["out"] for x in lines}
        return done[n_devices]

    return get


@pytest.mark.parametrize("n_devices", [1, 4])
def test_committed_configuration_scaled_down_reads_correct(results, n_devices):
    out = results(n_devices)["sound"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert out["device"]["count"] == n_devices
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_no_exchange_reads_not_correct_on_four_devices(results):
    out = results(4)["no_exchange"]
    assert not out["correct"]
    assert out["checks"]["fingerprint_mismatches"]["value"] == out["attempted"] > 0
    assert out["checks"]["overflow_tuples"]["value"] == 0


@pytest.mark.parametrize("n_devices", [1, 4])
def test_readers_find_nothing_in_an_untraced_run(results, n_devices):
    read = results(n_devices)["sound"]["read"]
    assert sorted(read) == sorted(READERS) and len(READERS) == 9
    assert all(v is None for v in read.values()), read


def test_readers_read_a_traced_run(results):
    """The span and counter readers read the program's own spans; the
    device-trace readers read the trace, which a CPU run lacks."""
    out = results(4)["traced"]
    assert out["correct"]
    read, with_trace = out["read"], out["read_with_trace"]
    for name in READERS:
        if name in DEVICE_TRACE:
            assert read[name] is None, name
        else:
            assert read[name] is not None and read[name] > 0, (name, read[name])
        assert with_trace[name] is not None and with_trace[name] > 0, (name, with_trace[name])
    assert with_trace["device.idle_pct.jobs"] == pytest.approx(50.0)
    assert with_trace["shuffle.all_to_all_ms"] == pytest.approx(4.0 / out["window_jobs"])
    assert with_trace["shuffle_roofline"] == pytest.approx(out["roofline_expected"])
    # the plan's 177 reducers, about 1.31 emissions a row (1,440 of 1,100)
    assert read["job.comm_per_row"] == pytest.approx(1440 / 1100, rel=1e-6)
    assert 1.0 <= read["job.imbalance"] < 5.0


def test_round_bytes_by_hand():
    # 1,000 rows of two int32 cells read once: 8,000 B; 300 emissions of
    # three cells (two and the reducer id), written and read on the mapper's
    # side and again on the reducer's: 300 * 4 * 12 B
    assert job_roofline.round_bytes(1000, 2, 300) == 8000 + 14400
    assert job_roofline.round_bytes(10, 3, 0) == 120


def test_every_job_of_the_committed_size_plans_alike():
    """Host planning only, no compile: the warm-up job and the 8 window jobs
    of two seeds share reducers, bin capacity and route capacities, so the
    window reuses the warm-up's executable from the compile cache."""
    from repro.core import make_query, plan_shares_skew
    from repro.mapreduce.executor import _bin_cap
    from repro.mapreduce.shuffle import route_caps

    cell = spec.load_cell(CELL)
    c = cell.config
    query = make_query({k: tuple(v) for k, v in c["relations"].items()})

    def shape(data):
        plan = plan_shares_skew(query, data, q=float(c["q"]))
        return (
            plan.total_reducers,
            _bin_cap(plan, c["job"]["cap_factor"]),
            tuple(sorted(route_caps(plan, cell.chips, c["job"]["route_cap_factor"]).items())),
            {nm: rows.shape for nm, rows in data.items()},
        )

    for seed in (2**31 + 3, 977):
        pool = harness.Pool(cell, seed, 1, int(cell.mix["pool_batches"]))
        warm = shape(pool[0])
        assert warm[:2] == (177, 23446)
        for i in range(1, 1 + pool.n_window):
            assert shape(pool[i]) == warm, (seed, i)

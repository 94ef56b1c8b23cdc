"""The job path end to end on the CPU at a small size: a sound batch join
reads correct on one device and on four, each fault it can have reads not
correct on both (and the exchange left out, where there is one, on four),
the entry's spans reach the metric readers, and a configuration or a
control the program cannot run as stated is refused."""
import contextlib
import dataclasses
import importlib.util
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

from chipbench import harness, job, spans, spec  # noqa: E402

FAKE_TPU = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite", memory_stats=lambda: {})
CELL = "tiny_job.bulk"
# the paper's section 9.1 job at a small size: one heavy hitter on 10 % of B
CONFIG = {
    "name": "tiny_job",
    "path": "job",
    "relations": {"R": ["A", "B"], "S": ["B", "C"]},
    "join_attr": "B",
    "domain": 4096,
    "keys": {"kind": "heavy_hitter", "value": 7, "fraction": 0.1},
    "batch_rows": 2000,
    "s_per_r": 0.1,
    "q": 100,
    "job": {"cap_factor": 4.0, "route_cap_factor": 4.0},
}
SMALL_BINS = {"cap_factor": 0.01, "route_cap_factor": 0.01}
# the result line of a stream cell, untraced
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "window_compiles", "checks"]
# the faults a job can have, each with the number that catches it
FAULTS = [
    ("dropped_reducer", "fingerprint_mismatches"),
    ("altered_count", "fingerprint_mismatches"),
    ("small_bins", "overflow_tuples"),
]
# on more than one device the exchange between them can be left out too
FAULTS_ACROSS_DEVICES = FAULTS + [("no_exchange", "fingerprint_mismatches")]


def write_job_cell(root: Path, **changes) -> dict:
    """A BENCHMARK.json under ``root`` with a job cell added, its
    configuration file beside it; returns the benchmark."""
    bench = spec.load_benchmark()
    (root / "tiny_job.json").write_text(json.dumps(dict(CONFIG, **changes)))
    bench["configs"].append(
        {"name": "tiny_job", "source": "t", "file": "tiny_job.json", "reduced": [], "why": "t"}
    )
    bench["workloads"].append(
        {"name": CELL, "config": "tiny_job", "traffic": "bulk", "chips": 1, "why": "t"}
    )
    next(e for e in bench["end_to_end"] if e["name"] == "rows_per_s")["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def job_cell(root: Path, **changes) -> spec.Cell:
    return spec.load_cell(CELL, bench=write_job_cell(root, **changes), root=root)


class AlteredCount:
    """The batch join with one job's count altered by one where it is
    produced."""

    def __init__(self, program):
        self.program, self.n = program, 0

    def ingest(self, data):
        self.n += 1
        report = self.program.ingest(data)
        if self.n == 2:  # the window's first job
            report = dataclasses.replace(report, count=report.count + 1)
        return report


@contextlib.contextmanager
def dropped_reducer():
    """``local_join_per_reducer`` with the reducer of most results on
    device 0 left out."""
    import jax
    import jax.numpy as jnp

    from repro.mapreduce import shuffle

    local_join = shuffle.local_join_per_reducer

    def dropped(*args, **kwargs):
        counts, checksum = local_join(*args, **kwargs)
        drop = (jax.lax.axis_index("shuffle") == 0) & (
            jnp.arange(counts.shape[0]) == jnp.argmax(counts)
        )
        return jnp.where(drop, 0, counts), jnp.where(drop, 0, checksum)

    shuffle.local_join_per_reducer = dropped
    try:
        yield
    finally:
        shuffle.local_join_per_reducer = local_join


@contextlib.contextmanager
def entry_with_spans():
    """``run_distributed`` as an entry that takes the ``obs`` facade and
    opens a span of its own."""
    import repro.mapreduce as mapreduce

    run_distributed = mapreduce.run_distributed

    def entry(query, data, plan, mesh=None, axis_name="shuffle", cap_factor=3.0,
              route_cap_factor=3.0, obs=None):
        with obs.span("shuffle.exchange", cat="job"):
            return run_distributed(
                query, data, plan, mesh, axis_name, cap_factor, route_cap_factor
            )

    mapreduce.run_distributed = entry
    try:
        yield
    finally:
        mapreduce.run_distributed = run_distributed


def run_case(case: str, root: Path, n_devices: int, seconds: float = 0.6) -> dict:
    """One run of the job cell under ``root`` on ``n_devices`` CPU devices:
    the sound program, a fault planted in it, or the sound program traced
    (``spans``); the result object, and for ``spans`` what the span
    readers find in the run."""
    import jax

    cpus = jax.devices()[:n_devices]
    assert len(cpus) == n_devices, cpus
    cell = spec.load_cell(CELL, root=root)
    if case == "small_bins":
        cell = dataclasses.replace(cell, config=dict(cell.config, job=SMALL_BINS))

    def make_program(cell, devices, settings, trace):
        if case == "no_exchange":
            return spec.load_control("no_exchange")(cell, cpus, settings, trace)
        program = job.BatchJoin(cell, cpus, settings, trace)
        return AlteredCount(program) if case == "altered_count" else program

    runs = []
    result = harness.result

    def keep(run, *args, **kwargs):
        runs.append(run)
        return result(run, *args, **kwargs)

    planted = {"dropped_reducer": dropped_reducer, "spans": entry_with_spans}
    harness.result = keep
    try:
        with planted.get(case, contextlib.nullcontext)():
            out = job.run_job_cell(
                cell, 2**31 + 7, seconds, case == "spans", devices=[FAKE_TPU] * n_devices,
                t_process=time.perf_counter(), make_program=make_program,
            )
    finally:
        harness.result = result
    if case == "spans":
        out["read"] = {
            "job.plan": spans.ms_per_batch(runs[0], "job.plan"),
            "job.run": spans.ms_per_batch(runs[0], "job.run"),
            "shuffle.exchange": spans.ms_per_batch(runs[0], "shuffle.exchange", parent="job.run"),
        }
    return out


_CASES = r"""
import json, sys
from pathlib import Path
tests, root, n, cases = sys.argv[1], Path(sys.argv[2]), int(sys.argv[3]), sys.argv[4:]
sys.path.insert(0, tests)
import test_chipbench_job as t
sys.path.insert(0, str(t.SRC))
for case in cases:
    print(json.dumps({"case": case, "out": t.run_case(case, root, n)}), flush=True)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    """Case -> result object, on one and on four CPU devices, each device
    count in one process of its own (XLA's device count is fixed at
    start-up)."""
    cases = {
        1: ["sound", "spans"] + [f for f, _ in FAULTS],
        4: ["sound"] + [f for f, _ in FAULTS_ACROSS_DEVICES],
    }
    done = {}

    def get(n_devices: int) -> dict:
        if n_devices not in done:
            root = tmp_path_factory.mktemp(f"jobs{n_devices}")
            write_job_cell(root)
            env = dict(
                os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
            )
            proc = subprocess.run(
                [sys.executable, "-c", _CASES, str(Path(__file__).parent), str(root),
                 str(n_devices), *cases[n_devices]],
                capture_output=True, text=True, timeout=900, env=env, cwd=HERE.parents[1],
            )
            assert proc.returncode == 0, proc.stderr[-3000:]
            lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
            done[n_devices] = {x["case"]: x["out"] for x in lines}
        return done[n_devices]

    return get


@pytest.mark.parametrize("n_devices", [1, 4])
def test_sound_program_reads_correct(results, n_devices):
    """On a mesh of one CPU device and of four: the exchange between
    devices runs, every job matches the reference, and the result line has
    a stream cell's keys."""
    out = results(n_devices)["sound"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert list(out) == RESULT_KEYS
    assert out["device"]["count"] == n_devices
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert set(out["checks"]) == {"fingerprint_mismatches", "overflow_tuples", "failed_jobs"}
    assert isinstance(out["window_compiles"], int)


def _reads_not_correct(out: dict, check: str) -> None:
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"][check]["value"] > 0


@pytest.mark.parametrize("fault, check", FAULTS)
def test_fault_reads_not_correct(results, fault, check):
    _reads_not_correct(results(1)[fault], check)


@pytest.mark.parametrize("fault, check", FAULTS_ACROSS_DEVICES)
def test_fault_reads_not_correct_on_four_devices(results, fault, check):
    """The faults again where the reducers sit on four devices, and the
    exchange between them left out: the job path's control
    ``controls/no_exchange.py``."""
    _reads_not_correct(results(4)[fault], check)


def test_entry_spans_reach_the_readers(results):
    """A traced run hands the tracer's events to the readers: the
    harness's ``job.plan`` and ``job.run`` around the entry's calls, and
    a span the entry opens on the ``obs`` facade it is given, under
    ``job.run``."""
    out = results(1)["spans"]
    assert out["correct"]
    assert all(v is not None and v > 0 for v in out["read"].values()), out["read"]


def refused_run(cell):
    job.run_job_cell(
        cell, 1, 0.1, False, devices=[FAKE_TPU], t_process=time.perf_counter(),
        make_program=lambda *a: pytest.fail("a refused configuration ran"),
    )


def test_job_setting_the_entry_lacks_is_refused(tmp_path):
    cell = job_cell(tmp_path, job={"cap_factor": 4.0, "sort_free": True})
    with pytest.raises(ValueError, match="sort_free"):
        refused_run(cell)


@pytest.mark.parametrize("setting", ["axis_name", "obs"])
def test_job_setting_the_harness_passes_is_refused(tmp_path, setting):
    cell = job_cell(tmp_path, job={"cap_factor": 4.0, setting: "x"})
    with pytest.raises(ValueError, match=setting):
        refused_run(cell)


def _run_py():
    module = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
    run_py = importlib.util.module_from_spec(module)
    module.loader.exec_module(run_py)
    return run_py


def test_stream_control_on_a_job_cell_is_refused(tmp_path, monkeypatch, capsys):
    cell = job_cell(tmp_path)
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    rc = _run_py().main(
        ["--workload", CELL, "--seed", "1", "--seconds", "1", "--control", "late_expiry"]
    )
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "job cell" in err


@pytest.mark.parametrize("path, control", [(None, "no_exchange"), ("job", "no_such_control")])
def test_control_of_another_path_is_refused(tmp_path, monkeypatch, capsys, path, control):
    """A job control on a stream cell, and a job control with no file in
    ``controls/``, exit non-zero before JAX is touched."""
    cell = job_cell(tmp_path) if path else spec.load_cell("paper_hh.bulk")
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    rc = _run_py().main(
        ["--workload", cell.name, "--seed", "1", "--seconds", "1", "--control", control]
    )
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert control in err

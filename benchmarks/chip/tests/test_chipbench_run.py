"""The harness end to end on the CPU at a small size: a sound engine reads
correct, the control and each fault the cells can have read not correct,
and the command itself refuses to run without a TPU."""
import dataclasses
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import harness, spec  # noqa: E402

ROWS = 250
FAKE_TPU = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite", memory_stats=lambda: {})


def small_cell(name: str) -> spec.Cell:
    """A cell of BENCHMARK.json at a small size: 250 R rows a batch, a
    window of 3 batches, over a 4096-value domain."""
    cell = spec.load_cell(name)
    config = dict(cell.config, domain=4096, batch_rows=ROWS, window_rows=3 * ROWS)
    return dataclasses.replace(cell, config=config, mix=dict(cell.mix, pool_batches=4))


class Faulty:
    """The engine with one fault planted where its answer is produced."""

    def __init__(self, engine, fault: str):
        self.engine, self.fault, self.n = engine, fault, 0

    def ingest(self, batch):
        self.n += 1
        if self.fault == "stale" and self.n > 3:  # the state stops moving
            return self.engine.reports[-1]
        if self.fault == "half":  # half the batch left out
            batch = {k: v[: len(v) // 2] for k, v in batch.items()}
        report = self.engine.ingest(batch)
        if self.fault == "altered" and self.n == 5:
            report = dataclasses.replace(report, delta_count=report.delta_count + 1)
        return report


def run(cell, make_engine=None, seconds=0.6):
    return harness.run_cell(
        cell, 2**31 + 7, seconds, False, devices=[FAKE_TPU],
        t_process=time.perf_counter(), make_engine=make_engine,
    )


@pytest.mark.parametrize("name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_sound_engine_reads_correct(name):
    out = run(small_cell(name), seconds=1.5)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {m["name"] for m in small_cell(name).end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["stale", "half", "altered", "control"])
def test_fault_reads_not_correct(fault):
    cell = small_cell("paper_hh.bulk")
    if fault == "control":
        def make(cell, config):
            return harness.ReferenceStandIn(cell, config.retention.window_batches)
    else:
        def make(cell, config):
            return Faulty(harness.engine_factory(cell, config), fault)
    out = run(cell, make)
    assert not out["correct"]
    assert out["failed"] > 0


def test_configuration_setting_the_engine_lacks_is_refused():
    cell = small_cell("paper_hh.bulk")
    stream = dict(cell.config["stream"], fused_ingest_v2=True)
    cell = dataclasses.replace(cell, config=dict(cell.config, stream=stream))
    with pytest.raises(ValueError, match="fused_ingest_v2"):
        run(cell)


def test_engine_runs_what_the_configuration_states():
    cell = small_cell("paper_hh.bulk")
    config = harness.stream_config(cell, trace=False)
    for key, value in cell.config["stream"].items():
        assert getattr(config, key) == value
    assert config.retention.window_batches == 3
    assert config.q == cell.config["q_per_batch_row"] * ROWS


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "paper_hh.bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=HERE.parents[1], env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr

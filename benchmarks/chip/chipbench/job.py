"""One run of a job cell: the batch SharesSkew join, one MapReduce round a
job, on the cell's own chips.

A configuration with ``"path": "job"`` is run here.  Job ``i`` is batch
``i`` of the traffic generator (``traffic.make_batch``): the
configuration's ``batch_rows`` of R and ``s_per_r`` as many of S.  The
program's public batch entry runs it as one job: ``plan_shares_skew``
with the configuration's ``q``, then ``run_distributed`` (map, one
``all_to_all``, reduce) on a mesh of the cell's devices, with the
configuration's ``job`` settings.

Set-up makes the pool of jobs from the seed and runs one warm-up job.
The window then runs the pool's jobs back to back for ``seconds``; the
job in flight at the close completes.  Each job is a ``harness.Batch``
whose ``rows`` are its R and S rows and whose ``report`` is the
``JoinResult``, so the stream path's readers of rows and set-up read a
job cell too.

Afterwards every job's ``(count, checksum)`` is compared with
``reference.keyed_fingerprint`` of its rows, and its ``overflow`` with 0.

In a traced run the entry's two calls are spans ``job.plan`` and
``job.run`` of a ``repro.obs`` tracer, batch-clocked by the job's index;
an entry that takes an ``obs`` argument gets the tracer's facade, so the
spans it opens itself nest under them and reach the metric readers
(``chipbench/spans.py``) as the stream engine's do.  A control is a
program of ``controls/<name>.py`` put in the entry's place
(``spec.load_control``).
"""
from __future__ import annotations

import gc
import inspect
import time
from typing import Callable

import numpy as np

from . import harness, reference
from .peaks import chip_peaks
from .spec import Cell

# the arguments of ``run_distributed`` the harness passes itself: the
# mesh's one axis is named "shuffle"
_PASSED = ("query", "data", "plan", "mesh", "axis_name", "obs")


def job_settings(cell: Cell) -> dict:
    """The configuration's ``job`` settings, which must all be arguments of
    ``run_distributed``: a cell runs exactly what its configuration states,
    or not at all."""
    from repro.mapreduce import run_distributed

    settings = dict(cell.config.get("job", {}))
    known = set(inspect.signature(run_distributed).parameters) - set(_PASSED)
    unknown = sorted(set(settings) - known)
    if unknown:
        raise ValueError(f"run_distributed has no setting {unknown}; the configuration states it")
    return settings


def _obs_kwargs(entry: Callable, obs) -> dict:
    """``{"obs": obs}`` where ``entry`` takes the facade, else nothing."""
    return {"obs": obs} if "obs" in inspect.signature(entry).parameters else {}


class BatchJoin:
    """The program's public batch entry on a mesh of ``devices``: each
    ``ingest(data)`` plans the job and runs it as one MapReduce round.
    ``obs.tracer`` is on in traced runs; its events are the run's spans."""

    def __init__(self, cell: Cell, devices: list, settings: dict, trace: bool = False):
        from jax.sharding import Mesh

        from repro.core import make_query, plan_shares_skew
        from repro.mapreduce import run_distributed
        from repro.obs import Observability, ObsPolicy

        self.query = make_query({k: tuple(v) for k, v in cell.config["relations"].items()})
        self.q = float(cell.config["q"])
        self.mesh = Mesh(np.array(devices), ("shuffle",))
        self.obs = Observability(ObsPolicy(trace=trace))
        self.plan_kwargs = _obs_kwargs(plan_shares_skew, self.obs)
        self.run_kwargs = {**_obs_kwargs(run_distributed, self.obs), **settings}
        self.jobs = 0

    def ingest(self, data):
        from repro.core import plan_shares_skew
        from repro.mapreduce import run_distributed

        tracer = self.obs.tracer
        tracer.set_batch(self.jobs)  # the harness's index of this job
        self.jobs += 1
        with tracer.span("job.plan", cat="job"):
            plan = plan_shares_skew(self.query, data, q=self.q, **self.plan_kwargs)
        with tracer.span("job.run", cat="job"):
            return run_distributed(self.query, data, plan, mesh=self.mesh, **self.run_kwargs)


def check(cell: Cell, pool: harness.Pool, jobs: list[harness.Batch]) -> tuple[dict, str | None]:
    """Compare every job with the reference.  Returns the counts of
    mismatching fingerprints, of overflowed tuples and of failed jobs, and
    the first failure."""
    shape = reference.JoinShape(cell.config["relations"])
    expected: dict[int, tuple[int, int]] = {}  # by the pool entry's id
    mismatches = overflow = failed = 0
    first_bad = None
    for b in sorted(jobs, key=lambda b: b.index):
        if b.report is None:
            failed += 1
            first_bad = first_bad or f"job {b.index}: {b.error or 'not served'}"
            continue
        data = pool[b.index]
        if id(data) not in expected:
            expected[id(data)] = reference.keyed_fingerprint(shape, data)
        want = expected[id(data)]
        r = b.report
        got = (int(r.count), int(r.checksum))
        bad = got != want
        mismatches += bad
        overflow += int(r.overflow)
        if bad or r.overflow:
            failed += 1
            first_bad = first_bad or (
                f"job {b.index}: (count, checksum) {got} vs {want}, overflow {r.overflow}"
            )
    counts = {
        "fingerprint_mismatches": mismatches,
        "overflow_tuples": overflow,
        "failed_jobs": failed,
    }
    return counts, first_bad


def run_job_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    devices: list,
    t_process: float,
    make_program: Callable | None = None,
    clock: Callable[[], float] = time.perf_counter,
) -> dict:
    """Run one job cell and return the result object of its last line.

    ``make_program(cell, devices, settings, trace)`` gives the object
    whose ``ingest(data)`` runs one job; by default ``BatchJoin``."""
    meter = harness.CompileMeter()
    settings = job_settings(cell)
    pool = harness.Pool(cell, seed, 1, int(cell.mix["pool_batches"]))
    program = (make_program or BatchJoin)(cell, devices, settings, trace)

    warm = harness.Batch(0, "fill", sum(len(r) for r in pool[0].values()))
    harness._ingest(program, pool[0], warm, clock, meter)
    jobs = [warm]
    harness.log(
        f"set-up: 1 warm-up job; backend compiles {meter.compiles} "
        f"({meter.seconds:.3f} s), cache hits {meter.hits}, misses {meter.misses}"
    )

    trace_dir = harness.start_trace() if trace else None
    compiles_before, compile_s_before = meter.compiles, meter.seconds
    setup_s = clock() - t_process
    if warm.error is None:
        t0, measured = harness._closed_loop(program, pool, 1, seconds, clock, meter)
    else:
        t0, measured = clock(), []
    jobs += measured
    window_compiles = meter.compiles - compiles_before
    harness.log(
        f"window: {len(measured)} jobs; backend compiles inside it "
        f"{window_compiles} ({meter.seconds - compile_s_before:.3f} s)"
    )

    peak = harness.peak_bytes(devices)
    view = harness.stop_trace(trace_dir, len(devices)) if trace else None
    spans = harness.obs_spans(program)
    del program
    gc.collect()

    counts, first_bad = check(cell, pool, jobs)
    run = harness.Run(
        cell=cell, setup_s=setup_s,
        window_start=t0, batches=jobs, spans=spans, trace=view,
        peaks=chip_peaks(devices[0].device_kind),
        arity={k: len(v) for k, v in cell.config["relations"].items()},
        sketch_cells=0,
        pool=pool,
    )
    checks = {name: {"value": value, "limit": 0} for name, value in counts.items()}
    return harness.result(
        run, checks, counts["failed_jobs"], first_bad,
        devices=devices, peak=peak, window_compiles=window_compiles, trace=trace,
    )

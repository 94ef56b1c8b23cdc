"""One run of one stream cell: set-up, the measured window, the check, the
result.  The helpers that end a run (the peak memory, the trace, the
result object) serve the job path (``job.py``) as well.

Set-up builds the engine through the public API, makes the batches from
the seed and ingests the warm-up batches, which fill the retained window
and compile every kernel shape the window meets.  The window then offers
batches for ``seconds`` in a closed loop: each batch is offered when the
previous one returns, and the batch in flight at the close completes.

Afterwards every ingested batch is checked against the plain reference
(``reference.WindowReference``): its ``delta_count`` and the window's
``(window_count, window_checksum)``.  The reference runs after the window
and after the device's peak memory has been read, never inside either.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
import types
from typing import Callable

from . import reference, traffic, xtrace
from .peaks import chip_peaks
from .spec import Cell, load_reader

# host spans the harness puts around its own calls; idle gaps are named
# after the innermost one that covers them
ANNOTATIONS = ("window", "ingest")
# the fused ingest kernel's label in a device trace: the Pallas call takes
# the name of the jitted entry
INGEST_KERNEL = "fused_ingest_dense"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Batch:
    index: int
    phase: str  # "fill" (set-up) or "window"
    rows: int
    start: float = 0.0
    done: float | None = None
    report: object | None = None
    error: str | None = None


@dataclasses.dataclass
class TraceView:
    """The device trace of the window, reduced to plain tuples."""

    ops: list  # xtrace.Op of the first device, inside the window
    spans: list  # xtrace.Span the harness annotated
    lo: float
    hi: float
    busy_s: float  # averaged over the devices used
    window_s: float


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""

    cell: Cell
    setup_s: float
    window_start: float
    batches: list[Batch]
    spans: list[dict]  # the engine's own obs spans (traced runs)
    trace: TraceView | None
    peaks: dict
    arity: dict[str, int]
    sketch_cells: int  # Count-Min cells written per sketched column
    pool: "Pool"

    def window(self) -> list[Batch]:
        return [b for b in self.batches if b.phase == "window"]

    def batch_rows(self, index: int) -> dict:
        """Relation name -> the rows of batch ``index``."""
        return self.pool[index]


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def stream_config(cell: Cell, trace: bool):
    """The engine's ``StreamConfig`` from the configuration's ``stream``
    settings, which must all be the engine's: a cell runs exactly what its
    configuration states, or not at all."""
    from repro.stream import ObsPolicy, RetentionPolicy, StreamConfig

    settings = cell.config["stream"]
    unknown = sorted(set(settings) - {f.name for f in dataclasses.fields(StreamConfig)})
    if unknown:
        raise ValueError(f"the engine has no setting {unknown}; the configuration states it")
    return StreamConfig(
        q=traffic.reducer_capacity(cell.config),
        retention=RetentionPolicy(window_batches=traffic.window_batches(cell.config)),
        obs=ObsPolicy(trace=trace, skewscope=trace),
        **settings,
    )


def engine_factory(cell: Cell, config):
    from repro.core import make_query
    from repro.stream import StreamingJoinEngine

    query = make_query({k: tuple(v) for k, v in cell.config["relations"].items()})
    return StreamingJoinEngine(query, config)


class ReferenceStandIn:
    """The control: the reference in the engine's place, with the window's
    guarantee broken — it retains ``late`` batches more than the
    configuration states, as an engine that expires late would."""

    def __init__(self, cell: Cell, window: int, late: int = 1):
        shape = reference.JoinShape(cell.config["relations"])
        self.ref = reference.WindowReference(shape, cell.config["domain"], window + late)

    def ingest(self, batch):
        d, c, s = self.ref.add(batch)
        return types.SimpleNamespace(
            delta_count=d, window_count=c, window_checksum=s,
            comm_tuples={}, replanned=False, migrated_tuples=0, obs=None,
        )


class Pool:
    """The batches of a run, made before the window: the ``fill`` that
    warms up, then ``n_window`` that the window takes in turn, from the
    start again if it outruns them."""

    def __init__(self, cell: Cell, seed: int, fill: int, n_window: int):
        self.fill = fill
        self.n_window = max(1, n_window)
        self.batches = [
            traffic.make_batch(cell.config, cell.key_column, seed, i)
            for i in range(fill + self.n_window)
        ]

    def __getitem__(self, i: int):
        if i < self.fill:
            return self.batches[i]
        return self.batches[self.fill + (i - self.fill) % self.n_window]


def _annotate(name: str, **kw):
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)


def _ingest(engine, batch_data, b: Batch, clock, meter) -> None:
    compiles = meter.compiles
    b.start = clock()
    try:
        with _annotate("ingest", batch=b.index):
            b.report = engine.ingest(batch_data)
    except Exception as e:  # a batch that raises is a failed batch
        b.error = f"{type(e).__name__}: {e}"
    b.done = clock()
    r = b.report
    log(
        f"batch {b.index} ({b.phase}): {b.done - b.start:.3f} s"
        + (f", {meter.compiles - compiles} backend compiles" if meter.compiles > compiles else "")
        + (
            f", replanned={getattr(r, 'replanned', '?')} "
            f"migrated={getattr(r, 'migrated_tuples', '?')} "
            f"max_load={getattr(r, 'max_load', '?')}"
            if r is not None else f", error: {b.error}"
        )
    )


def _closed_loop(engine, pool, first, seconds, clock, meter) -> tuple[float, list[Batch]]:
    out, i = [], first
    t0 = clock()
    with _annotate("window"):
        while clock() - t0 < seconds:
            b = Batch(i, "window", sum(len(r) for r in pool[i].values()))
            _ingest(engine, pool[i], b, clock, meter)
            out.append(b)
            i += 1
            if b.error:
                break
    return t0, out


def _trace_view(log_dir: str, n_devices: int) -> TraceView | None:
    devices, spans = xtrace.read_xplane(xtrace.find_xplane(log_dir), set(ANNOTATIONS))
    windows = [s for s in spans if s.name == "window"]
    if not devices or not windows:
        return None
    lo, hi = windows[-1].start, windows[-1].end
    names = sorted(devices)[:n_devices]
    busy = [xtrace.busy_ns(xtrace.clip(devices[n], lo, hi)) for n in names]
    return TraceView(
        ops=xtrace.clip(devices[names[0]], lo, hi),
        spans=[s for s in spans if s.name != "window"],
        lo=lo, hi=hi,
        busy_s=sum(busy) / len(busy) / 1e9,
        window_s=(hi - lo) / 1e9,
    )


def check(cell: Cell, pool: Pool, batches: list[Batch], window: int) -> dict:
    """Compare every ingested batch with the reference.  Returns the
    counts of mismatches and of failed batches."""
    shape = reference.JoinShape(cell.config["relations"])
    ref = reference.WindowReference(shape, cell.config["domain"], window)
    delta_bad = window_bad = failed = 0
    first_bad = None
    for b in sorted(batches, key=lambda b: b.index):
        if b.report is None:
            failed += 1
            first_bad = first_bad or f"batch {b.index}: {b.error or 'not served'}"
            continue
        d, c, s = ref.add(pool[b.index])
        r = b.report
        bad_d = int(r.delta_count) != d
        bad_w = (int(r.window_count), int(r.window_checksum)) != (c, s)
        delta_bad += bad_d
        window_bad += bad_w
        if bad_d or bad_w:
            failed += 1
            first_bad = first_bad or (
                f"batch {b.index}: delta_count {r.delta_count} vs {d}, window "
                f"({r.window_count}, {r.window_checksum}) vs ({c}, {s})"
            )
    return {
        "delta_count_mismatches": delta_bad,
        "window_fingerprint_mismatches": window_bad,
        "failed": failed,
        "first_bad": first_bad,
    }


def start_trace() -> str:
    """Start the profiler on a fresh directory and return it."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir


def stop_trace(trace_dir: str, n_devices: int) -> TraceView | None:
    """Stop the profiler, reduce its trace and remove the directory."""
    import jax

    jax.profiler.stop_trace()
    view = _trace_view(trace_dir, n_devices)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return view


def obs_spans(program) -> list[dict]:
    """The events of the program's ``repro.obs`` tracer, if it has one."""
    tracer = getattr(getattr(program, "obs", None), "tracer", None)
    return list(getattr(tracer, "events", []) or [])


def peak_bytes(devices: list) -> int:
    """The peak memory in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def result(
    run: Run, checks: dict, failed: int, first_bad: str | None, *,
    devices: list, peak: int, window_compiles: int, trace: bool,
) -> dict:
    """The result object of a run's last line: the cell's metrics read
    from ``run``, and ``checks``, each number compared beside its limit."""
    metrics = {}
    for m in run.cell.per_layer if trace else run.cell.end_to_end:
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = bool(run.batches) and all(c["value"] <= c["limit"] for c in checks.values())
    if first_bad:
        log(f"first mismatch: {first_bad}")
    d0 = devices[0]
    device = {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": correct,
        "attempted": len(run.batches),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    view = run.trace
    if trace and view is not None:
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s
        out["breakdown"] = {
            "device_ops": [[n, t / 1e9] for n, t in xtrace.top_ops(view.ops, 10)],
            "idle_gaps": [
                [n, t / 1e9]
                for n, t in xtrace.idle_gaps(view.ops, view.lo, view.hi, view.spans, 10)
            ],
        }
    out["window_compiles"] = window_compiles
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    devices: list,
    t_process: float,
    make_engine: Callable | None = None,
    clock: Callable[[], float] = time.perf_counter,
) -> dict:
    """Run one stream cell and return the result object of its last line."""
    meter = CompileMeter()
    config = stream_config(cell, trace)
    window = config.retention.window_batches
    pool = Pool(cell, seed, window, int(cell.mix["pool_batches"]))
    engine = (make_engine or engine_factory)(cell, config)

    batches = []
    for i in range(window):  # warm-up: fill the window, compile its shapes
        b = Batch(i, "fill", sum(len(r) for r in pool[i].values()))
        _ingest(engine, pool[i], b, clock, meter)
        batches.append(b)
        if b.error:
            break
    log(
        f"set-up: {window} warm-up batches; backend compiles {meter.compiles} "
        f"({meter.seconds:.3f} s), cache hits {meter.hits}, misses {meter.misses}"
    )

    trace_dir = start_trace() if trace else None
    compiles_before, compile_s_before = meter.compiles, meter.seconds
    setup_s = clock() - t_process
    if batches[-1].error is None:
        t0, measured = _closed_loop(engine, pool, window, seconds, clock, meter)
    else:
        t0, measured = clock(), []
    batches += measured
    window_compiles = meter.compiles - compiles_before
    log(
        f"window: {len(measured)} batches; backend compiles inside it "
        f"{window_compiles} ({meter.seconds - compile_s_before:.3f} s)"
    )

    peak = peak_bytes(devices)
    view = stop_trace(trace_dir, len(devices)) if trace else None

    spans = obs_spans(engine)
    del engine
    gc.collect()

    result_check = check(cell, pool, batches, window)
    run = Run(
        cell=cell, setup_s=setup_s,
        window_start=t0, batches=batches, spans=spans, trace=view,
        peaks=chip_peaks(devices[0].device_kind),
        arity={k: len(v) for k, v in cell.config["relations"].items()},
        sketch_cells=config.sketch_depth * config.sketch_width,
        pool=pool,
    )
    checks = {
        "delta_count_mismatches": {"value": result_check["delta_count_mismatches"], "limit": 0},
        "window_fingerprint_mismatches": {
            "value": result_check["window_fingerprint_mismatches"], "limit": 0,
        },
        "failed_batches": {"value": result_check["failed"], "limit": 0},
    }
    return result(
        run, checks, result_check["failed"], result_check["first_bad"],
        devices=devices, peak=peak, window_compiles=window_compiles, trace=trace,
    )

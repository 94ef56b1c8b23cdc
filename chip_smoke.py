"""Smoke run of the streaming SharesSkew engine on TPU chips.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the all_to_all shuffle only

It drives the engine through the same public entry points
``examples/streaming_join.py`` uses (``repro.stream``, ``repro.mapreduce``)
and checks every result against a host reference:

  * phase A — parity at bench size: the drifting-Zipf stream of
    ``benchmarks/bench_stream.py`` (1,500 R + 400 S rows, 8 batches, drift
    at batch 4) through the baseline and the fused engine; their
    ``BatchReport``s must be equal and every batch must take the fused
    kernel path;
  * phase B — real size: ``two_way()`` with ``fused_ingest=True`` and a
    4-batch window, 8 batches of 131,072 R + 131,072 S rows over a 2^20 key
    domain, Zipf a = 2.0 moving to 1.4 on a shifted hot range at batch 4.
    Every batch's ``delta_count`` and window fingerprint must equal the
    per-key reference, and a drift replan must fire;
  * ``--four-chips`` — the batch path's MapReduce shuffle (``all_to_all``
    over an explicit 4-device mesh) on the paper's 2-way and 3-way
    workloads, each equal to the per-key reference and to the same call on
    a 1-device mesh, with no capacity overflow.

It requires a TPU and exits non-zero on anything else, JAX's quiet fallback
to the CPU included.  Times it prints are smoke timings, not a benchmark.
No phase's failure is caught: any mismatch exits non-zero, and only a run
in which every phase passed prints the last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def require_tpu(count: int):
    """The devices, when JAX sees at least ``count`` TPUs; else exit."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {d0.platform!r} "
            f"({d0.device_kind})"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} TPU devices, found {len(devices)}"
        )
    log(
        f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}"
    )
    return devices


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def zipf_batch(rng, shift, n_r, n_s, domain, a):
    """One two_way() batch: Zipf(a) join column B moved by ``shift``, the
    other columns uniform (the ``bench_stream`` generator)."""
    b_r = ((rng.zipf(a, n_r) - 1) + shift) % domain
    b_s = ((rng.zipf(a, n_s) - 1) + shift) % domain
    r = np.stack([rng.integers(0, domain, n_r), b_r], 1).astype(np.int64)
    s = np.stack([b_s, rng.integers(0, domain, n_s)], 1).astype(np.int64)
    return {"R": r, "S": s}


def phase_a() -> None:
    """Fused vs baseline engine on the bench stream: equal reports."""
    from repro.core import two_way
    from repro.mapreduce import oracle_join
    from repro.stream import StreamConfig, StreamingJoinEngine

    rng = np.random.default_rng(0)
    batches = []
    for i in range(8):
        shift, a = (0, 2.0) if i < 4 else (1300, 1.4)
        batches.append(zipf_batch(rng, shift, 1500, 400, 4000, a))
    engines = {}
    for name, fused in (("baseline", False), ("fused", True)):
        eng = StreamingJoinEngine(
            two_way(),
            StreamConfig(q=120, decay=0.5, load_factor=2.0, fused_ingest=fused),
        )
        t0 = time.perf_counter()
        for batch in batches:
            eng.ingest(batch)
        log(
            f"[smoke timing] phase A {name}: 8 batches in "
            f"{time.perf_counter() - t0:.3f} s"
        )
        engines[name] = eng
    base, fused = engines["baseline"], engines["fused"]
    for i, (rb, rf) in enumerate(zip(base.reports, fused.reports)):
        check(rb == rf, f"phase A batch {i}: fused report != baseline")
    check(
        fused.fused_batches == len(batches),
        f"phase A: fused kernel ran on {fused.fused_batches}/{len(batches)} "
        "batches",
    )
    count, checksum, _, _ = oracle_join(two_way(), fused.history_data())
    check(
        (fused.total_count, fused.total_checksum) == (count, checksum),
        "phase A: fused engine != oracle_join",
    )
    log(
        f"phase A ok: {len(batches)} equal reports, count={count} "
        f"checksum={checksum} replans={base.replan_count}"
    )


def phase_b(
    meter: CompileMeter,
    n_rows: int = 131_072,
    n_batches: int = 8,
    drift_at: int = 4,
    domain: int = 1 << 20,
    q: float = 8192,
    window: int = 4,
    seed: int = 0,
) -> None:
    """The fused engine at a real stream size vs the per-key reference."""
    from repro.core import two_way
    from repro.mapreduce import keyed_join_fingerprint
    from repro.stream import RetentionPolicy, StreamConfig, StreamingJoinEngine

    query = two_way()
    eng = StreamingJoinEngine(
        query,
        StreamConfig(
            q=q, decay=0.5, load_factor=2.0, fused_ingest=True,
            retention=RetentionPolicy(window_batches=window),
        ),
    )
    rng = np.random.default_rng(seed)
    history = []

    def fingerprint(batches):
        if not batches:
            return 0, 0
        data = {nm: np.concatenate([b[nm] for b in batches]) for nm in ("R", "S")}
        return keyed_join_fingerprint(query, data)

    for i in range(n_batches):
        shift, a = (0, 2.0) if i < drift_at else (domain // 2 + 12_345, 1.4)
        batch = zipf_batch(rng, shift, n_rows, n_rows, domain, a)
        compile_before = meter.seconds
        t0 = time.perf_counter()
        report = eng.ingest(batch)
        wall = time.perf_counter() - t0
        history.append(batch)
        kept = history[-window:]
        w_count, w_checksum = fingerprint(kept)
        prev_count, _ = fingerprint(kept[:-1])
        log(
            f"[smoke timing] phase B batch {i}: ingest {wall:.3f} s, "
            f"of which backend compile {meter.seconds - compile_before:.3f} s; "
            f"replanned={report.replanned} trigger={report.drift_trigger!r} "
            f"delta_count={report.delta_count} "
            f"window_count={report.window_count} "
            f"comm={report.total_comm} max_load={report.max_load}"
        )
        check(
            report.delta_count == w_count - prev_count,
            f"phase B batch {i}: delta_count {report.delta_count} != "
            f"reference {w_count - prev_count}",
        )
        check(
            (report.window_count, report.window_checksum)
            == (w_count, w_checksum),
            f"phase B batch {i}: window fingerprint "
            f"({report.window_count}, {report.window_checksum}) != "
            f"reference ({w_count}, {w_checksum})",
        )
    drift_replans = sum(r.replanned for r in eng.reports[1:])
    check(drift_replans >= 1, "phase B: no drift replan fired")
    check(
        eng.fused_batches == n_batches,
        f"phase B: fused kernel ran on {eng.fused_batches}/{n_batches} batches",
    )
    log(
        f"phase B ok: {n_batches} batches of {n_rows} + {n_rows} rows match "
        f"the per-key reference; drift replans={drift_replans}; "
        f"reducers={eng.plan.total_reducers}"
    )


def four_chips(devices, n_2way: int = 200_000, n_3way: int = 10_000) -> None:
    """The all_to_all shuffle on 4 devices vs 1 device vs the reference:
    ``paper_2way`` with ``n_2way`` R and a tenth as many S rows, and
    ``paper_3way`` with ``n_3way`` rows per relation."""
    from jax.sharding import Mesh

    from repro.core import plan_shares_skew, three_way_paper, two_way
    from repro.data import paper_2way, paper_3way
    from repro.mapreduce import keyed_join_fingerprint, run_distributed

    meshes = {
        nd: Mesh(np.array(devices[:nd]), ("shuffle",)) for nd in (4, 1)
    }
    rng = np.random.default_rng(0)
    cases = (
        ("paper_2way", two_way(),
         paper_2way(rng, n_r=n_2way, n_s=n_2way // 10, domain=5 * n_2way),
         2000),
        ("paper_3way", three_way_paper(),
         paper_3way(rng, n=n_3way, domain=12 * n_3way), 500),
    )
    for name, query, data, q in cases:
        plan = plan_shares_skew(query, data, q=q)
        ref = keyed_join_fingerprint(query, data)
        results = {}
        for nd, mesh in meshes.items():
            t0 = time.perf_counter()
            res = run_distributed(
                query, data, plan, mesh=mesh, cap_factor=4.0,
                route_cap_factor=4.0,
            )
            wall = time.perf_counter() - t0
            g = -(-plan.total_reducers // nd)
            per_device = [
                int(res.reducer_loads[d * g:(d + 1) * g].sum()) for d in range(nd)
            ]
            log(
                f"[smoke timing] {name} on {nd} device(s): {wall:.3f} s "
                f"(compile included); count={res.count} "
                f"checksum={res.checksum} overflow={res.overflow} "
                f"per-device loads={per_device}"
            )
            check(res.overflow == 0, f"{name}/{nd}: {res.overflow} overflowed")
            check(
                (res.count, res.checksum) == ref,
                f"{name}/{nd}: ({res.count}, {res.checksum}) != reference {ref}",
            )
            results[nd] = res
        check(
            results[4].comm_tuples == results[1].comm_tuples,
            f"{name}: shuffle volume differs between 4 and 1 devices",
        )
        log(
            f"{name} ok: rows={ {k: len(v) for k, v in data.items()} } "
            f"q={q} reducers={plan.total_reducers} count={ref[0]} "
            f"checksum={ref[1]}, equal on 4 and 1 devices"
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the all_to_all shuffle on a 4-device mesh",
    )
    args = parser.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)

    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    if args.four_chips:
        four_chips(devices)
    else:
        phase_a()
        phase_b(meter)
    log(
        f"compile: backend {meter.seconds:.3f} s, persistent cache "
        f"hits={meter.hits} misses={meter.misses}"
    )
    d0 = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

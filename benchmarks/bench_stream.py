"""Streaming SharesSkew (DESIGN.md §6-§7): drifting Zipf stream, drift-
triggered replanning, fused vs baseline ingest.

The workload shifts the Zipf mode of the join attribute mid-run.  Two
engines consume the *same* pre-generated batches:

  * baseline — sketch, ``map_phase`` routing, and the einsum delta join as
    separate eager passes (the correctness oracle);
  * fused    — the single-pass Pallas ingest kernel (``kernels.
    ingest_fused``) plus the sorted merge-join delta (DESIGN.md §7).

Tracked:

  * cumulative new-tuple shuffle volume vs an exact-HH replan-every-batch
    oracle (acceptance: ratio <= 1.25, identical for both engines);
  * number of drift-triggered replans and migrated state;
  * per-batch ingest wall time for both paths, the fused speedup (hard
    gate: fused median must be >= 10x faster than the 852 ms baseline
    median recorded at PR 5), and the modeled DMA/compute overlap profile
    of the fused kernel;
  * bounded state (DESIGN.md §8): a third engine runs the same batches
    under windowed retention + admission accounting — peak carried state
    must drop below the unbounded engine's, the window fingerprint must
    equal the oracle on the retained suffix, and the retention/shed
    counters land in the ``bounded`` sub-record;
  * reducer-loss recovery (DESIGN.md §5): a fourth engine runs with the
    host model on and one host killed mid-run by the fault injector —
    recovery must complete at the kill boundary itself (no checkpoint
    restore), replay no more than the lost reducers' retained-window
    share, and verify the window fingerprint; the ``recovery`` sub-record
    tracks the boundary wall time and replay volume;
  * replan boundaries (DESIGN.md §7): the dense route encoding keeps the
    fused kernel's padded shapes static across replans, so a replan batch
    must NOT pay a kernel recompile — ``replan_compile_us`` records the
    replan-boundary overhead over the steady-state median (planning +
    migration only), with a hard 1 s ceiling per replan batch;
  * multi-tenant (DESIGN.md §9): a ``MultiQueryEngine`` runs 3 copies of
    the query over the same batches — every tenant must stay bit-identical
    to the solo run with ZERO private sketch passes (the shared pass runs
    once per relation batch), and a weighted fair-share run with an
    injected overload burst must shed ONLY the offending tenant; the
    ``tenancy`` sub-record tracks isolation overhead vs N separate
    engines, sketch-sharing savings, and the per-tenant shed counters;
  * observability (DESIGN.md §10): a fifth engine repeats the fused run
    with tracing + metrics + skewscope all on — it must stay bit-identical
    to the plain fused run and its median ingest overhead must stay under
    2% (``obs.overhead_pct`` in the sub-record, alongside the span
    taxonomy, the per-reducer skew snapshot, and the replan triggers).

``BENCH_stream.json`` (all fields documented in BENCHMARKS.md) records the
trajectory run over run.  The fused engine counts its kernel passes; this
bench fails loudly if that counter ever disagrees with the batch count —
there is no silent fallback path, and this assertion keeps it that way.
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np

from repro.core import plan_shares_skew, two_way
from repro.kernels.ingest_fused import overlap_profile, route_width
from repro.mapreduce import oracle_join, predicted_comm
from repro.mapreduce.keys import static_route_table
from repro.stream import (
    AdmissionPolicy,
    MultiQueryEngine,
    RecoveryPolicy,
    RetentionPolicy,
    ObsPolicy,
    StreamConfig,
    StreamingJoinEngine,
    TenancyPolicy,
    TenantSpec,
    replication_width,
)
from repro.testing import FaultInjector, FaultSpec

from .common import emit

# the bench-host gate: PR 5 recorded median_ingest_us = 852574 on this
# workload; the fused path must beat it by >= 10x
RECORDED_BASELINE_US = 852_574.0
FUSED_GATE_US = RECORDED_BASELINE_US / 10.0


def _zipf_batch(rng, shift, n_r, n_s, domain, a=1.6):
    b_r = ((rng.zipf(a, n_r) - 1) + shift) % domain
    b_s = ((rng.zipf(a, n_s) - 1) + shift) % domain
    r = np.stack([rng.integers(0, domain, n_r), b_r], 1).astype(np.int64)
    s = np.stack([b_s, rng.integers(0, domain, n_s)], 1).astype(np.int64)
    return {"R": r, "S": s}


def _median(xs: list[float]) -> float:
    return sorted(xs)[len(xs) // 2]


def main(out_json: str | None = "BENCH_stream.json") -> None:
    rng = np.random.default_rng(0)
    query = two_way()
    n_r, n_s, domain = 1500, 400, 4000
    n_batches, shift_at = 8, 4
    rows_per_batch = n_r + n_s

    # identical batches for both engines: the drift moves both the Zipf
    # exponent and the heavy values' location mid-run
    batches = []
    for i in range(n_batches):
        shift, a = (0, 2.0) if i < shift_at else (1300, 1.4)
        batches.append(_zipf_batch(rng, shift, n_r, n_s, domain, a=a))

    def run(config: StreamConfig):
        eng = StreamingJoinEngine(query, config)
        us = []
        for batch in batches:
            t0 = time.perf_counter()
            eng.ingest(batch)
            us.append((time.perf_counter() - t0) * 1e6)
        return eng, us

    base, base_us = run(StreamConfig(q=120, decay=0.5, load_factor=2.0))
    fused, fused_us = run(
        StreamConfig(q=120, decay=0.5, load_factor=2.0, fused_ingest=True)
    )

    # ---- correctness gates -------------------------------------------------
    count, checksum, _, _ = oracle_join(query, base.history_data())
    assert (base.total_count, base.total_checksum) == (count, checksum), (
        "streaming engine != concatenated oracle"
    )
    assert (fused.total_count, fused.total_checksum) == (count, checksum), (
        "fused engine != concatenated oracle"
    )
    for i, (rb, rf) in enumerate(zip(base.reports, fused.reports)):
        assert rb == rf, f"fused batch {i} report diverges from baseline"
    assert fused.fused_batches == n_batches, (
        f"fused engine ran the kernel on {fused.fused_batches}/{n_batches} "
        "batches — the fused path silently fell back"
    )

    oracle_comm = 0
    for batch in batches:
        oracle_plan = plan_shares_skew(query, batch, q=120)
        oracle_comm += sum(predicted_comm(oracle_plan).values())
    ratio = base.cumulative_comm / max(1, oracle_comm)
    assert ratio <= 1.25, f"comm ratio {ratio:.3f} exceeds 1.25x oracle"
    assert base.replan_count >= 1, "no drift replan fired on the shifted stream"

    # ---- perf gate ---------------------------------------------------------
    base_med, fused_med = _median(base_us), _median(fused_us)
    speedup = base_med / fused_med
    assert fused_med < FUSED_GATE_US, (
        f"fused median ingest {fused_med / 1e3:.1f} ms misses the 10x gate "
        f"({FUSED_GATE_US / 1e3:.1f} ms) vs the recorded "
        f"{RECORDED_BASELINE_US / 1e3:.0f} ms baseline"
    )

    # replan boundaries: with the dense route encoding the compiled kernel
    # survives replans, so a replan batch is planning + migration only —
    # not the multi-second recompile spike PR 8's BENCH_stream recorded
    replan_ix = [
        i for i, r in enumerate(fused.reports) if r.replanned and i > 0
    ]
    steady_us = [
        u for i, u in enumerate(fused_us) if i > 0 and i not in replan_ix
    ]
    steady_med = _median(steady_us)
    replan_compile_us = (
        max(0.0, _median([fused_us[i] for i in replan_ix]) - steady_med)
        if replan_ix
        else 0.0
    )
    for i in replan_ix:
        assert fused_us[i] < 1_000_000, (
            f"replan batch {i} took {fused_us[i] / 1e3:.0f} ms — the fused "
            "kernel recompiled at a replan boundary"
        )

    # ---- observability overhead (DESIGN.md §10) ----------------------------
    # the same fused run with every obs surface on (tracing + metrics +
    # skewscope).  The kernels are warm by now (identical shapes), so the
    # median delta over the plain fused run is the obs tax itself — gated
    # at < 2% so the layer stays always-on-able
    obs_eng, obs_us = run(
        StreamConfig(
            q=120, decay=0.5, load_factor=2.0, fused_ingest=True,
            obs=ObsPolicy(trace=True, metrics=True, skewscope=True),
        )
    )
    assert (obs_eng.total_count, obs_eng.total_checksum) == (count, checksum), (
        "obs-enabled engine diverged from the oracle — instrumentation "
        "touched the data path"
    )
    for i, (rf, ro) in enumerate(zip(fused.reports, obs_eng.reports)):
        assert rf == ro, f"obs-enabled batch {i} report diverges from fused"
    obs_med = _median(obs_us)
    obs_overhead_pct = (obs_med - fused_med) / fused_med * 100.0
    assert obs_overhead_pct < 2.0, (
        f"tracing+metrics added {obs_overhead_pct:.2f}% to the fused median "
        "ingest — the observability layer is no longer cheap"
    )
    chrome = obs_eng.obs.tracer.to_chrome()
    skew_snapshot = obs_eng.skew_report()
    obs_metrics = obs_eng.obs.metrics.snapshot()
    replan_triggers = [
        {
            "batch": r.batch,
            "trigger": r.drift_trigger,
            "observed": r.drift_observed,
            "threshold": r.drift_threshold,
        }
        for r in obs_eng.reports
        if r.replanned
    ]

    # ---- bounded state (DESIGN.md §8) --------------------------------------
    # same batches under windowed retention + admission: carried state must
    # flatten (vs the unbounded engine's monotonic growth) and the window
    # fingerprint must stay exact on the retained suffix
    bounded, _ = run(
        StreamConfig(
            q=120, decay=0.5, load_factor=2.0, fused_ingest=True,
            retention=RetentionPolicy(window_batches=3),
            admission=AdmissionPolicy(headroom=50.0),  # accounting on, no throttle
        )
    )
    w_count, w_checksum, _, _ = oracle_join(query, bounded.history_data())
    assert (bounded.window_count, bounded.window_checksum) == (
        w_count, w_checksum,
    ), "bounded engine window fingerprint != oracle on retained suffix"
    assert bounded.expired_batches == n_batches - 3
    peak_carried_bounded = max(r.carried_tuples for r in bounded.reports)
    peak_carried_unbounded = max(r.carried_tuples for r in base.reports)
    assert peak_carried_bounded < peak_carried_unbounded, (
        "retention failed to bound carried state"
    )

    # ---- reducer-loss recovery (DESIGN.md §5) ------------------------------
    # same batches again with the host model on and a host killed mid-run:
    # recovery must run at the batch boundary (no checkpoint restore),
    # replay exactly the lost reducers' retained-window share, and keep
    # the window fingerprint exact
    kill_batch = shift_at + 1
    inj = FaultInjector(
        [FaultSpec(kind="host_loss", target="host", host_id=2,
                   batch=kill_batch)]
    )
    rec_eng = StreamingJoinEngine(
        query,
        StreamConfig(
            q=120, decay=0.5, load_factor=2.0,
            retention=RetentionPolicy(window_batches=3),
            recovery=RecoveryPolicy(n_hosts=8),
        ),
    )
    rec_eng.arm_faults(inj)
    recovery_us = 0.0
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        rec_eng.ingest(batch)
        if i == kill_batch:  # the boundary that detected + recovered
            recovery_us = (time.perf_counter() - t0) * 1e6
    inj.assert_all_resolved()
    assert len(rec_eng.recoveries) == 1, "host loss was not recovered"
    rec = rec_eng.recoveries[0]
    assert rec.verified, "recovered state failed fingerprint verification"
    assert rec.replayed_tuples <= rec.lost_share_tuples
    r_count, r_checksum, _, _ = oracle_join(query, rec_eng.history_data())
    assert (rec_eng.window_count, rec_eng.window_checksum) == (
        r_count, r_checksum,
    ), "post-recovery window fingerprint != oracle"

    # ---- multi-tenant (DESIGN.md §9) ---------------------------------------
    # 3 tenants over the same batches.  Reference: N separate engines (the
    # sharing-free deployment).  Contracts: every tenant bit-identical to
    # the solo run, zero private sketch passes, and the shared pass count
    # equals (sketch columns) x (batches) — computed once, absorbed N times.
    n_tenants = 3
    t_cfg = StreamConfig(q=120, decay=0.5, load_factor=2.0, fused_ingest=True)
    solo_runs = [run(t_cfg) for _ in range(n_tenants)]
    solo_engines = [e for e, _ in solo_runs]
    # per shared batch, the reference cost is the SUM over the N engines;
    # medians keep one-off compile spikes out of the overhead ratio (the
    # multi-tenant run compiles a sketch-off kernel variant on batch 0)
    solo_batch_us = [
        sum(us[i] for _, us in solo_runs) for i in range(n_batches)
    ]
    solo_wall_us = sum(solo_batch_us)
    solo_med_us = _median(solo_batch_us)
    solo_private_passes = sum(
        e.sketch_ingest_calls for e in solo_engines
    )

    mq = MultiQueryEngine(
        [TenantSpec(f"t{i}", query, t_cfg) for i in range(n_tenants)],
        TenancyPolicy(),
    )
    mq_batch_us = []
    for batch in batches:
        t0 = time.perf_counter()
        mq.ingest(batch)
        mq_batch_us.append((time.perf_counter() - t0) * 1e6)
    mq_wall_us = sum(mq_batch_us)
    mq_med_us = _median(mq_batch_us)
    for i in range(n_tenants):
        eng = mq.engine(f"t{i}")
        assert (eng.total_count, eng.total_checksum) == (count, checksum), (
            f"tenant t{i} diverged from the solo engine"
        )
        assert eng.sketch_ingest_calls == 0, (
            f"tenant t{i} computed {eng.sketch_ingest_calls} private sketch "
            "passes — sketch sharing silently fell back"
        )
    n_sketch_cols = 2  # (B, R) and (B, S): one shared signature group
    assert mq.shared_sketch_passes == n_sketch_cols * n_batches, (
        f"shared sketch ran {mq.shared_sketch_passes} column passes, "
        f"expected {n_sketch_cols * n_batches} (once per relation batch)"
    )
    isolation_overhead = mq_med_us / solo_med_us
    assert isolation_overhead < 1.5, (
        f"multi-tenant median batch {isolation_overhead:.2f}x the "
        "N-separate-engines reference — tenancy bookkeeping is no longer cheap"
    )

    # weighted fair-share under an injected overload burst: capacity is
    # raised operator-style to 1.5x the observed steady demand right before
    # the burst batch, so normal load fits and ONLY the burst is over
    overload_batch = shift_at + 2
    fmq = MultiQueryEngine(
        [
            TenantSpec(f"f{i}", query, t_cfg, weight=2.0 if i == 0 else 1.0)
            for i in range(n_tenants)
        ],
        TenancyPolicy(),
    )
    inj2 = FaultInjector(
        [FaultSpec(kind="tenant_overload", target="tenant", tenant="f2",
                   batch=overload_batch, rel="R", rows=6000)]
    )
    fmq.arm_faults(inj2)
    for i, batch in enumerate(batches):
        if i == overload_batch:
            demand = sum(
                len(batch[rel.name])
                * replication_width(fmq.engine(nm).plan, rel.name)
                for nm in fmq.serving()
                for rel in query.relations
            )
            fmq.fair.capacity = 1.5 * demand
        fmq.ingest(batch)
        if i == overload_batch:
            fmq.fair.capacity = None
    inj2.assert_all_resolved()
    shed = dict(fmq.fair.overload_shed)
    assert shed["f2"] > 0, "the overloaded tenant was never shed"
    assert shed["f0"] == 0 and shed["f1"] == 0, (
        f"overload on f2 shed a well-behaved neighbor: {shed}"
    )
    for nm in ("f0", "f1"):
        eng = fmq.engine(nm)
        assert (eng.total_count, eng.total_checksum) == (count, checksum), (
            f"tenant {nm} perturbed by f2's overload burst"
        )
    contained = inj2.report().contained

    # modeled roofline of the fused pass under the final plan (R relation),
    # on the chip the kernel targets: a model, so no device is asked
    rel = query.relations[0]
    profile = overlap_profile(
        n_rows=n_r,
        arity=rel.arity,
        route_w=route_width(static_route_table(fused.plan, rel)),
        num_reducers=fused.plan.total_reducers,
        n_sketch_cols=1,
        depth=fused.config.sketch_depth,
        width=fused.config.sketch_width,
        device_kind="TPU v5 lite",
        block=fused.config.fused_block,
    )

    emit("stream_comm_ratio_vs_oracle", ratio * 1000,
         f"engine={base.cumulative_comm};oracle={oracle_comm};x1000")
    emit("stream_replans", base.replan_count,
         f"migrated={base.total_migrated};epochs={base.plan_epoch + 1}")
    emit("stream_ingest_wall", base_med,
         f"batches={n_batches};total_count={base.total_count}")
    emit("stream_fused_ingest_wall", fused_med,
         f"speedup={speedup:.1f}x;vs_recorded="
         f"{RECORDED_BASELINE_US / fused_med:.1f}x")
    emit("stream_bounded_peak_carried", peak_carried_bounded,
         f"unbounded={peak_carried_unbounded};"
         f"window={bounded.config.retention.window_batches};"
         f"expired={bounded.expired_batches}")
    emit("stream_bounded_shed", bounded.total_shed,
         f"deferred={bounded.total_deferred};"
         f"retracted={bounded.total_retracted}")
    emit("stream_recovery_wall", recovery_us,
         f"mode={rec.mode};replayed={rec.replayed_tuples};"
         f"lost_reducers={rec.lost_reducers};verified={rec.verified}")
    emit("stream_replan_compile", replan_compile_us,
         f"steady_median={steady_med:.0f}us;replans={len(replan_ix)}")
    emit("stream_obs_overhead", obs_overhead_pct * 1000,
         f"obs_median={obs_med:.0f}us;fused_median={fused_med:.0f}us;"
         f"spans={len(chrome['traceEvents'])};x1000")
    emit("stream_tenancy_overhead", isolation_overhead * 1000,
         f"tenants={n_tenants};shared_passes={mq.shared_sketch_passes};"
         f"private_avoided={solo_private_passes};x1000")
    emit("stream_tenancy_shed", shed["f2"],
         f"neighbors={shed['f0']}+{shed['f1']};contained={contained}")
    for i, (bu, fu) in enumerate(zip(base_us, fused_us)):
        replanned = base.reports[i].replanned
        print(f"# batch {i}: baseline {bu / 1e3:8.1f} ms  "
              f"fused {fu / 1e3:8.1f} ms"
              f"{'  [replan]' if replanned else ''}")

    if out_json:
        record = {
            "bench": "stream",
            "batches": n_batches,
            "rows_per_batch": {"R": n_r, "S": n_s},
            "comm_ratio_vs_oracle": ratio,
            "engine_comm": base.cumulative_comm,
            "oracle_comm": oracle_comm,
            "replans": base.replan_count,
            "migrated_tuples": base.total_migrated,
            # wall-clock AND per-row-normalized medians for both paths: the
            # per-row figures stay comparable if the workload shape changes
            "median_ingest_us": base_med,
            "median_ingest_ns_per_row": base_med * 1e3 / rows_per_batch,
            "fused_median_ingest_us": fused_med,
            "fused_median_ingest_ns_per_row": fused_med * 1e3 / rows_per_batch,
            "fused_speedup": speedup,
            "fused_speedup_vs_recorded": RECORDED_BASELINE_US / fused_med,
            "fused_batches": fused.fused_batches,
            # replan boundaries with the dense route encoding: overhead of
            # a replan batch over steady state (planning + migration; a
            # recompile here trips the 1 s assertion instead of landing
            # silently in this field)
            "fused_steady_median_us": steady_med,
            "replan_compile_us": replan_compile_us,
            "replan_batches": replan_ix,
            "ingest_us_trend": [
                {
                    "batch": i,
                    "baseline_us": bu,
                    "fused_us": fu,
                    "replanned": base.reports[i].replanned,
                }
                for i, (bu, fu) in enumerate(zip(base_us, fused_us))
            ],
            "overlap_profile": profile,
            "bounded": {
                "window_batches": bounded.config.retention.window_batches,
                "admission_headroom": bounded.config.admission.headroom,
                "peak_carried_tuples": peak_carried_bounded,
                "peak_carried_tuples_unbounded": peak_carried_unbounded,
                "final_carried_tuples": bounded.reports[-1].carried_tuples,
                "max_carried_per_reducer": max(
                    r.max_carried for r in bounded.reports
                ),
                "expired_batches": bounded.expired_batches,
                "retracted_results": bounded.total_retracted,
                "deferred_rows": bounded.total_deferred,
                "shed_rows": bounded.total_shed,
                "window_count": bounded.window_count,
                "window_fingerprint_verified": True,  # asserted above
            },
            "recovery": {
                "n_hosts": rec_eng.config.recovery.n_hosts,
                "kill_batch": kill_batch,
                "mode": rec.mode,
                "lost_hosts": list(rec.lost_hosts),
                "lost_reducers": rec.lost_reducers,
                "batches_to_recover": 1,  # detected + repaired at the
                #                           kill boundary itself
                "batches_replayed": rec.batches_replayed,
                "replayed_tuples": rec.replayed_tuples,
                "lost_share_tuples": rec.lost_share_tuples,
                "recovery_boundary_us": recovery_us,
                "survivors": rec.survivors,
                "fingerprint_verified": rec.verified,  # also asserted above
            },
            "tenancy": {
                "tenants": n_tenants,
                "isolation_overhead": isolation_overhead,
                "mq_median_batch_us": mq_med_us,
                "solo_median_batch_us": solo_med_us,
                "mq_wall_us": mq_wall_us,
                "solo_wall_us": solo_wall_us,
                "shared_sketch_passes": mq.shared_sketch_passes,
                "private_sketch_passes_avoided": solo_private_passes,
                "tenants_bit_identical": True,  # asserted above
                "overload_batch": overload_batch,
                "overload_shed_rows": shed,
                "fair_weights": {"f0": 2.0, "f1": 1.0, "f2": 1.0},
                "contained_faults": contained,
            },
            "obs": {
                # overhead of trace+metrics+skewscope over the plain fused
                # run (same warm kernels) — gated < 2% above
                "overhead_pct": obs_overhead_pct,
                "obs_median_ingest_us": obs_med,
                "fused_median_ingest_us": fused_med,
                "trace_events": len(chrome["traceEvents"]),
                "span_names": sorted(obs_eng.obs.tracer.span_names()),
                "metric_series": {
                    kind: len(series)
                    for kind, series in obs_metrics.items()
                },
                "skew": skew_snapshot.as_dict(),
                "replan_triggers": replan_triggers,
            },
            "total_count": base.total_count,
            "replan_reasons": [
                r.drift_reason for r in base.reports if r.replanned and r.batch > 0
            ],
        }
        path = pathlib.Path(out_json)
        path.write_text(json.dumps(record, indent=2) + "\n")
        print(f"# wrote {path}")


if __name__ == "__main__":
    main()

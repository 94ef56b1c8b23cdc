"""Chip benchmark of the SharesSkew join: the streaming engine, or batch jobs.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with TPU chips.  The cell is
an entry of ``workloads`` in ``BENCHMARK.json``; ``--trace 0`` reports its
end-to-end metrics, ``--trace 1`` its per-layer metrics from a traced run.
A cell whose configuration says ``"path": "job"`` runs the batch join in
whole jobs on the cell's chips (``chipbench/job.py``); one without the key
runs the streaming engine (``chipbench/harness.py``).  ``--control
<name>`` puts the control in the program's place, and the check must
then fail: on a stream cell ``late_expiry``, on a job cell the program
of ``controls/<name>.py``.
The last line of standard output is one JSON object; the numbers of the
correctness check, each beside its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--control", default=None,
        help="stream cell: late_expiry, the reference with its window one "
        "batch late in the engine's place; job cell: a file of controls/",
    )
    args = parser.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from chipbench import harness, job
    from chipbench.spec import control_file, load_cell, load_control

    cell = load_cell(args.workload)
    path = cell.config.get("path")
    if path not in (None, "job"):
        harness.log(f"configuration {cell.config['name']!r} states an unknown path {path!r}")
        return 2
    if path == "job" and args.control and not control_file(args.control).is_file():
        harness.log(f"no control {args.control!r} of the job path for the job cell {cell.name}")
        return 2
    if path is None and args.control not in (None, "late_expiry"):
        harness.log(f"--control {args.control} is not a control of the stream cell {cell.name}")
        return 2

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"needs a TPU; JAX found {devices[0].platform!r} ({devices[0].device_kind})")
        return 2
    if len(devices) < cell.chips:
        harness.log(f"needs {cell.chips} TPU chips, found {len(devices)}")
        return 2
    devices = devices[: cell.chips]

    harness.log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {cache_dir}")
    make_engine = None
    if args.control == "late_expiry":
        def make_engine(cell, config):
            return harness.ReferenceStandIn(cell, config.retention.window_batches)

    if path == "job":
        out = job.run_job_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            devices=devices, t_process=T_PROCESS,
            make_program=load_control(args.control) if args.control else None,
        )
    else:
        out = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            devices=devices, t_process=T_PROCESS, make_engine=make_engine,
        )
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section banners on
stderr-free stdout comments).  Mapping to the paper:

  bench_2way           -> Fig 1(a)/(b)  (naive vs SharesSkew, 2-way)
  bench_2way_scaling   -> Fig 2         (shuffle volume ~ 2*sqrt(krs))
  bench_3way           -> Fig 3 / §9.2  (Shares vs SharesSkew, 3-way)
  bench_closed_forms   -> §8.1-8.3, §7.3 (chains, symmetric, lower bound)
  bench_moe_skew       -> beyond-paper  (SharesSkew expert dispatch)
  bench_stream         -> beyond-paper  (streaming engine, BENCH_stream.json)
  roofline             -> §Roofline     (from dry-run artifacts)
"""
from __future__ import annotations

import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_2way,
        bench_2way_scaling,
        bench_3way,
        bench_closed_forms,
        bench_moe_skew,
        bench_stream,
        roofline,
    )

    print("name,us_per_call,derived")
    failures = []
    for mod in (
        bench_2way,
        bench_2way_scaling,
        bench_3way,
        bench_closed_forms,
        bench_moe_skew,
        bench_stream,
        roofline,
    ):
        name = mod.__name__.split(".")[-1]
        print(f"# --- {name} ---")
        try:
            mod.main()
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()

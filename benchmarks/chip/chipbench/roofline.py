"""The bytes the fused ingest must move for one relation's batch.

The count is of the work, not of the kernel's layout, so it reads the
same whatever implements the pass:

* read every tuple once: ``4 · rows · arity`` (int32 cells);
* write each emission's reducer id and its rank within that reducer:
  ``8 · emissions`` (the pack plan; padded route columns do not count);
* write the Count-Min increment of each sketched column:
  ``4 · columns · depth · width``.

The per-reducer counts (a few hundred int32) are left out.  The chip's
least time for the pass is these bytes over its HBM bandwidth; the ingest
does a handful of integer operations per byte, so bandwidth bounds it.
"""
from __future__ import annotations


def ingest_bytes(rows: int, arity: int, emissions: int, sketch_cells: int) -> int:
    return 4 * rows * arity + 8 * emissions + 4 * sketch_cells


def roofline_share(bytes_moved: float, seconds: float, hbm_bytes_per_s: float) -> float:
    """Least time over measured time, in percent."""
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / seconds

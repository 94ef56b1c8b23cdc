"""Pallas TPU kernel: incremental Count-Min sketch update (DESIGN.md §6;
jnp oracle: ``kernels.ref.cms_update_ref``).

The streaming engine tracks heavy-hitter candidates across micro-batches
with decaying Count-Min sketches (``repro.stream.sketch``).  The per-batch
table increment is a [depth, width] histogram of hashed bucket ids — the
same one-hot block-counting pattern as ``kernels.histogram`` (DESIGN.md §2:
scatter-add serializes on TPU), computed once per hash row with the mix32
universal family of ``repro.mapreduce.hashing`` so host and device buckets
agree bit-for-bit.

The increment is the sketch-only mode of the fused ingest kernel
(``kernels.ingest_fused``) over a one-column relation: the same 2-D tuple
blocks (multiples of 128 rows, short inputs padded, never a shrunken
block) and the same resident [depth, width] accumulator.  Padding rows are
masked out by the kernel's validity column — any int32 value is a legal
key, so no in-band sentinel exists.
"""
from __future__ import annotations

import jax.numpy as jnp

from .ingest_fused import fused_ingest_pallas


def cms_update_pallas(
    values: jnp.ndarray,
    seeds: tuple[int, ...],
    width: int,
    block: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """[depth, width] int32 bucket-count increment for one batch of keys.

    ``seeds`` selects the mix32 hash row family (one seed per sketch row);
    the caller's sketch must use the same seeds for buckets to line up.
    """
    if values.shape[0] == 0:
        return jnp.zeros((len(seeds), width), jnp.int32)
    _, _, _, cms = fused_ingest_pallas(
        values.astype(jnp.int32)[:, None],
        sketch_cols=(0,),
        seeds=tuple(seeds),
        width=width,
        block=block,
        interpret=interpret,
        double_buffer=False,
    )
    return cms[0]

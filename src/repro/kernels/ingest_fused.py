"""Pallas TPU kernel: fused streaming-ingest pass (DESIGN.md §7; jnp oracle:
``kernels.ref.fused_ingest_ref``).

The streaming engine's ingest hot path runs three per-tuple stages that each
traverse the same micro-batch: map-phase destination ids
(``mapreduce.keys.map_phase``), decaying Count-Min sketch increments
(``kernels.sketch_update``), and per-destination send-buffer packing
(``stream.engine``).  This kernel fuses all three into ONE pass over the
tuple blocks:

  * **destinations** — the static route table of ``mapreduce.keys``
    (hash/pin/exclude/replicate per residual) evaluated in-kernel with the
    same mix32 family, emitting ``dest [N, W]`` global reducer ids
    (−1 = not emitted);
  * **sketch** — the [n_cols·depth, width] Count-Min increment accumulated
    in a VMEM-resident table across grid steps (the one-hot block-counting
    pattern of ``kernels.histogram``: scatter-add serializes on TPU,
    DESIGN.md §2); the host applies decay and absorbs the increment;
  * **pack plan** — per-reducer arrival ``counts [K]`` plus each emission's
    ``rank [N, W]`` within its destination (flat emission order, matching a
    stable sort by destination bit-for-bit).  ``bins[dest, base + rank]``
    is then a pure precomputed-index scatter: the send buffers pack with no
    sort, no searchsorted, and no data-dependent control flow.

Layout: every in-kernel value is 2-D.  A tuple block is ``[B, arity+1]``
(the last column is the validity mask); each attribute and each emission
column is a ``[B, 1]`` static lane slice, and the rank window is one MXU
product per block (``_write_ranks``), so no intermediate grows with the
route width squared.  The grid walks the blocks in order; ``dest``/``rank``
leave VMEM block by block through their ``BlockSpec``, while ``counts`` and
the sketch table stay resident and accumulate — VMEM use is independent of
N.  Input streaming: with ``double_buffer=True`` rows stay in HBM and two
VMEM slots are filled by ``make_async_copy``, the copy of block i+1
overlapping the compute on block i; ``double_buffer=False`` lets the grid
pipeline do the same double buffering implicitly.  Both variants run under
interpret mode on CPU, which is what CI exercises against the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ONE definition of the hash family keeps host routing/sketching and the
# fused device pass in sync bit-for-bit
from repro.mapreduce.hashing import mix32_jnp as _mix32

# Route table entry (all-static, hashable — a jit static argument):
#   (offset, hashed, replica_offsets, pins, excludes)
#   hashed:  ((col, seed, dim, stride), ...)   attrs the tuple owns
#   replica_offsets: (int, ...)                flat grid offsets (the paper's
#                                              recursive_keys enumeration)
#   pins:    ((col, value), ...)               HH equality constraints
#   excludes:((col, (value, ...)), ...)        ordinary-type HH exclusions
RouteTable = tuple

# rank-window counts go through the MXU in bf16, which is exact for the
# per-row arrival counts (<= route width) only up to 256
MAX_ROUTE_WIDTH = 256
# the [k_pad, B] one-hot planes of the rank window are kept at <= 1 MiB
_ONEHOT_WORDS = 256 * 1024


def route_width(routes: RouteTable) -> int:
    """Total emission width W = sum of per-residual replication."""
    return sum(len(rep) for _, _, rep, _, _ in routes)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _block_rows(block: int, k_pad: int) -> int:
    """Tuples per grid step: a multiple of 128 (one lane tile), shrunk while
    the [k_pad, B] one-hot planes exceed 1 MiB."""
    block = _round_up(max(int(block), 1), 128)
    while block > 128 and block * k_pad > _ONEHOT_WORDS:
        block //= 2
    return block


def _dest_rows(cols, msk, routes: RouteTable):
    """W destination rows ``[1, B]`` for one tuple block (−1 = not emitted).
    Mirrors ``mapreduce.keys.RouteSpec.destinations`` exactly, column order
    included (residual-major, replica-minor)."""
    out = []
    for offset, hashed, rep, pins, excludes in routes:
        base = jnp.full(msk.shape, offset, jnp.int32)
        for col, seed, dim, stride in hashed:
            bucket = (_mix32(cols[col], seed) % jnp.uint32(dim)).astype(
                jnp.int32
            )
            base = base + bucket * jnp.int32(stride)
        ok = msk
        for col, value in pins:
            ok = ok & (cols[col] == value)
        for col, values in excludes:
            for hv in values:
                ok = ok & (cols[col] != hv)
        for r_off in rep:
            out.append(jnp.where(ok, base + jnp.int32(r_off), jnp.int32(-1)))
    return out


def _dest_static(cols, msk, enc_ref, dest_ref, kiota, *, routes):
    """Write the static route table's destination rows into ``dest_ref``;
    returns the block's per-tuple destination histogram ``[k_pad, B]``."""
    per_tuple = jnp.zeros(kiota.shape, jnp.int32)
    for w, d in enumerate(_dest_rows(cols, msk, routes)):
        dest_ref[pl.ds(w, 1), :] = d
        per_tuple = per_tuple + (d == kiota).astype(jnp.int32)
    return per_tuple


def _cms_rows(cols, msk, sketch_cols, seeds, width):
    """``[1, width]`` Count-Min increments, one per (column, seed) row.  The
    one-hot plane is ``[width, B]``; its tuple-axis sum is an MXU product
    with a ones operand (exact: counts <= B)."""
    b = msk.shape[1]
    bins = jax.lax.broadcasted_iota(jnp.int32, (width, b), 0)
    ones = jnp.ones((8, b), jnp.bfloat16)
    out = []
    for col in sketch_cols:
        for seed in seeds:
            bucket = (_mix32(cols[col], seed) % jnp.uint32(width)).astype(
                jnp.int32
            )
            bucket = jnp.where(msk, bucket, jnp.int32(-1))  # padding: no bin
            counts = jax.lax.dot_general(
                ones, (bucket == bins).astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [8, width], all rows equal
            out.append(jax.lax.slice_in_dim(counts, 0, 1).astype(jnp.int32))
    return out


def _write_ranks(dest_ref, rank_ref, per_tuple, prev_counts, kiota, w_out):
    """Write each emission's arrival rank at its destination into
    ``rank_ref``, in flat order ``b·W + w``: ``prev_counts [k_pad, 1]`` for
    earlier blocks, plus earlier tuples of this block (one product of the
    per-tuple histogram with a strictly-upper-triangular ``[B, B]``), plus
    this tuple's earlier columns (a running one-hot sum).  No sort, no
    scatter."""
    b = per_tuple.shape[1]
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    ).astype(jnp.bfloat16)
    before = jnp.dot(
        per_tuple.astype(jnp.bfloat16), upper,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32) + prev_counts

    def column(w, before):
        d = dest_ref[pl.ds(w, 1), :]
        hit = d == kiota  # invalid (−1) hits nothing
        r = jnp.where(hit, before, 0).sum(axis=0, keepdims=True)
        rank_ref[pl.ds(w, 1), :] = jnp.where(d >= 0, r, jnp.int32(-1))
        return before + hit.astype(jnp.int32)

    jax.lax.fori_loop(0, w_out, column, before)


def _ingest_kernel(
    rows_in, *refs, dest_fn, has_enc, with_route, with_sketch, sketch_cols,
    seeds, width, k_pad, w_out, arity, block, nsteps, double_buffer,
):
    """One grid step = one tuple block.  ``counts`` and the sketch table are
    revisited every step and accumulate in VMEM; ``dest``/``rank`` blocks
    are written back to HBM by the pipeline."""
    refs = list(refs)
    enc_ref = refs.pop(0) if has_enc else None
    if double_buffer:
        sem = refs.pop()
        scratch = refs.pop()
    dest_ref = rank_ref = counts_ref = cms_ref = None
    if with_route:
        dest_ref, rank_ref, counts_ref = refs[:3]
    if with_sketch:
        cms_ref = refs[-1]
    i = pl.program_id(0)

    def dma(slot, j):
        return pltpu.make_async_copy(
            rows_in.at[:, pl.ds(j * block, block)],
            scratch.at[slot],
            sem.at[slot],
        )

    @pl.when(i == 0)
    def _init():
        if counts_ref is not None:
            counts_ref[...] = jnp.zeros_like(counts_ref)
        if cms_ref is not None:
            cms_ref[...] = jnp.zeros_like(cms_ref)
        if double_buffer:
            dma(0, 0).start()

    if double_buffer:
        slot = i % 2

        @pl.when(i + 1 < nsteps)
        def _prefetch():
            dma(1 - slot, i + 1).start()

        dma(slot, i).wait()
        blk = scratch[slot]
    else:
        blk = rows_in[...]
    # [R, B]: one lane-dense row per attribute, then the validity mask
    cols = [
        jax.lax.slice_in_dim(blk, c, c + 1, axis=0) for c in range(arity + 1)
    ]
    rows, msk = cols[:-1], cols[-1] != 0
    if cms_ref is not None:
        for j, inc in enumerate(_cms_rows(rows, msk, sketch_cols, seeds, width)):
            cms_ref[pl.ds(j, 1), :] += inc
    if dest_ref is not None:
        kiota = jax.lax.broadcasted_iota(jnp.int32, (k_pad, block), 0)
        per_tuple = dest_fn(rows, msk, enc_ref, dest_ref, kiota)
        _write_ranks(
            dest_ref, rank_ref, per_tuple, counts_ref[...], kiota, w_out
        )
        counts_ref[...] += per_tuple.sum(axis=1, keepdims=True)


def _ingest_call(
    rows, enc_flat, dest_fn, w_out, k_pad, sketch_cols, seeds, width, block,
    interpret, double_buffer, name=None,
):
    """Shared ``pallas_call`` plumbing of the static and dense variants.

    The kernel works on the transposed batch (tuples along lanes) and its
    ``dest``/``rank`` come out as ``[w_out, N_pad]``; this returns them as
    padded ``[N_pad, w_out]``, with ``counts [k_pad]`` and
    ``cms [n_cols·depth, width]``.  Route outputs are None when ``w_out``
    is 0, ``cms`` is None without ``sketch_cols``.  ``name`` labels the
    kernel in a profile."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if w_out > MAX_ROUTE_WIDTH:
        raise ValueError(
            f"route width {w_out} exceeds the exact rank-window bound "
            f"{MAX_ROUTE_WIDTH}"
        )
    n, arity = rows.shape
    with_route, with_sketch = w_out > 0, bool(sketch_cols)
    block = _block_rows(block, k_pad if with_route else 0)
    n_pad = max(_round_up(n, block), block)
    nsteps = n_pad // block
    # transposed batch, the validity mask as row ``arity``; rows padded to
    # a sublane tile so each block is one aligned DMA
    n_rows_t = _round_up(arity + 1, 8)
    rows_t = jnp.concatenate(
        [rows.astype(jnp.int32).T, jnp.ones((1, n), jnp.int32)], axis=0
    )
    rows_t = jnp.pad(rows_t, ((0, n_rows_t - arity - 1), (0, n_pad - n)))

    out_shapes, out_specs = [], []
    if with_route:
        out_shapes += [
            jax.ShapeDtypeStruct((w_out, n_pad), jnp.int32),  # dest
            jax.ShapeDtypeStruct((w_out, n_pad), jnp.int32),  # rank
            jax.ShapeDtypeStruct((k_pad, 1), jnp.int32),  # counts
        ]
        out_specs += [
            pl.BlockSpec((w_out, block), lambda i: (0, i)),
            pl.BlockSpec((w_out, block), lambda i: (0, i)),
            pl.BlockSpec((k_pad, 1), lambda i: (0, 0)),
        ]
    if with_sketch:
        n_rows = len(sketch_cols) * len(seeds)
        out_shapes.append(jax.ShapeDtypeStruct((n_rows, width), jnp.int32))
        out_specs.append(pl.BlockSpec((n_rows, width), lambda i: (0, 0)))

    if double_buffer:
        in_specs = [pl.BlockSpec(memory_space=pltpu.HBM)]
        scratch = [
            pltpu.VMEM((2, n_rows_t, block), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        in_specs = [pl.BlockSpec((n_rows_t, block), lambda i: (0, i))]
        scratch = []
    operands = [rows_t]
    if enc_flat is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(enc_flat)

    outs = pl.pallas_call(
        functools.partial(
            _ingest_kernel, dest_fn=dest_fn, has_enc=enc_flat is not None,
            with_route=with_route, with_sketch=with_sketch,
            sketch_cols=tuple(sketch_cols), seeds=tuple(seeds), width=width,
            k_pad=k_pad, w_out=w_out, arity=arity, block=block, nsteps=nsteps,
            double_buffer=double_buffer,
        ),
        grid=(nsteps,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=name,
    )(*operands)

    outs = list(outs)
    dest = rank = counts = cms = None
    if with_route:
        dest, rank, counts = outs[0].T, outs[1].T, outs[2].reshape(-1)
    if with_sketch:
        cms = outs[-1]
    return dest, rank, counts, cms


def fused_ingest_pallas(
    rows: jnp.ndarray,  # [N, arity] int32
    routes: RouteTable = (),
    sketch_cols: tuple[int, ...] = (),
    seeds: tuple[int, ...] = (),
    width: int = 2048,
    num_reducers: int = 1,
    block: int = 256,
    interpret: bool | None = None,
    double_buffer: bool = True,
):
    """One fused pass over a micro-batch for one relation.

    Returns ``(dest [N, W], rank [N, W], counts [num_reducers],
    cms [n_cols, depth, width])``; the route outputs are None when
    ``routes`` is empty (sketch-only pass), ``cms`` is None when
    ``sketch_cols`` is empty (route-only pass).
    """
    if not routes and not sketch_cols:
        raise ValueError("fused ingest needs routes and/or sketch_cols")
    if sketch_cols and not seeds:
        raise ValueError("sketching requires the Count-Min row seeds")
    n = rows.shape[0]
    k_pad = max(_round_up(num_reducers, 128), 128)
    dest, rank, counts, cms = _ingest_call(
        rows, None, functools.partial(_dest_static, routes=routes),
        route_width(routes), k_pad, sketch_cols, seeds, width, block,
        interpret, double_buffer,
    )
    if routes:
        dest, rank, counts = dest[:n], rank[:n], counts[:num_reducers]
    if sketch_cols:
        cms = cms.reshape(len(sketch_cols), len(seeds), width)
    return dest, rank, counts, cms


# ---- dynamic-route variant (replan-stable compile cache) -------------------
#
# ``fused_ingest_pallas`` bakes the route table into the compiled kernel as
# a static argument: correct, but every drift replan produces a new table
# and therefore a full recompile (~seconds) on the ingest critical path —
# the batch-0/5 spikes in BENCH_stream.json.  The dense variant passes the
# SAME routing recipe as data: per padded output column, a base offset plus
# padded per-attr (seed, dim, stride) hash terms, pin equalities, and
# exclude lists, all as int32 scalars in SMEM.  Only the *padded shapes* are
# static — (W_pad, H, P, V) derived from the relation arity and the config's
# HH cap — so replans that stay within the same power-of-two replication
# bucket reuse the compiled executable and pay microseconds, not seconds.
# Column selection multiplies each attribute column by a 0/1 scalar (no
# data-dependent gather) and the arithmetic is term-for-term identical to
# ``_dest_rows``, so destinations stay bit-identical to ``map_phase``.

def dense_route_encoding(
    routes: RouteTable,
    arity: int,
    w_pad: int,
    max_values: int,
) -> dict:
    """Encode a static route table as dense int32 arrays (dynamic operands).

    Shapes: per padded flat column ``w < w_pad`` (real columns first, in
    ``_dest_rows``'s residual-major/replica-minor order):

      * ``col_base [Wp]``   — residual offset + replica offset (0 padded)
      * ``col_valid [Wp]``  — 1 for real columns
      * ``h_col/h_seed/h_dim/h_stride [Wp, H]`` — hashed-attr terms, padded
        with (0, 0, 1, 0) so a padded slot contributes bucket 0 * stride 0
      * ``p_col/p_val/p_on [Wp, P]`` — pin equalities (``p_on=0`` ignored)
      * ``e_col [Wp, P]``, ``e_val/e_on [Wp, P, V]`` — exclude lists

    ``H = P = arity`` (a residual can hash/pin/exclude at most every
    attribute) and ``V = max_values`` must bound the per-attr exclude list
    (the planner's ``max_hh_per_attr``); violations raise rather than
    silently truncate.
    """
    import numpy as np

    w = route_width(routes)
    if w > w_pad:
        raise ValueError(f"w_pad {w_pad} < route width {w}")
    H = P = max(1, arity)
    V = max(1, max_values)
    enc = {
        "col_base": np.zeros(w_pad, np.int32),
        "col_valid": np.zeros(w_pad, np.int32),
        "h_col": np.zeros((w_pad, H), np.int32),
        "h_seed": np.zeros((w_pad, H), np.int32),
        "h_dim": np.ones((w_pad, H), np.int32),
        "h_stride": np.zeros((w_pad, H), np.int32),
        "p_col": np.zeros((w_pad, P), np.int32),
        "p_val": np.zeros((w_pad, P), np.int32),
        "p_on": np.zeros((w_pad, P), np.int32),
        "e_col": np.zeros((w_pad, P), np.int32),
        "e_val": np.zeros((w_pad, P, V), np.int32),
        "e_on": np.zeros((w_pad, P, V), np.int32),
    }
    col = 0
    for offset, hashed, rep, pins, excludes in routes:
        if len(hashed) > H or len(pins) > P or len(excludes) > P:
            raise ValueError(
                f"route terms exceed arity padding {H}: "
                f"{len(hashed)} hashed / {len(pins)} pins / "
                f"{len(excludes)} excludes"
            )
        for r_off in rep:
            enc["col_base"][col] = offset + r_off
            enc["col_valid"][col] = 1
            for j, (c, seed, dim, stride) in enumerate(hashed):
                enc["h_col"][col, j] = c
                enc["h_seed"][col, j] = np.int32(np.uint32(seed))
                enc["h_dim"][col, j] = dim
                enc["h_stride"][col, j] = stride
            for j, (c, value) in enumerate(pins):
                enc["p_col"][col, j] = c
                enc["p_val"][col, j] = value
                enc["p_on"][col, j] = 1
            for j, (c, values) in enumerate(excludes):
                if len(values) > V:
                    raise ValueError(
                        f"exclude list ({len(values)}) exceeds max_values "
                        f"padding ({V}); raise the pad_values hint"
                    )
                enc["e_col"][col, j] = c
                for v_i, hv in enumerate(values):
                    enc["e_val"][col, j, v_i] = hv
                    enc["e_on"][col, j, v_i] = 1
            col += 1
    return enc


_ENC_KEYS = (
    "col_base", "col_valid", "h_col", "h_seed", "h_dim", "h_stride",
    "p_col", "p_val", "p_on", "e_col", "e_val", "e_on",
)


def _dest_dense(cols, msk, enc_ref, dest_ref, kiota, *, layout):
    """``_dest_static`` with the route terms read as scalars from the flat
    SMEM encoding (``layout[key] = (offset, shape)`` of ``enc[key]`` in it),
    one loop iteration per padded column."""
    wp, h = layout["h_col"][1]
    _, p, v = layout["e_val"][1]

    def at(key, *idx):  # scalar enc[key][idx] (row-major)
        off, shape = layout[key]
        flat = 0
        for i, dim in zip(idx, shape):
            flat = flat * dim + i
        return enc_ref[off + flat]

    def select(c):  # dynamic attribute id -> its [1, B] row
        out = cols[0] * (c == 0).astype(jnp.int32)
        for a in range(1, len(cols)):
            out = out + cols[a] * (c == a).astype(jnp.int32)
        return out

    def column(w, per_tuple):
        base = jnp.full(msk.shape, at("col_base", w), jnp.int32)
        for j in range(h):
            bucket = (
                _mix32(select(at("h_col", w, j)), at("h_seed", w, j))
                % at("h_dim", w, j).astype(jnp.uint32)
            ).astype(jnp.int32)
            base = base + bucket * at("h_stride", w, j)
        # count failed pins and matched excludes; emit only when zero
        bad = jnp.full(msk.shape, 1 - at("col_valid", w), jnp.int32)
        for j in range(p):
            pv = select(at("p_col", w, j))
            bad = bad + (pv != at("p_val", w, j)).astype(jnp.int32) * at(
                "p_on", w, j
            )
            ev = select(at("e_col", w, j))
            for k in range(v):
                bad = bad + (ev == at("e_val", w, j, k)).astype(
                    jnp.int32
                ) * at("e_on", w, j, k)
        d = jnp.where(msk & (bad == 0), base, jnp.int32(-1))
        dest_ref[pl.ds(w, 1), :] = d
        return per_tuple + (d == kiota).astype(jnp.int32)

    return jax.lax.fori_loop(
        0, wp, column, jnp.zeros(kiota.shape, jnp.int32)
    )


def fused_ingest_dense_pallas(
    rows: jnp.ndarray,  # [N, arity] int32
    enc: dict,  # dense_route_encoding arrays (dynamic operands)
    sketch_cols: tuple[int, ...] = (),
    seeds: tuple[int, ...] = (),
    width: int = 2048,
    k_pad: int = 128,
    block: int = 256,
    interpret: bool | None = None,
    double_buffer: bool = True,
):
    """``fused_ingest_pallas`` with the routes as data, not code.

    Returns padded ``(dest [N_pad, Wp], rank [N_pad, Wp], counts [k_pad],
    cms [n_cols, depth, width] | None)`` — the caller slices to the real
    (N, W, K), which live outside the compile cache on purpose.  The only
    static inputs are padded shapes and the sketch signature, so replans
    within the same (Wp, k_pad) bucket hit the compiled executable.

    ``k_pad`` MUST be >= the plan's total reducers: destination ids are
    dynamic, so a too-small histogram cannot be detected at trace time and
    silently corrupts counts/ranks (the engine rounds total_reducers up to
    a 128 multiple in ``_dense_routes``).
    """
    layout, off, flat = {}, 0, []
    for key in _ENC_KEYS:
        arr = jnp.asarray(enc[key], jnp.int32)
        layout[key] = (off, arr.shape)
        off += arr.size
        flat.append(arr.reshape(-1))
    wp = layout["col_base"][1][0]
    dest, rank, counts, cms = _ingest_call(
        rows, jnp.concatenate(flat),
        functools.partial(_dest_dense, layout=layout),
        wp, k_pad, sketch_cols, seeds, width, block, interpret, double_buffer,
        name="fused_ingest_dense",
    )
    if sketch_cols:
        cms = cms.reshape(len(sketch_cols), len(seeds), width)
    return dest, rank, counts, cms


# ---- roofline / overlap model (DESIGN.md §7) -------------------------------
# Per-chip peaks, keyed by ``jax.devices()[0].device_kind``.  A kind that is
# not listed raises: a model fed another chip's peaks is wrong, not rough.
CHIP_PEAKS = {
    "TPU v5 lite": {
        # Google Cloud TPU documentation, "TPU v5e": 819 GB/s HBM2
        "hbm_bytes_per_s": 819e9,
        # same page: 197 TFLOP/s bf16 per chip
        "mxu_flops_per_s": 197e12,
        # derived, one 32-bit op per VPU lane per cycle: the same page's
        # 197 TFLOP/s over its 4 MXUs of 128x128 MACs gives a 1.5 GHz
        # clock; times the 8x128-lane vector unit
        "vpu_ops_per_s": 197e12 / (4 * 128 * 128 * 2) * 8 * 128,
    },
}


def chip_peaks(device_kind: str) -> dict:
    """The peak table entry of one device kind; unknown kinds raise."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak figures for device kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}"
        ) from None


def overlap_profile(
    n_rows: int,
    arity: int,
    route_w: int,
    num_reducers: int,
    n_sketch_cols: int,
    depth: int,
    width: int,
    *,
    device_kind: str,
    block: int = 256,
) -> dict:
    """Model the fused pass against the roofline of ``device_kind``.

    Returns modeled HBM traffic, VPU and MXU work, the serial vs
    double-buffered time, and which side of the roofline binds.
    ``bench_stream`` writes this next to the measured wall times so the gap
    between "what the kernel does" and "what the host pays" stays visible.
    """
    peaks = chip_peaks(device_kind)
    k_pad = max(_round_up(num_reducers, 128), 128)
    block = _block_rows(block, k_pad if route_w else 0)
    bytes_in = n_rows * (arity + 1) * 4
    bytes_out = (2 * n_rows * route_w + num_reducers + n_sketch_cols * depth * width) * 4
    dma_s = (bytes_in + bytes_out) / peaks["hbm_bytes_per_s"]

    nsteps = max(1, -(-n_rows // block))
    ops_rank = n_rows * route_w * k_pad * 5  # one-hot, select, sum, carry
    ops_dest = n_rows * route_w * 8  # mix32 + pin/exclude masks
    ops_cms = n_rows * n_sketch_cols * depth * (width * 2 + 8)
    vpu_ops = ops_rank + ops_dest + ops_cms
    mxu_flops = nsteps * 2 * block * block * k_pad if route_w else 0
    compute_s = (
        vpu_ops / peaks["vpu_ops_per_s"] + mxu_flops / peaks["mxu_flops_per_s"]
    )

    serial_s = dma_s + compute_s
    overlapped_s = max(dma_s, compute_s)
    return {
        "device_kind": device_kind,
        "bytes_in": bytes_in,
        "bytes_out": bytes_out,
        "vpu_ops": vpu_ops,
        "mxu_flops": mxu_flops,
        "dma_us": dma_s * 1e6,
        "compute_us": compute_s * 1e6,
        "serial_us": serial_s * 1e6,
        "overlapped_us": overlapped_s * 1e6,
        "overlap_speedup": serial_s / overlapped_s if overlapped_s else 1.0,
        "bound": "compute" if compute_s >= dma_s else "dma",
    }

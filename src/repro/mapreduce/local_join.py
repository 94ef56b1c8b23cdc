"""Reduce-phase primitives: bin emissions by reducer, join locally.

Grouping uses a sort + rank-in-group scatter (static shapes, no host
roundtrip).  The local multiway join is expressed as an einsum over pairwise
match matrices — on TPU this contraction is exactly what the MXU wants, and
the 2-way inner block is what ``repro.kernels.block_join`` implements as a
Pallas kernel (the jnp path here doubles as its oracle at system level).

Join *outputs* are returned as (count, checksum) rather than materialized
tuples: output size is data-dependent (unknowable statically), while count +
an orderless hash-weighted checksum give a complete correctness fingerprint
against the host oracle.  A capacity-bounded materialization is provided for
2-way joins.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.schema import JoinQuery

from .hashing import row_weight_jnp

_EINSUM_LETTERS = "abcdefghij"


def group_by_reducer(
    dests: jnp.ndarray,  # [M] int32 global reducer ids, -1 = dropped
    rows: jnp.ndarray,  # [M, arity]
    num_reducers: int,
    cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter emissions into per-reducer bins.

    Returns (bins [K, cap, arity], valid [K, cap], loads [K], overflow).
    ``loads`` counts *all* arrivals (pre-capacity) so skew is observable;
    ``overflow`` counts tuples dropped because a bin exceeded cap.
    """
    m = dests.shape[0]
    k = num_reducers
    d = jnp.where(dests >= 0, dests, k).astype(jnp.int32)  # invalid -> bin k
    order = jnp.argsort(d, stable=True)
    ds = d[order]
    rs = rows[order]
    # rank within group: position - first index of this dest value
    first = jnp.searchsorted(ds, ds, side="left")
    rank = jnp.arange(m, dtype=jnp.int32) - first.astype(jnp.int32)
    ok = (ds < k) & (rank < cap)
    # scatter; clamped ids for dropped rows point at a scratch bin
    bid = jnp.where(ok, ds, k)
    rid = jnp.where(ok, rank, 0)
    bins = jnp.zeros((k + 1, cap, rows.shape[1]), dtype=rows.dtype)
    bins = bins.at[bid, rid].set(rs)
    valid = jnp.zeros((k + 1, cap), dtype=bool).at[bid, rid].set(ok)
    loads = jnp.zeros(k + 1, dtype=jnp.int32).at[d].add(1)[:k]
    overflow = jnp.sum((ds < k) & (rank >= cap))
    return bins[:k], valid[:k], loads, overflow


@dataclasses.dataclass(frozen=True)
class LocalJoinSpec:
    """Static join structure: which relation pairs share which columns."""

    rel_names: tuple[str, ...]
    # (rel_i, rel_j, ((col_in_i, col_in_j), ...)) for every linked pair i<j
    links: tuple[tuple[int, int, tuple[tuple[int, int], ...]], ...]

    @classmethod
    def from_query(cls, query: JoinQuery) -> "LocalJoinSpec":
        rels = query.relations
        links = []
        for i in range(len(rels)):
            for j in range(i + 1, len(rels)):
                shared = [a for a in rels[i].attrs if a in rels[j].attrs]
                if shared:
                    links.append(
                        (
                            i,
                            j,
                            tuple(
                                (rels[i].index_of(a), rels[j].index_of(a))
                                for a in shared
                            ),
                        )
                    )
        if len(rels) > len(_EINSUM_LETTERS):
            raise ValueError("joins over >10 relations not supported")
        return cls(tuple(r.name for r in rels), tuple(links))


def _match_matrix(
    bi: jnp.ndarray, vi: jnp.ndarray, bj: jnp.ndarray, vj: jnp.ndarray, cols
) -> jnp.ndarray:
    """Batched pairwise equality: bi [K,ca,arity], bj [K,cb,arity] ->
    [K, ca, cb] bool."""
    m = vi[:, :, None] & vj[:, None, :]
    for ci, cj in cols:
        m &= bi[:, :, ci][:, :, None] == bj[:, :, cj][:, None, :]
    return m


def local_join_per_reducer(
    spec: LocalJoinSpec,
    bins: dict[str, jnp.ndarray],  # name -> [K, cap, arity]
    valids: dict[str, jnp.ndarray],  # name -> [K, cap]
    weight_seed: int = 0x5EED,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-reducer-batched multiway join: (count [K], checksum [K]) int32.

    checksum = sum over joined tuples of the product of per-relation tuple
    weights (mod 2^32 as int32 wraparound) — orderless, matches the oracle.
    A reducer's count is exact while it stays below 2^31; sums across
    reducers are the caller's, in whatever width the total needs.
    """
    n = len(spec.rel_names)
    letters = _EINSUM_LETTERS[:n]
    operands_cnt = []
    subs = []
    for i, j, cols in spec.links:
        name_i, name_j = spec.rel_names[i], spec.rel_names[j]
        m = _match_matrix(
            bins[name_i], valids[name_i], bins[name_j], valids[name_j], cols
        )
        operands_cnt.append(m.astype(jnp.int32))
        subs.append(f"k{letters[i]}{letters[j]}")
    # validity for relations not covered by any link (cross products)
    covered = {i for i, j, _ in spec.links} | {j for _, j, _ in spec.links}
    ones = []
    for i in range(n):
        if i not in covered:
            ones.append(valids[spec.rel_names[i]].astype(jnp.int32))
            subs.append(f"k{letters[i]}")
    expr = ",".join(subs) + "->k"
    count = jnp.einsum(expr, *operands_cnt, *ones)

    # weights folded per relation
    w_ops = []
    w_subs = []
    for i, name in enumerate(spec.rel_names):
        b, v = bins[name], valids[name]
        flat = b.reshape(-1, b.shape[-1])
        w = row_weight_jnp(flat, weight_seed + i).reshape(b.shape[0], b.shape[1])
        w = jnp.where(v, w, 0)  # invalid rows never join; zero is safe
        w_ops.append(w.astype(jnp.uint32))
        w_subs.append(f"k{letters[i]}")
    # uint32 einsum unsupported on some backends; do modular arithmetic via
    # float64-free int32 wraparound: cast through int32 (two's complement wrap)
    expr_w = ",".join(subs + w_subs) + "->k"
    checksum = jnp.einsum(
        expr_w,
        *[o.astype(jnp.int32) for o in operands_cnt],
        *[o.astype(jnp.int32) for o in ones],
        *[w.astype(jnp.int32) for w in w_ops],
    )
    return count, checksum


def local_join_count_checksum(
    spec: LocalJoinSpec,
    bins: dict[str, jnp.ndarray],  # name -> [K, cap, arity]
    valids: dict[str, jnp.ndarray],  # name -> [K, cap]
    weight_seed: int = 0x5EED,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``local_join_per_reducer`` summed: (count int32, checksum uint32)."""
    count, checksum = local_join_per_reducer(spec, bins, valids, weight_seed)
    return jnp.sum(count), jnp.sum(checksum).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("spec", "weight_seed"))
def local_join_count_checksum_jit(
    spec: LocalJoinSpec,
    bins: dict[str, jnp.ndarray],
    valids: dict[str, jnp.ndarray],
    weight_seed: int = 0x5EED,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Jit-cached ``local_join_count_checksum`` (``spec`` is hashable and
    static).  Same integer math, so results are bit-identical; the eager
    version stays as the oracle while this one serves latency-critical
    callers (the streaming fused-ingest path, DESIGN.md §7) where per-call
    op-by-op dispatch would dominate the batch budget."""
    return local_join_count_checksum(spec, bins, valids, weight_seed)


def materialize_two_way(
    spec: LocalJoinSpec,
    bins: dict[str, jnp.ndarray],
    valids: dict[str, jnp.ndarray],
    out_cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """2-way joins only: emit joined rows [out_cap, arity_l + arity_r]
    (zero-padded), their validity mask, and an overflow count."""
    if len(spec.rel_names) != 2:
        raise ValueError("materialize_two_way is for 2-way joins")
    (i, j, cols), = spec.links
    li, lj = spec.rel_names[i], spec.rel_names[j]
    m = _match_matrix(bins[li], valids[li], bins[lj], valids[lj], cols)  # [K,ca,cb]
    k, ca, cb = m.shape
    flat = m.reshape(-1)
    total = flat.shape[0]
    idx = jnp.nonzero(flat, size=out_cap, fill_value=total)[0]
    ok = idx < total
    idx = jnp.where(ok, idx, 0)
    kk = idx // (ca * cb)
    ra = (idx // cb) % ca
    rb = idx % cb
    left = bins[li][kk, ra]
    right = bins[lj][kk, rb]
    rows = jnp.concatenate([left, right], axis=-1)
    rows = jnp.where(ok[:, None], rows, 0)
    overflow = jnp.maximum(jnp.sum(m) - jnp.sum(ok), 0)
    return rows, ok, overflow

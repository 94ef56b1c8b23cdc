"""Host-side reference oracle: exact multiway join via hash merges (numpy).

Computes (count, checksum, optionally materialized rows) for any JoinQuery.
The checksum uses the same per-relation tuple weights as the device path
(``hashing.row_weight_np``) summed over joined combinations mod 2^32, so
device results can be compared bit-for-bit.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from repro.core.schema import JoinQuery

from .hashing import row_weight_np

_MASK32 = np.uint64(0xFFFFFFFF)


def _join_two(
    left_rows: np.ndarray,
    left_attrs: list[str],
    left_w: np.ndarray,
    right_rows: np.ndarray,
    right_attrs: list[str],
    right_w: np.ndarray,
) -> tuple[np.ndarray, list[str], np.ndarray]:
    shared = [a for a in left_attrs if a in right_attrs]
    li = [left_attrs.index(a) for a in shared]
    ri = [right_attrs.index(a) for a in shared]
    buckets: dict[tuple, list[int]] = defaultdict(list)
    for j in range(right_rows.shape[0]):
        buckets[tuple(right_rows[j, ri])].append(j)
    out_left, out_right = [], []
    for i in range(left_rows.shape[0]):
        key = tuple(left_rows[i, li])
        for j in buckets.get(key, ()):
            out_left.append(i)
            out_right.append(j)
    keep = [a for a in right_attrs if a not in shared]
    ki = [right_attrs.index(a) for a in keep]
    if out_left:
        l_idx = np.asarray(out_left)
        r_idx = np.asarray(out_right)
        rows = np.concatenate(
            [left_rows[l_idx], right_rows[r_idx][:, ki]], axis=1
        )
        w = (left_w[l_idx].astype(np.uint64) * right_w[r_idx].astype(np.uint64)) & 0xFFFFFFFF
    else:
        rows = np.zeros((0, left_rows.shape[1] + len(keep)), dtype=left_rows.dtype)
        w = np.zeros(0, dtype=np.uint64)
    return rows, left_attrs + keep, w.astype(np.uint32)


def oracle_join(
    query: JoinQuery,
    data: dict[str, np.ndarray],
    weight_seed: int = 0x5EED,
) -> tuple[int, int, np.ndarray, list[str]]:
    """Returns (count, checksum_uint32, rows, attr_order).

    checksum = sum over join results of prod_i weight_i(tuple_i) mod 2^32 —
    identical to the device computation (weights multiply in uint32 wrap
    because all intermediate weights stay < 2^32 via masking each step;
    the device multiplies in int32 two's complement which matches mod 2^32).
    """
    rels = query.relations
    rows = np.asarray(data[rels[0].name], dtype=np.int64)
    attrs = list(rels[0].attrs)
    w = row_weight_np(rows, weight_seed + 0).astype(np.uint32)
    for i, rel in enumerate(rels[1:], start=1):
        r = np.asarray(data[rel.name], dtype=np.int64)
        rw = row_weight_np(r, weight_seed + i).astype(np.uint32)
        rows, attrs, w = _join_two(rows, attrs, w, r, list(rel.attrs), rw)
    count = rows.shape[0]
    checksum = int(np.sum(w.astype(np.uint64)) & 0xFFFFFFFF)
    return count, checksum, rows, attrs


def _star_hub(query: JoinQuery) -> int:
    """Index of the relation every other one joins to, in a query whose
    other relations share no attribute among themselves (a binary join is
    the one-spoke case).  Raises for any other shape."""
    rels = query.relations
    for i, hub in enumerate(rels):
        spokes = [r for j, r in enumerate(rels) if j != i]
        if not all(set(s.attrs) & set(hub.attrs) for s in spokes):
            continue
        if all(
            not set(a.attrs) & set(b.attrs)
            for x, a in enumerate(spokes)
            for b in spokes[x + 1:]
        ):
            return i
    raise ValueError(f"{query} is not a binary or star equi-join")


def _key_index(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Distinct rows of ``keys [n, c]`` and each row's id among them."""
    if keys.shape[1] == 1:
        uniq, inv = np.unique(keys[:, 0], return_inverse=True)
        return inv.reshape(-1), uniq[:, None]
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    return inv.reshape(-1), uniq


def keyed_join_fingerprint(
    query: JoinQuery,
    data: dict[str, np.ndarray],
    weight_seed: int = 0x5EED,
) -> tuple[int, int]:
    """(count, checksum) of a binary or star equi-join, aggregated per key.

    ``oracle_join``'s fingerprint without materialising a single output
    row, so it scales to heavy keys whose results number in the billions.
    The hub relation's tuples are grouped by their join key; for each key
    the count is the product of the per-relation tuple counts and the
    checksum term the product of the per-relation weight sums (mod 2^32) —
    ``cntR·cntS`` and ``ΣwR·ΣwS`` for a binary join.  Weights are
    ``oracle_join``'s, so the two agree exactly.
    """
    rels = query.relations
    hub_i = _star_hub(query)
    hub = rels[hub_i]
    arrays = {r.name: np.asarray(data[r.name], np.int64) for r in rels}
    weights = {
        r.name: row_weight_np(arrays[r.name], weight_seed + i).astype(np.int64)
        for i, r in enumerate(rels)
    }
    spokes = [r for j, r in enumerate(rels) if j != hub_i]
    key_attrs = [a for a in hub.attrs if any(a in s.attrs for s in spokes)]
    hub_rows = arrays[hub.name]
    ids, hub_keys = _key_index(
        hub_rows[:, [hub.index_of(a) for a in key_attrs]]
    )
    n_keys = len(hub_keys)
    count = np.bincount(ids, minlength=n_keys).astype(np.uint64)
    wsum = np.bincount(ids, weights=weights[hub.name], minlength=n_keys)
    wsum = wsum.astype(np.uint64) & _MASK32
    for s in spokes:
        shared = [a for a in s.attrs if a in hub.attrs]
        s_rows = arrays[s.name][:, [s.index_of(a) for a in shared]]
        probe = hub_keys[:, [key_attrs.index(a) for a in shared]]
        both, uniq = _key_index(np.concatenate([s_rows, probe]))
        n_both = len(uniq)
        s_ids, p_ids = both[: s_rows.shape[0]], both[s_rows.shape[0]:]
        s_cnt = np.bincount(s_ids, minlength=n_both).astype(np.uint64)
        s_w = np.bincount(s_ids, weights=weights[s.name], minlength=n_both)
        count = count * s_cnt[p_ids]
        wsum = (wsum * (s_w.astype(np.uint64)[p_ids] & _MASK32)) & _MASK32
    return int(count.sum()), int(wsum.sum() & _MASK32)

"""Plain reference of a binary equi-join's fingerprint, per key, in numpy.

It imports nothing of the program.  The weights and the checksum follow
the program's published definition (count of joined pairs, and the sum of
the product of per-tuple weights mod 2^32, weights from a murmur3-style
32-bit mix of each row), copied here so that no later change to the
program can move the yardstick.

* ``keyed_fingerprint`` — the (count, checksum) of R ⋈ S over given rows,
  aggregated per key: ``Σ_k cntR[k]·cntS[k]`` and ``Σ_k ΣwR[k]·ΣwS[k]``.
* ``WindowReference`` — the same over a sliding window of the last
  ``window`` batches, kept incrementally: each batch adds its per-key
  counts and weight sums, and the batch that leaves the window takes its
  own away again, so a run of hundreds of batches checks in seconds.
"""
from __future__ import annotations

import numpy as np

MASK32 = np.uint64(0xFFFFFFFF)
WEIGHT_SEED = 0x5EED


def mix32(x: np.ndarray, seed: int) -> np.ndarray:
    x = x.astype(np.uint32) ^ np.uint32(seed)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def row_weight(rows: np.ndarray, seed: int, mod: int = 251) -> np.ndarray:
    """Per-tuple weight in [1, mod]; relation i of the query uses seed
    ``WEIGHT_SEED + i``."""
    h = np.full(rows.shape[0], np.uint32(seed), dtype=np.uint32)
    for j in range(rows.shape[1]):
        h = mix32(rows[:, j].astype(np.uint32) + h, seed + j + 1)
    return (h % np.uint32(mod)).astype(np.int64) + 1


class JoinShape:
    """A binary equi-join on one shared attribute, from a configuration's
    ``relations`` mapping (relation name -> attribute names, in order)."""

    def __init__(self, relations: dict[str, list[str]]):
        (self.left, la), (self.right, ra) = list(relations.items())
        shared = [a for a in la if a in ra]
        if len(shared) != 1:
            raise ValueError(f"not a binary join on one attribute: {relations}")
        self.key = shared[0]
        self.key_col = {self.left: la.index(self.key), self.right: ra.index(self.key)}
        self.seed = {self.left: WEIGHT_SEED, self.right: WEIGHT_SEED + 1}

    def keys_and_weights(self, name: str, rows: np.ndarray):
        rows = np.asarray(rows, np.int64)
        return rows[:, self.key_col[name]], row_weight(rows, self.seed[name])


def keyed_fingerprint(shape: JoinShape, data: dict[str, np.ndarray]) -> tuple[int, int]:
    """(count, checksum mod 2^32) of ``left ⋈ right`` over ``data``."""
    kl, wl = shape.keys_and_weights(shape.left, data[shape.left])
    kr, wr = shape.keys_and_weights(shape.right, data[shape.right])
    keys, inv = np.unique(np.concatenate([kl, kr]), return_inverse=True)
    il, ir = inv[: kl.size], inv[kl.size:]
    n = keys.size
    cl = np.bincount(il, minlength=n).astype(np.int64)
    cr = np.bincount(ir, minlength=n).astype(np.int64)
    sl = np.bincount(il, weights=wl, minlength=n).astype(np.uint64) & MASK32
    sr = np.bincount(ir, weights=wr, minlength=n).astype(np.uint64) & MASK32
    return int((cl * cr).sum()), int((sl * sr).sum() & MASK32)


class WindowReference:
    """Incremental fingerprint of the join over the last ``window`` batches.

    Keys must lie in ``[0, domain)``.  ``add(batch)`` first retires the
    batch that leaves the window (as the engine expires before it joins),
    then adds the new one, and returns ``(delta_count, window_count,
    window_checksum)``: the results the new batch contributed against
    what the window retained, and the fingerprint of the window after it.
    """

    def __init__(self, shape: JoinShape, domain: int, window: int):
        self.shape, self.domain, self.window = shape, int(domain), int(window)
        names = (shape.left, shape.right)
        self.count = {nm: np.zeros(self.domain, np.int64) for nm in names}
        self.wsum = {nm: np.zeros(self.domain, np.uint64) for nm in names}
        self.retained: list[dict[str, tuple[np.ndarray, np.ndarray]]] = []

    def _fold(self, part: dict, sign: int) -> None:
        for nm, (keys, w) in part.items():
            c = np.bincount(keys, minlength=self.domain).astype(np.int64)
            s = np.bincount(keys, weights=w, minlength=self.domain).astype(np.uint64)
            self.count[nm] += sign * c
            if sign > 0:
                self.wsum[nm] = (self.wsum[nm] + s) & MASK32
            else:
                self.wsum[nm] = (self.wsum[nm] - (s & MASK32)) & MASK32

    def fingerprint(self) -> tuple[int, int]:
        l, r = self.shape.left, self.shape.right
        count = int((self.count[l] * self.count[r]).sum())
        checksum = int((self.wsum[l] * self.wsum[r]).sum() & MASK32)
        return count, checksum

    def add(self, batch: dict[str, np.ndarray]) -> tuple[int, int, int]:
        while len(self.retained) >= self.window:
            self._fold(self.retained.pop(0), -1)
        before, _ = self.fingerprint()
        part = {
            nm: self.shape.keys_and_weights(nm, batch[nm])
            for nm in (self.shape.left, self.shape.right)
        }
        for keys, _ in part.values():
            if keys.size and (keys.min() < 0 or keys.max() >= self.domain):
                raise ValueError(f"join key outside [0, {self.domain})")
        self._fold(part, +1)
        self.retained.append(part)
        count, checksum = self.fingerprint()
        return count - before, count, checksum

"""Mergeable, decaying stream summaries for online heavy-hitter tracking
(DESIGN.md §6).

The batch planner sees all data up front and finds heavy hitters with one
exact scan (``core.heavy_hitters.exact_heavy_hitters``).  A streaming join
never sees "all data": the skew profile must be maintained incrementally
and must *forget*, so a value that was heavy an hour ago stops forcing a
pinned residual today.  Three layers:

  * ``DecayingCountMin`` — a ``core.heavy_hitters.CountMinSketch`` with a
    mix32 hash family (bit-identical on host numpy and on device via
    ``kernels.cms_update``) and exponential decay: before each batch the
    table is scaled by ``decay``, so counts converge to an EMA of per-batch
    frequencies.  ``rate()`` is the bias-corrected per-batch rate estimate.
  * ``SpaceSaving`` — Metwally et al.'s stream-summary with a fixed number
    of counters; generates the candidate set (CMS alone cannot enumerate
    which values to ask about).  Mergeable and decayable the same way.
  * ``StreamHHTracker`` — per share-attribute SpaceSaving candidates plus
    per (attribute, relation) DecayingCountMin rates, combined exactly like
    the batch detector: a value is a live HH when its estimated per-batch
    rate in ANY relation containing the attribute reaches the threshold.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.dominance import share_attributes
from repro.core.heavy_hitters import CountMinSketch
from repro.core.schema import JoinQuery
from repro.mapreduce.hashing import bucket_np
from repro.obs import NULL_OBS, Observability


def _row_seeds(seed: int, depth: int) -> tuple[int, ...]:
    """Per-row mix32 seeds, reproducible from one integer seed."""
    rng = np.random.default_rng(seed)
    return tuple(int(s) for s in rng.integers(1, (1 << 31) - 1, size=depth))


def cms_delta(col: np.ndarray, seeds: tuple[int, ...], width: int) -> np.ndarray:
    """One column's [depth, width] Count-Min bucket-count increment.

    Integer counts over the mix32 family — bit-identical to what
    ``DecayingCountMin.update`` would add for the same column, so the
    result can be ``absorb``-ed by any sketch sharing ``(seeds, width)``.
    This is how a ``MultiQueryEngine`` computes ONE shared increment per
    relation batch and hands it to every tenant's tracker (DESIGN.md §9).
    """
    delta = np.zeros((len(seeds), int(width)), dtype=np.float64)
    col = np.asarray(col, dtype=np.int64)
    if col.size:
        for d, s in enumerate(seeds):
            buckets = bucket_np(col, s, int(width))
            delta[d] = np.bincount(buckets, minlength=int(width))
    return delta


class DecayingCountMin(CountMinSketch):
    """Count-Min over the mix32 row family with exponential decay.

    The bucket function matches ``kernels.cms_update`` bit-for-bit, so the
    per-batch table increment can be produced on-device and absorbed here.
    The table is float64: after ``step()`` it holds
    ``sum_t decay^(T-t) * c_t`` per bucket — a geometric average whose
    bias-corrected normalization ``(1-decay)/(1-decay^T)`` turns estimates
    into per-batch rates.
    """

    def __init__(
        self, width: int = 2048, depth: int = 4, seed: int = 0, decay: float = 0.5
    ):
        if not (0.0 < decay <= 1.0):
            raise ValueError("decay must be in (0, 1]")
        self.width = int(width)
        self.depth = int(depth)
        self.seeds = _row_seeds(seed, depth)
        self.decay_factor = float(decay)
        self.table = np.zeros((depth, width), dtype=np.float64)
        self.total = 0.0
        self.batches = 0

    # mix32 family instead of the Mersenne universal hashes of the parent
    def _buckets(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.int64)
        return np.stack([bucket_np(keys, s, self.width) for s in self.seeds])

    def step(self) -> None:
        """Advance one batch boundary: decay everything seen so far."""
        if self.decay_factor < 1.0:
            self.table *= self.decay_factor
            self.total *= self.decay_factor
        self.batches += 1

    def absorb(self, delta_table: np.ndarray, n: int) -> None:
        """Add a [depth, width] increment (e.g. from ``kernels.cms_update``)."""
        if delta_table.shape != self.table.shape:
            raise ValueError("increment shape must match sketch table")
        self.table += delta_table
        self.total += float(n)

    def rate(self, keys: np.ndarray) -> np.ndarray:
        """Bias-corrected per-batch rate estimates (upper bounds)."""
        if self.batches == 0:
            return np.zeros(np.asarray(keys).size)
        g = self.decay_factor
        norm = 1.0 / self.batches if g >= 1.0 else (1.0 - g) / (1.0 - g**self.batches)
        return self.estimate(keys) * norm

    def merge(self, other: "DecayingCountMin") -> "DecayingCountMin":
        if (self.width, self.depth) != (other.width, other.depth):
            raise ValueError("sketch shapes must match to merge")
        if self.seeds != other.seeds or self.decay_factor != other.decay_factor:
            raise ValueError("sketch seeds/decay must match to merge")
        out = DecayingCountMin(self.width, self.depth, decay=self.decay_factor)
        out.seeds = self.seeds
        out.table = self.table + other.table
        out.total = self.total + other.total
        out.batches = max(self.batches, other.batches)
        return out

    # ---- checkpoint (DESIGN.md §8) -----------------------------------------
    def state_dict(self) -> dict[str, np.ndarray]:
        return {
            "table": self.table.copy(),
            "scalars": np.array([self.total, float(self.batches)], np.float64),
        }

    def load_state_dict(self, state: dict[str, np.ndarray]) -> None:
        table = np.asarray(state["table"], dtype=np.float64)
        if table.shape != self.table.shape:
            raise ValueError("checkpointed sketch table shape mismatch")
        self.table = table.copy()
        scalars = np.asarray(state["scalars"])
        self.total = float(scalars[0])
        self.batches = int(scalars[1])


# Equal-count runs shorter than this take ``SpaceSaving._fold_one``: below
# it the O(capacity) victim scans cost less than ``_fold_run``'s set-up
# (about 160 us, against 3 to 6 us a scan at 64 counters on a CPU).
_RUN_PATH_MIN = 32


def _rounds_below(first: float, bound: float, c: int, cap: int) -> int:
    """How many terms of ``first, first + c, (first + c) + c, ...``, summed
    in that order, lie below ``bound`` (``first`` does), at most ``cap``."""
    rounds, x = 1, first
    while rounds < cap:
        m = min(cap - rounds, int((bound - x) / c) + 2)
        chain = np.full(m + 1, float(c))
        chain[0] = x
        np.add.accumulate(chain, out=chain)
        below = int(np.searchsorted(chain[1:], bound, side="left"))
        rounds += below
        if below < m:
            break
        x = chain[-1]
    return rounds


class SpaceSaving:
    """Stream-summary with ``capacity`` counters (Metwally et al. 2005).

    Guarantees: every value with true (decayed) count > total/capacity is
    retained; ``counts[v]`` overestimates by at most ``errors[v]``.
    """

    def __init__(self, capacity: int = 64):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.counts: dict[int, float] = {}
        self.errors: dict[int, float] = {}

    def update(self, keys: np.ndarray) -> tuple[int, int, int]:
        """Fold one batch's values in; returns ``(distinct, evictions,
        run_evictions)``: values folded, counters replaced, and how many of
        those replacements took the run path (``_fold_run``).

        Values go in by batch count, highest first, equal counts in
        ascending value order, so evictions never displace a bigger
        newcomer.  The victim is the counter with the least count, the
        oldest (first in the dicts' insertion order) among equals.
        """
        keys = np.asarray(keys, dtype=np.int64)
        if keys.size == 0:
            return 0, 0, 0
        vals, cnts = np.unique(keys, return_counts=True)
        order = np.argsort(-cnts, kind="stable")
        vals, cnts = vals[order], cnts[order]
        bounds = [0, *(np.flatnonzero(np.diff(cnts)) + 1).tolist(), vals.size]
        evictions = run_evictions = 0
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            c = int(cnts[lo])
            i = lo
            while i < hi and (
                len(self.counts) < self.capacity or hi - i < _RUN_PATH_MIN
            ):
                evictions += self._fold_one(int(vals[i]), c)
                i += 1
            if i < hi:
                n = self._fold_run(vals[i:hi], c)
                evictions += n
                run_evictions += n
        return int(vals.size), evictions, run_evictions

    def _fold_one(self, v: int, c: int) -> int:
        """Metwally's step for one value; returns the evictions (0 or 1)."""
        if v in self.counts:
            self.counts[v] += c
            return 0
        if len(self.counts) < self.capacity:
            self.counts[v] = float(c)
            self.errors[v] = 0.0
            return 0
        victim = min(self.counts, key=self.counts.__getitem__)
        floor = self.counts.pop(victim)
        self.errors.pop(victim)
        self.counts[v] = floor + c
        self.errors[v] = floor
        return 1

    def _fold_run(self, run: np.ndarray, c: int) -> int:
        """``_fold_one`` over ``run`` (distinct values, ascending, each with
        batch count ``c``) into a full summary, without a victim scan.

        The counters are kept as arrays sorted by (count, age), where age
        is the dicts' insertion order and every counter a newcomer takes
        is younger than all others.  A newcomer's counter ``floor + c`` is
        never below the ``floor`` it evicted, so evicted floors never
        decrease: the first L counters, those with a count at most
        ``reach`` (the first's count plus ``c``), are the next L victims in
        their sorted order.  While the chain ``reach, reach + c, ...`` stays
        below the rest, the same L counters are the victims of whole rounds,
        and ``np.add.accumulate`` adds ``c`` to each in the loop's own order.
        Floats are summed as ``_fold_one`` sums them, so the result is
        bit-identical to it.  A value monitored when the run starts is
        added to if it is still there at its turn, and is a newcomer if it
        was evicted before.  Returns the evictions.
        """
        k = len(self.counts)
        keys = np.fromiter(self.counts, np.int64, k)
        cnt = np.fromiter(self.counts.values(), np.float64, k)
        err = np.fromiter(self.errors.values(), np.float64, k)
        age = np.argsort(cnt, kind="stable")  # the insertion order
        keys, cnt, err = keys[age], cnt[age], err[age]
        n = run.size
        # positions in ``run`` of the values monitored at the start, then n
        stops = [*np.flatnonzero(np.isin(run, keys)).tolist(), n]
        pos = 0  # next value of ``run`` to fold
        added = 0  # values of ``run`` still monitored at their turn
        for stop in stops:
            while pos < stop:
                reach = cnt[0] + c
                width = int(np.searchsorted(cnt, reach, side="right"))
                rounds = 1
                if width > stop - pos:
                    width = stop - pos
                elif width < k:
                    rounds = _rounds_below(reach, cnt[width], c, (stop - pos) // width)
                else:
                    rounds = (stop - pos) // width
                block = np.full((rounds + 1, width), float(c))
                block[0] = cnt[:width]
                np.add.accumulate(block, axis=0, out=block)
                last = pos + (rounds - 1) * width  # the last round's newcomers
                cnt = np.concatenate([cnt[width:], block[rounds]])
                err = np.concatenate([err[width:], block[rounds - 1]])
                keys = np.concatenate([keys[width:], run[last : last + width]])
                # a newcomer's age is k + its index in ``run``: younger than all
                age = np.concatenate([age[width:], k + last + np.arange(width)])
                pos += rounds * width
                order = np.argsort(cnt, kind="stable")
                cnt, err, keys, age = cnt[order], err[order], keys[order], age[order]
            hit = np.flatnonzero(keys == run[stop]) if stop < n else ()
            if len(hit):  # still monitored at its turn: add to it
                cnt[hit[0]] += c
                order = np.lexsort((age, cnt))
                cnt, err, keys, age = cnt[order], err[order], keys[order], age[order]
                pos = stop + 1
                added += 1
        order = np.argsort(age, kind="stable")
        keys, cnt, err = keys[order].tolist(), cnt[order].tolist(), err[order].tolist()
        self.counts = dict(zip(keys, cnt))
        self.errors = dict(zip(keys, err))
        return n - added

    def decay(self, factor: float) -> None:
        for v in self.counts:
            self.counts[v] *= factor
            self.errors[v] *= factor

    def merge(self, other: "SpaceSaving") -> "SpaceSaving":
        out = SpaceSaving(self.capacity)
        for src in (self, other):
            for v, c in src.counts.items():
                out.counts[v] = out.counts.get(v, 0.0) + c
                out.errors[v] = out.errors.get(v, 0.0) + src.errors[v]
        if len(out.counts) > out.capacity:
            keep = sorted(out.counts, key=out.counts.__getitem__, reverse=True)
            for v in keep[out.capacity :]:
                del out.counts[v], out.errors[v]
        return out

    def candidates(self) -> tuple[np.ndarray, np.ndarray]:
        """(values, counts) sorted by count descending."""
        if not self.counts:
            return np.empty(0, np.int64), np.empty(0, np.float64)
        items = sorted(self.counts.items(), key=lambda kv: -kv[1])
        vals = np.array([v for v, _ in items], dtype=np.int64)
        cnts = np.array([c for _, c in items], dtype=np.float64)
        return vals, cnts

    # ---- checkpoint (DESIGN.md §8) -----------------------------------------
    def state_dict(self) -> dict[str, np.ndarray]:
        """Counters in *insertion order* — eviction and candidate ordering
        tie-break on it, so preserving it makes restore bit-deterministic."""
        vals = np.array(list(self.counts), dtype=np.int64)
        return {
            "values": vals,
            "counts": np.array([self.counts[v] for v in vals], np.float64),
            "errors": np.array([self.errors[v] for v in vals], np.float64),
        }

    def load_state_dict(self, state: dict[str, np.ndarray]) -> None:
        vals = np.asarray(state["values"], dtype=np.int64)
        if vals.size > self.capacity:
            raise ValueError("checkpointed SpaceSaving exceeds capacity")
        self.counts = {
            int(v): float(c) for v, c in zip(vals, np.asarray(state["counts"]))
        }
        self.errors = {
            int(v): float(e) for v, e in zip(vals, np.asarray(state["errors"]))
        }


@dataclasses.dataclass(frozen=True)
class HHSnapshot:
    """Live heavy-hitter view for one attribute."""

    attr: str
    values: np.ndarray  # candidate values, rate-descending
    rates: np.ndarray  # per-batch rate estimates (max over relations)


class StreamHHTracker:
    """Per-attribute HH candidate tracking across micro-batches.

    ``observe(batch)`` decays all summaries one step and folds in the
    batch's join-attribute columns; ``snapshot()`` returns, per share
    attribute, candidates whose estimated per-batch rate crosses the
    threshold — the streaming analogue of ``detect_heavy_hitters``.
    """

    def __init__(
        self,
        query: JoinQuery,
        width: int = 2048,
        depth: int = 4,
        capacity: int = 64,
        decay: float = 0.5,
        seed: int = 0,
        use_device_sketch: bool = False,
        obs: Observability = NULL_OBS,
    ):
        self.query = query
        self.obs = obs
        self.attrs = share_attributes(query)
        self.decay = float(decay)
        self.width = int(width)
        self.seeds = _row_seeds(seed, depth)  # shared by every CMS below
        self.use_device_sketch = bool(use_device_sketch)
        self._ss = {a: SpaceSaving(capacity) for a in self.attrs}
        self._cms: dict[tuple[str, str], DecayingCountMin] = {}
        for a in self.attrs:
            for rel in query.relations_of(a):
                self._cms[(a, rel.name)] = DecayingCountMin(
                    width, depth, seed=seed, decay=decay
                )
        self.batches = 0

    def _columns(self, batch: dict[str, np.ndarray]):
        """(attr, relation name, join column) for every sketched column."""
        return [
            (a, rel.name, np.asarray(batch[rel.name])[:, rel.index_of(a)])
            for a in self.attrs
            for rel in self.query.relations_of(a)
        ]

    def _observe_candidates(self, columns) -> None:
        with self.obs.span("sketch.candidates") as span:
            for a in self.attrs:
                self._ss[a].decay(self.decay)
            folded = np.zeros(3, np.int64)
            for a, _, col in columns:
                folded += self._ss[a].update(col)
            distinct, evictions, run_evictions = folded.tolist()
            span.set(
                distinct=distinct, evictions=evictions, run_evictions=run_evictions
            )

    def observe(self, batch: dict[str, np.ndarray]) -> None:
        columns = self._columns(batch)
        with self.obs.span("sketch.cms"):
            for cms in self._cms.values():
                cms.step()
            for a, rel_name, col in columns:
                cms = self._cms[(a, rel_name)]
                if self.use_device_sketch and col.size:
                    import jax.numpy as jnp

                    from repro.kernels import cms_update

                    delta = np.asarray(
                        cms_update(
                            jnp.asarray(col, dtype=jnp.int32), cms.seeds, cms.width
                        )
                    )
                    cms.absorb(delta.astype(np.float64), col.size)
                else:
                    cms.update(col)
        self._observe_candidates(columns)
        self.batches += 1

    def observe_absorbed(
        self,
        batch: dict[str, np.ndarray],
        deltas: dict[tuple[str, str], np.ndarray],
    ) -> None:
        """``observe`` with the Count-Min increments precomputed elsewhere.

        ``deltas[(attr, rel_name)]`` is the [depth, width] bucket-count
        increment for that column — e.g. from the fused ingest kernel
        (``kernels.ingest_fused``), which shares this tracker's ``seeds``
        so tables stay bit-identical to the host ``observe`` path
        (integer counts are exact in float64).  SpaceSaving candidate
        tracking still runs host-side: it needs the raw values, which the
        sketch buckets discard.
        """
        columns = self._columns(batch)
        with self.obs.span("sketch.cms"):
            for cms in self._cms.values():
                cms.step()
            for a, rel_name, col in columns:
                self._cms[(a, rel_name)].absorb(
                    np.asarray(deltas[(a, rel_name)], dtype=np.float64), col.size
                )
        self._observe_candidates(columns)
        self.batches += 1

    def candidates_of(self, attr: str) -> tuple[np.ndarray, np.ndarray]:
        """Public view of the SpaceSaving candidate set for ``attr`` —
        (values, decayed counts), count-descending.  This is the value set
        planning decisions are made from, and the set ``obs.skewscope``
        audits the sketch against."""
        return self._ss[attr].candidates()

    def rate_in(self, attr: str, rel_name: str, values: np.ndarray) -> np.ndarray:
        """Per-batch rate estimates for ``values`` in ONE relation's
        sketch.  ``rate_of`` takes the max over relations (the planning
        view); the CMS-error audit in ``obs.skewscope`` needs the
        per-relation estimate that exact per-relation counts compare to."""
        values = np.asarray(values, dtype=np.int64)
        if values.size == 0:
            return np.empty(0, np.float64)
        return self._cms[(attr, rel_name)].rate(values)

    def rate_of(self, attr: str, values: np.ndarray) -> np.ndarray:
        """Max per-batch rate over relations containing ``attr``."""
        values = np.asarray(values, dtype=np.int64)
        if values.size == 0:
            return np.empty(0, np.float64)
        rates = [
            self._cms[(attr, rel.name)].rate(values)
            for rel in self.query.relations_of(attr)
        ]
        return np.max(np.stack(rates), axis=0)

    def snapshot(self, threshold: float, max_per_attr: int = 8) -> dict[str, HHSnapshot]:
        out: dict[str, HHSnapshot] = {}
        for a in self.attrs:
            cand, _ = self._ss[a].candidates()
            if cand.size == 0:
                continue
            rates = self.rate_of(a, cand)
            mask = rates >= threshold
            if not mask.any():
                continue
            vals, rates = cand[mask], rates[mask]
            order = np.argsort(-rates, kind="stable")[:max_per_attr]
            out[a] = HHSnapshot(a, vals[order], rates[order])
        return out

    def hh_values(self, threshold: float, max_per_attr: int = 8) -> dict[str, np.ndarray]:
        """The ``plan_with_hh``-shaped view of ``snapshot``."""
        return {
            a: s.values for a, s in self.snapshot(threshold, max_per_attr).items()
        }

    # ---- checkpoint (DESIGN.md §8) -----------------------------------------
    def state_dict(self) -> dict[str, np.ndarray]:
        """Flat array tree of every summary — restoring it into a tracker
        built from the same config resumes estimation bit-for-bit."""
        out: dict[str, np.ndarray] = {
            "batches": np.array([self.batches], np.int64)
        }
        for (a, rel_name), cms in self._cms.items():
            for k, v in cms.state_dict().items():
                out[f"cms/{a}/{rel_name}/{k}"] = v
        for a, ss in self._ss.items():
            for k, v in ss.state_dict().items():
                out[f"ss/{a}/{k}"] = v
        return out

    def load_state_dict(self, state: dict[str, np.ndarray]) -> None:
        self.batches = int(np.asarray(state["batches"])[0])
        for (a, rel_name), cms in self._cms.items():
            cms.load_state_dict(
                {
                    "table": state[f"cms/{a}/{rel_name}/table"],
                    "scalars": state[f"cms/{a}/{rel_name}/scalars"],
                }
            )
        for a, ss in self._ss.items():
            ss.load_state_dict(
                {
                    "values": state[f"ss/{a}/values"],
                    "counts": state[f"ss/{a}/counts"],
                    "errors": state[f"ss/{a}/errors"],
                }
            )

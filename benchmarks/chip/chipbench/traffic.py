"""The one traffic generator: micro-batches from a configuration and a
seed.

A configuration states the relations, the join attribute's distribution
(``keys``), the key domain, the R rows of a batch and the ratio of S rows
to R rows.  Batch ``i`` is drawn from its own stream
``default_rng([i, seed])``, so the same seed gives the same batches in any
order and any number of them.

The join attribute's column comes from ``keys/<kind>.py`` (found by
``spec.load_key_column``), a function ``column(rng, n, *, domain, params,
batch, relation)``; every other attribute is uniform over the domain.
"""
from __future__ import annotations

from typing import Callable

import numpy as np


def _rng(seed: int, i: int) -> np.random.Generator:
    return np.random.default_rng([int(i), int(seed) % (1 << 64)])


def batch_rows(config: dict) -> dict[str, int]:
    """Rows per batch of each relation: the configuration's R rows, and S
    rows at its ratio."""
    (left, _), (right, _) = config["relations"].items()
    n = int(config["batch_rows"])
    return {left: n, right: int(n * float(config["s_per_r"]))}


def make_batch(config: dict, key_column: Callable, seed: int, i: int) -> dict[str, np.ndarray]:
    """Batch ``i`` of the stream: relation name -> int32 rows [n, arity]."""
    rng = _rng(seed, i)
    domain = int(config["domain"])
    key = config["join_attr"]
    out = {}
    for name, n in batch_rows(config).items():
        cols = [
            key_column(rng, n, domain=domain, params=config["keys"], batch=i, relation=name)
            if a == key
            else rng.integers(0, domain, size=n, dtype=np.int64)
            for a in config["relations"][name]
        ]
        out[name] = np.stack(cols, axis=1).astype(np.int32)
    return out


def window_batches(config: dict) -> int:
    """The retained window in batches: the window's R rows over a batch's."""
    per = int(config["batch_rows"])
    rows = int(config["window_rows"])
    if rows % per:
        raise ValueError(f"window of {rows} rows is not a whole number of {per}-row batches")
    return rows // per


def reducer_capacity(config: dict) -> float:
    """The plan's per-reducer capacity ``q``: a share of the R rows of one
    batch, so that the heavy hitters clear the per-batch threshold."""
    return float(config["q_per_batch_row"]) * int(config["batch_rows"])

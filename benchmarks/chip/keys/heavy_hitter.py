"""The join-key column of the SharesSkew paper's §9.1 job: uniform over
the domain with the heavy value moved out of the ordinary draws, then
``fraction`` of the rows, chosen without replacement, set to it.  The
same in every batch and in both relations."""
import numpy as np


def column(rng, n: int, *, domain: int, params: dict, batch: int, relation: str):
    value, fraction = int(params["value"]), float(params["fraction"])
    col = rng.integers(0, domain, size=n, dtype=np.int64)
    col[col == value] = (value + 1 + rng.integers(0, domain - 1)) % domain
    col[col == value] = (value + 7) % domain
    n_hh = int(n * fraction)
    if n_hh:
        col[rng.choice(n, size=n_hh, replace=False)] = value
    return col

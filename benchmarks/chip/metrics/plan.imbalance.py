"""Max over mean per-reducer load of the live plan at the window's end (SkewScope)."""
from chipbench import readers


def read(run):
    return readers.imbalance(run)

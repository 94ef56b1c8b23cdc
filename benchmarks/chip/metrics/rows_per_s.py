"""Rows of R and S of every batch offered in the window, over the time from the window's
start to the return of its last batch (closed loop)."""
from chipbench import readers


def read(run):
    return readers.rows_per_s(run)

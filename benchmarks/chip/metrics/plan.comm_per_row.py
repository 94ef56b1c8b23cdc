"""Emissions shipped to reducers per input row over the window (the paper's cost)."""
from chipbench import readers


def read(run):
    return readers.comm_per_row(run)

"""Tuples the map phase emits to reducers per input row (the paper's communication
cost), as counted by ``comm_tuples`` on the program's ``shuffle.fetch`` span."""
from chipbench import spans


def read(run):
    return spans.count_per_row(run, "shuffle.fetch", "comm_tuples")

"""The program's ``shuffle.execute`` span (running the compiled round on the chips and
waiting for its outputs: map, all_to_all, reduce) per window job, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "shuffle.execute")

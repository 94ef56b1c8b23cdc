"""The program's ``shuffle.lower`` span (building, lowering and compiling the round's
program, or loading it from the persistent compilation cache) per window job, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "shuffle.lower")

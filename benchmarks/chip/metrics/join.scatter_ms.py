"""The engine's ``join.scatter`` spans inside ``join.delta`` (appending the batch to the
binned reducer state, its copy included) per window batch, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "join.scatter", parent="join.delta")

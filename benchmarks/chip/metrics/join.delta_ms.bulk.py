"""The engine's ``join.delta`` span per window batch, in ms."""
from chipbench import readers


def read(run):
    return readers.span_ms_per_batch(run, ("join.delta",))

"""The engine's ``retention.expire`` span (retraction of the batch that leaves
the window) per window batch, in ms."""
from chipbench import readers


def read(run):
    return readers.span_ms_per_batch(run, ("retention.expire",))

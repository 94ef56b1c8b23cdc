"""The engine's ``sketch.update`` and ``drift.check`` spans per window batch, in ms."""
from chipbench import readers


def read(run):
    return readers.span_ms_per_batch(run, ("sketch.update", "drift.check"))

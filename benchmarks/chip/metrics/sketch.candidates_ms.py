"""The engine's ``sketch.candidates`` span (SpaceSaving decay and update, inside
``sketch.update``) per window batch, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "sketch.candidates")

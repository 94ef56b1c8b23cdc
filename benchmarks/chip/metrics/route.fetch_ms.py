"""The engine's ``route.fetch`` spans inside ``route.fused`` (waiting for the fused kernel
and copying its outputs to the host) per window batch, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "route.fetch", parent="route.fused")

"""Bytes the fused pass sends to the device (the rows and the dense route encoding),
as counted on the engine's ``route.fused`` span, per window row."""
from chipbench import spans


def read(run):
    return spans.count_per_row(run, "route.fused", "h2d_bytes")

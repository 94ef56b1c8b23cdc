"""Bytes the fused pass fetches from the device (the kernel's outputs at their padded
shapes), as counted on the engine's ``route.fused`` span, per window row."""
from chipbench import spans


def read(run):
    return spans.count_per_row(run, "route.fused", "d2h_bytes")

"""The fused ingest kernel's share of its HBM roofline, in percent: the bytes the
ingest must move (chipbench/roofline.py) over HBM bandwidth, over its device time."""
from chipbench import readers


def read(run):
    return readers.ingest_roofline(run)

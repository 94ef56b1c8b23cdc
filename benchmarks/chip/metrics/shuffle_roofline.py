"""The round's device program's share of its HBM roofline, in percent: the bytes the
round must move (chipbench/job_roofline.py) over the chips' HBM bandwidth, over the
device time of the window summed over the chips."""
from chipbench import job_roofline


def read(run):
    return job_roofline.shuffle_roofline(run)

"""Share of the traced window in which no operation ran on the device, in percent,
the mean of the cell's chips."""
from chipbench import readers


def read(run):
    return readers.idle_pct(run)

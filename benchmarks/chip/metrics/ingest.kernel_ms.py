"""Device time of the fused ingest kernel per window batch, in ms, from the trace."""
from chipbench import readers


def read(run):
    return readers.kernel_ms(run)

"""The chip benchmark of the SharesSkew join (``benchmarks/chip``).

It runs two paths of the program: the streaming join engine over a
window of micro-batches (``harness.py``), and the batch join, one
MapReduce round a job, on a cell's own chips (``job.py``).

Everything the benchmark measures with lives here and nowhere else: the
traffic generator, the plain reference that decides ``correct``, the
reduction from a device trace to busy time and kernel time, the table of
chip peaks and the byte count of the ingest kernel's roofline.  From the
program it takes only what is under test, the streaming engine or the
batch entry (``plan_shares_skew`` and ``run_distributed``), and its spans
and reports.
"""

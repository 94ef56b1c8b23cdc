"""The chip benchmark of the streaming join engine (``benchmarks/chip``).

Everything the benchmark measures with lives here and nowhere else: the
traffic generator, the plain reference that decides ``correct``, the
reduction from a device trace to busy time and kernel time, the table of
chip peaks and the byte count of the ingest kernel's roofline.  From the
program it takes only the engine under test, its spans and its reports.
"""

"""Self time of the engine's ``ingest`` span per window batch, in ms: its duration less the
union of its children's intervals (children by ``args.parent``), the batch time no span names."""
from chipbench import spans


def read(run):
    return spans.self_ms(run, "ingest")

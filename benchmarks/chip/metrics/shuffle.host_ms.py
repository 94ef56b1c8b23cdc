"""The program's ``shuffle.stage`` (padding and the host-to-device copy) and
``shuffle.fetch`` (the device-to-host copy and the result) spans per window job, in ms."""
from chipbench import spans


def read(run):
    stage = spans.ms_per_batch(run, "shuffle.stage")
    fetch = spans.ms_per_batch(run, "shuffle.fetch")
    if stage is None or fetch is None:
        return None
    return stage + fetch

"""Device time of the all-to-all operations per window job, in ms, from the trace of
the first chip (the trace view holds that chip's operations)."""


def _all_to_all(label: str) -> bool:
    return label.replace("-", "_").startswith("all_to_all")


def read(run):
    t = run.trace
    jobs = run.window()
    if t is None or not jobs:
        return None
    ops = [o for o in t.ops if _all_to_all(o.name)]
    if not ops:
        return None
    return sum(o.dur for o in ops) / 1e6 / len(jobs)

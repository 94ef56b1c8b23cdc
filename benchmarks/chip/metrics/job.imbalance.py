"""The largest reducer load over the mean load (``max_load · reducers / total_load`` on
the program's ``shuffle.fetch`` span), the mean over the window's jobs."""


def read(run):
    window = {b.index for b in run.window()}
    found = [
        e["args"] for e in run.spans
        if e.get("name") == "shuffle.fetch"
        and e.get("args", {}).get("batch") in window
        and e["args"].get("total_load")
    ]
    if not found:
        return None
    return sum(a["max_load"] * a["reducers"] / a["total_load"] for a in found) / len(found)

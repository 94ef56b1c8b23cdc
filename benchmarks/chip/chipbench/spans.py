"""Readers of the engine's own span tree: the ``repro.obs`` events of a
traced run, each with ``args.batch``, ``args.span_id`` and the id of the
span open around it, ``args.parent``.

Every function returns None where the run holds nothing it reads: an
untraced run, or a program whose spans lack the name, the count or the
parent ids.
"""
from __future__ import annotations

from .harness import Run
from .xtrace import Op, merged


def _window_spans(run: Run) -> tuple[set[int], list[dict]]:
    """The window's batch indices and the complete spans of those batches."""
    window = {b.index for b in run.window()}
    return window, [
        e for e in run.spans
        if e.get("ph") == "X" and e.get("args", {}).get("batch") in window
    ]


def ms_per_batch(run: Run, name: str, parent: str | None = None) -> float | None:
    """Milliseconds per window batch in the spans named ``name``; with
    ``parent``, only those whose enclosing span is named ``parent``."""
    window, spans = _window_spans(run)
    if parent is not None:
        names = {e["args"].get("span_id"): e["name"] for e in spans}
        spans = [e for e in spans if names.get(e["args"].get("parent")) == parent]
    found = [e for e in spans if e["name"] == name]
    if not found:
        return None
    return sum(e["dur"] for e in found) / 1e3 / len(window)


def count_per_row(run: Run, name: str, key: str) -> float | None:
    """The count ``key`` that the spans named ``name`` carry, summed over
    the window, over the rows of the batches that carry it."""
    _, spans = _window_spans(run)
    found = [e["args"] for e in spans if e["name"] == name and key in e["args"]]
    if not found:
        return None
    batches = {a["batch"] for a in found}
    rows = sum(b.rows for b in run.window() if b.index in batches)
    if not rows:
        return None
    return sum(a[key] for a in found) / rows


def self_ms(run: Run, name: str) -> float | None:
    """The mean self time of the spans named ``name`` in the window, in
    ms: each one's duration less the union of its children's intervals,
    the children found by ``args.parent`` and clipped to the span."""
    _, spans = _window_spans(run)
    if not any("parent" in e["args"] for e in spans):
        return None
    children: dict[str, list[dict]] = {}
    for e in spans:
        children.setdefault(e["args"].get("parent"), []).append(e)
    found = [e for e in spans if e["name"] == name]
    if not found:
        return None
    total_us = 0.0
    for e in found:
        lo, hi = e["ts"], e["ts"] + e["dur"]
        parts = [
            Op(c["name"], max(c["ts"], lo), min(c["ts"] + c["dur"], hi) - max(c["ts"], lo))
            for c in children.get(e["args"]["span_id"], [])
            if c["ts"] < hi and c["ts"] + c["dur"] > lo
        ]
        total_us += e["dur"] - sum(end - start for start, end in merged(parts))
    return total_us / 1e3 / len(found)

"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples: the device operations of each TPU, and the host spans the
benchmark annotated.  The rest are pure functions of those tuples, so a
test can check them on a hand-made trace:

* ``busy_ns`` — the union of the intervals in which an operation ran;
* ``kernel_ops`` — the operations of one kernel, by its label;
* ``top_ops`` — device time per operation name;
* ``idle_gaps`` — the longest stretches with no operation on the device,
  each named by the innermost host span that covers its middle;
* ``within`` — the operations that lie inside given host spans.

Times are nanoseconds on the trace's clock; the profiler puts device and
host events on one clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple


class Op(NamedTuple):
    name: str  # the operation's label: its HLO instruction name, no suffix
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


class Span(NamedTuple):
    name: str
    start: float
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


def op_label(name: str) -> str:
    """The instruction name of an operation, without its numeric suffix:
    ``"%fused_ingest_dense.1 = (s32[32,131072]...) custom-call(...)"`` ->
    ``"fused_ingest_dense"``.  A name that is not HLO text is kept whole."""
    if name.startswith("%") and " = " in name:
        name = name[1:name.index(" = ")]
    return _SUFFIX.sub("", name)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str, span_names: set[str]) -> tuple[dict[str, list[Op]], list[Span]]:
    """(device plane name -> its operations, host spans named in
    ``span_names``) from one profiler file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        Op(op_label(e.name), e.start_ns, e.duration_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append(Span(e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return devices, spans


def clip(ops: list[Op], lo: float, hi: float) -> list[Op]:
    """The parts of ``ops`` inside ``[lo, hi)``."""
    out = []
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            out.append(Op(o.name, s, e - s))
    return out


def merged(ops: list[Op]) -> list[tuple[float, float]]:
    """The union of the operations' intervals, as sorted disjoint
    ``(start, end)`` pairs."""
    out: list[list[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end)
        else:
            out.append([o.start, o.end])
    return [(s, e) for s, e in out]


def busy_ns(ops: list[Op]) -> float:
    return sum(e - s for s, e in merged(ops))


def kernel_ops(ops: list[Op], name: str) -> list[Op]:
    """Operations whose label is ``name``."""
    return [o for o in ops if o.name == name]


def top_ops(ops: list[Op], n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` operation names with the most device time, in ns."""
    total: dict[str, float] = {}
    for o in ops:
        total[o.name] = total.get(o.name, 0.0) + o.dur
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def _innermost(spans: list[Span], t: float) -> Span | None:
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best


def idle_gaps(
    ops: list[Op], lo: float, hi: float, spans: list[Span], n: int = 10
) -> list[tuple[str, float]]:
    """The ``n`` longest idle stretches of ``[lo, hi)``, in ns, each named
    by the innermost host span covering its middle (``"none"`` where no
    span does)."""
    gaps, t = [], lo
    for s, e in merged(clip(ops, lo, hi)) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        span = _innermost(spans, (s + e) / 2)
        label = "none" if span is None else span.name
        if span is not None and "batch" in span.stats:
            label += f" batch {span.stats['batch']}"
        out.append((label, e - s))
    return out


def within(ops: list[Op], spans: list[Span]) -> list[list[Op]]:
    """For each span, the operations that start inside it."""
    out = [[] for _ in spans]
    for o in ops:
        for i, s in enumerate(spans):
            if s.start <= o.start < s.end:
                out[i].append(o)
                break
    return out

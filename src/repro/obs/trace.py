"""Low-overhead nested-span tracing for the streaming engine (DESIGN.md §10).

One batch through ``StreamingJoinEngine.ingest`` is a tree of phases —
ingest → sketch update → route → delta join → retention/expiry → replan
(solve / migrate / first-kernel compile) → recovery (detect / replay /
repair / verify) — and the only way to see where a batch's time goes is
to clock those phases *as a tree*, not as a flat list of
``perf_counter`` deltas.  ``Tracer`` is that clock:

  * spans are context managers over ``time.perf_counter_ns`` (injectable
    for tests), nested by a plain stack, each stamped with the current
    *batch index* plus a per-batch sequence number — the batch-clocked
    span id ``"<batch>:<seq>"``, so two runs over the same seeded stream
    produce the same id sequence — and with the id of the span open
    around it (``args.parent``), so self time needs no inference from
    timestamps;
  * ``span.set(**counts)`` attaches counts (bytes moved, rows) that land
    in the event's ``args`` when the span closes;
  * every span is mirrored into the JAX profiler as a
    ``jax.profiler.TraceAnnotation`` named ``obs/<name>`` with stats
    ``batch`` and ``span_id``, so a profile of the run shows the engine's
    phases on the device trace's clock.  The prefix keeps the mirror
    apart from annotations a caller puts around the engine (a benchmark's
    ``ingest``); the id's ``:`` keeps the profiler from reading it as a
    number.  Outside a profiler session the annotation records nothing;
  * ``to_chrome()`` exports the Chrome/Perfetto trace-event JSON format
    (``ph: "X"`` complete events in microseconds), so
    ``tracer.dump("out.json")`` loads directly in ``chrome://tracing`` /
    https://ui.perfetto.dev and renders the nesting by time containment;
  * *disabled is free*: a disabled tracer's ``span()`` returns one
    module-level singleton — no span object, no args dict, no clock
    read, no per-call allocation, no JAX import — so leaving trace hooks
    in the fused hot path costs a predicate check per call and nothing
    else.  Callers that want to attach argument dicts guard their
    construction with ``tracer.enabled`` (the ``args=None`` default keeps
    the common call allocation-free).

The tracer is deliberately single-threaded (the engine's batch loop);
thread-fanout code (``mapreduce.straggler``) records per-attempt
latencies into ``obs.metrics`` histograms instead, which lock.
"""
from __future__ import annotations

import json
import time
from typing import Callable


class _NullSpan:
    """The disabled-tracer span: one shared instance, no state, no cost."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **counts) -> None:
        pass


NULL_SPAN = _NullSpan()


def _annotation(name: str, batch: int, span_id: str):
    # imported here: a process that never enables a tracer never imports JAX
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(f"obs/{name}", batch=batch, span_id=span_id)


class _Span:
    """One open span; closing it appends a finished event to the tracer."""

    __slots__ = (
        "_tracer", "name", "cat", "args", "counts", "span_id", "parent",
        "_mirror", "_start_ns",
    )

    def __init__(self, tracer: "Tracer", name: str, cat: str, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.counts = None
        self.span_id = ""
        self.parent = None
        self._mirror = None
        self._start_ns = 0

    def set(self, **counts) -> None:
        """Attach counts to the event this span emits when it closes."""
        if self.counts is None:
            self.counts = counts
        else:
            self.counts.update(counts)

    def __enter__(self) -> "_Span":
        t = self._tracer
        t._seq += 1
        self.span_id = f"{t._batch}:{t._seq}"
        self.parent = t._stack[-1].span_id if t._stack else None
        t._stack.append(self)
        self._mirror = _annotation(self.name, t._batch, self.span_id)
        self._mirror.__enter__()
        self._start_ns = t._clock_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t = self._tracer
        end_ns = t._clock_ns()
        self._mirror.__exit__(*exc)
        if t._stack and t._stack[-1] is self:
            t._stack.pop()
        args = dict(self.args) if self.args else {}
        if self.counts:
            args.update(self.counts)
        args["batch"] = t._batch
        args["span_id"] = self.span_id
        args["parent"] = self.parent
        t.events.append(
            {
                "name": self.name,
                "cat": self.cat,
                "ph": "X",
                "ts": (self._start_ns - t._epoch_ns) / 1e3,  # µs
                "dur": (end_ns - self._start_ns) / 1e3,
                "pid": 0,
                "tid": 0,
                "args": args,
            }
        )
        return False


class Tracer:
    """Nested-span tracer with Chrome/Perfetto trace-event export.

    ``enabled=False`` (the default) makes every hook free: ``span()``
    returns ``NULL_SPAN`` and ``instant()`` returns immediately.
    """

    def __init__(
        self,
        enabled: bool = False,
        clock_ns: Callable[[], int] | None = None,
    ):
        self.enabled = bool(enabled)
        self._clock_ns = clock_ns or time.perf_counter_ns
        self._epoch_ns = self._clock_ns()
        self._batch = -1  # set_batch() before the first ingest
        self._seq = 0
        self._stack: list[_Span] = []
        self.events: list[dict] = []

    # ---- recording ---------------------------------------------------------
    def set_batch(self, batch: int) -> None:
        """Advance the batch clock: span ids restart at ``<batch>:1``."""
        if not self.enabled:
            return
        self._batch = int(batch)
        self._seq = 0

    def span(self, name: str, cat: str = "stream", args: dict | None = None):
        """Context manager clocking one phase.  ``args`` (optional dict)
        lands in the trace event; pass it pre-built and guard expensive
        construction with ``tracer.enabled``."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "stream", args: dict | None = None) -> None:
        """A zero-duration marker (``ph: "i"``) — decisions, triggers."""
        if not self.enabled:
            return
        self._seq += 1
        a = dict(args) if args else {}
        a["batch"] = self._batch
        a["span_id"] = f"{self._batch}:{self._seq}"
        a["parent"] = self._stack[-1].span_id if self._stack else None
        self.events.append(
            {
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",  # thread-scoped instant
                "ts": (self._clock_ns() - self._epoch_ns) / 1e3,
                "pid": 0,
                "tid": 0,
                "args": a,
            }
        )

    @property
    def depth(self) -> int:
        """Current open-span nesting depth (0 outside any span)."""
        return len(self._stack)

    # ---- export ------------------------------------------------------------
    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        """Write ``to_chrome()`` to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
        return path

    def span_names(self) -> list[str]:
        """Distinct event names in first-seen order (test/report helper)."""
        seen: dict[str, None] = {}
        for ev in self.events:
            seen.setdefault(ev["name"], None)
        return list(seen)

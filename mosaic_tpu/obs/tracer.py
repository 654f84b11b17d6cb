"""Span tracer: per-stage histograms, counters, Chrome-trace events.

Reference counterpart: Mosaic has no custom tracer — it leans on the
Spark UI for task timing and records ``last_command``/``last_error``/
``full_error`` into raster tile metadata for post-hoc debugging
(core/raster/operator/gdal/GDALCalc.scala:39-55); micro-benchmarks use
``SparkSuite.benchmark`` (test/SparkSuite.scala:30-36).  Standalone, we
supply the equivalent surface ourselves:

* ``tracer`` — process-global span timer.  Each span aggregates
  total/calls/max (the original flat counters) **and** an
  exponential-bucket histogram so ``report()`` carries p50/p95/p99 per
  stage.  Spans also append to a bounded event ring that
  ``obs.chrometrace.export_chrome_trace`` turns into a Perfetto-loadable
  JSON timeline.  Disabled by default; enable with ``tracer.enable()``
  or ``MOSAIC_TPU_TRACE=1``.  ``MosaicContext.call`` wraps every by-name
  dispatch in a span, so external engines driving the string surface get
  per-function wall times for free.
* **Profiler annotations** — while a ``jax.profiler`` session is
  recording, every span (tracer enabled or not) also enters a
  ``jax.profiler.TraceAnnotation("mosaic/<name>")``, so program stages
  land in the device trace on the profiler's own clock, beside the
  device ops.  The check is one static ``TraceMe`` probe, made only
  once JAX is imported; the tracer itself never imports JAX.
* **Trace-scoped span trees** — the span stack lives in a
  ``contextvars.ContextVar`` (not a thread-local), so it follows the
  active :class:`~mosaic_tpu.obs.context.TraceContext`: every completed
  span carries its trace id, a process-unique span id, and its parent's
  span id.  ``report()["traces"]`` groups spans per trace;
  two interleaved SQL queries land in two distinct trees.
* ``record_command`` / ``record_error`` — the GDALCalc metadata pattern:
  raster operators stamp what ran (and what failed) into ``tile.meta``;
  both also bump registry counters so fleet-wide rates are visible.
* ``device_trace`` — context manager around ``jax.profiler.trace`` for
  XLA/TPU timeline captures (inspect with tensorboard or xprof; the
  program's spans appear in it as ``mosaic/<name>`` host events).

``tracer.enable()`` also enables the metrics registry (span call-sites
feed counters/gauges into it); ``disable()`` turns the registry back off
unless ``MOSAIC_TPU_METRICS`` asked for it independently.  Completed
spans additionally land in the flight recorder (``obs.recorder``) so a
crash dump contains the failing span chain.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from .context import current_trace, next_span_id
from .metrics import Histogram, metrics
from .recorder import recorder

__all__ = ["Tracer", "tracer", "SpanEvent", "record_command",
           "record_error", "device_trace"]

_MAX_EVENTS = 100_000   # bounded Chrome-trace ring (~10 MB of JSON)

#: active span stack: tuple of (name, span_id) pairs.  A ContextVar
#: (copy-on-write tuples) instead of a thread-local list so the stack
#: follows the trace context across threads and executors.
_SPAN_STACK: "contextvars.ContextVar[Tuple[Tuple[str, int], ...]]" = \
    contextvars.ContextVar("mosaic_span_stack", default=())


#: what ``Tracer.span`` returns with the tracer off and no profiler
#: recording (a ``nullcontext`` is reusable and reentrant)
_NO_SPAN = contextlib.nullcontext()


def _profiler_annotation(name: str):
    """A ``TraceAnnotation("mosaic/<name>")`` while a ``jax.profiler``
    session is recording, else None.  Looks JAX up in ``sys.modules``:
    the tracer never imports it."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)   # None mid-import too
    return cls("mosaic/" + name) if cls is not None and cls.is_enabled() \
        else None


class SpanEvent(NamedTuple):
    """One completed span in the event ring."""

    qual: str                  # qualified name ("outer/inner")
    start_s: float             # offset from the tracer epoch
    dur_s: float
    tid: int                   # python thread ident
    native_tid: int            # OS thread id (Perfetto lanes)
    trace_id: Optional[str]    # active TraceContext (None outside)
    trace_name: Optional[str]
    span_id: int
    parent_id: Optional[int]
    error: Optional[str]       # "ExcType: msg" when the body raised


class _Span:
    __slots__ = ("name", "total_s", "calls", "max_s", "hist")

    def __init__(self, name: str):
        self.name = name
        self.total_s = 0.0
        self.calls = 0
        self.max_s = 0.0
        self.hist = Histogram(name)


class Tracer:
    """Span wall-times + named counters, thread-safe, ~zero cost when
    disabled (one attribute check and one profiler probe per span)."""

    def __init__(self):
        self._enabled = bool(os.environ.get("MOSAIC_TPU_TRACE"))
        self._lock = threading.Lock()
        self._spans: Dict[str, _Span] = {}
        self._counters: Dict[str, float] = {}
        self._events: "collections.deque[SpanEvent]" \
            = collections.deque(maxlen=_MAX_EVENTS)
        self._epoch = time.perf_counter()

    # -- switches
    def enable(self) -> None:
        # graftlint: ignore[lock-unguarded-attr] — GIL-atomic bool store; probes read it unlocked by design
        self._enabled = True
        metrics.enable()

    def disable(self) -> None:
        # graftlint: ignore[lock-unguarded-attr] — GIL-atomic bool store; probes read it unlocked by design
        self._enabled = False
        if not os.environ.get("MOSAIC_TPU_METRICS"):
            metrics.disable()

    @property
    def enabled(self) -> bool:
        return self._enabled

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()
            self._events.clear()
            self._epoch = time.perf_counter()
        metrics.reset()

    # -- spans
    def span(self, name: str):
        """Context manager timing its body as span ``name``."""
        ann = _profiler_annotation(name)
        if ann is None and not self._enabled:
            return _NO_SPAN
        if not self._enabled:
            return ann
        return self._recorded(name, ann)

    @contextlib.contextmanager
    def _recorded(self, name: str, ann):
        """A span of the enabled tracer: stack, ring, histogram and
        flight-recorder event, around the profiler annotation ``ann``
        (None when no profiler is recording)."""
        stack = _SPAN_STACK.get()
        sid = next_span_id()
        parent = stack[-1][1] if stack else None
        qual = "/".join([n for n, _ in stack] + [name])
        token = _SPAN_STACK.set(stack + ((name, sid),))
        t0 = time.perf_counter()
        err: Optional[str] = None
        try:
            with ann if ann is not None else _NO_SPAN:
                yield
        except BaseException as e:
            err = f"{type(e).__name__}: {e}"[:200]
            raise
        finally:
            dt = time.perf_counter() - t0
            _SPAN_STACK.reset(token)
            ctx = current_trace()
            try:
                ntid = threading.get_native_id()
            except Exception:
                ntid = threading.get_ident()
            ev = SpanEvent(
                qual, t0 - self._epoch, dt, threading.get_ident(),
                ntid, ctx.trace_id if ctx else None,
                ctx.name if ctx else None, sid, parent, err)
            with self._lock:
                s = self._spans.setdefault(qual, _Span(qual))
                s.total_s += dt
                s.calls += 1
                s.max_s = max(s.max_s, dt)
                s.hist.observe(dt)
                self._events.append(ev)
            extra = {"error": err} if err else {}
            recorder.record("span", name=qual, span=sid,
                            parent=parent, dur_s=round(dt, 6), **extra)

    def current_label(self) -> Optional[str]:
        """Innermost active span in this context (None outside spans).
        Used by ``obs.jaxmon`` to attribute anonymous JAX compile events
        to whatever stage triggered them."""
        stack = _SPAN_STACK.get()
        return "/".join(n for n, _ in stack) if stack else None

    # -- counters
    def count(self, name: str, value: float = 1.0) -> None:
        if not self._enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    # -- Chrome-trace events
    def events(self) -> List[SpanEvent]:
        """Snapshot of completed :class:`SpanEvent` records, oldest
        first."""
        with self._lock:
            return list(self._events)

    # -- reporting
    def report(self) -> Dict[str, object]:
        """One-stop snapshot: per-stage span histograms plus everything
        the metrics registry holds (counters merged; tracer-local names
        win on collision), plus per-trace span trees under
        ``"traces"``: ``{trace_id: {"name": ..., "spans": [...]}}``
        with each span carrying ``span_id``/``parent_id`` links."""
        reg = metrics.report()
        with self._lock:
            spans = {}
            for n, s in self._spans.items():
                h = s.hist.snapshot()
                spans[n] = {"total_s": s.total_s, "calls": s.calls,
                            "max_s": s.max_s, "p50_s": h["p50"],
                            "p95_s": h["p95"], "p99_s": h["p99"]}
            counters = dict(reg["counters"])
            counters.update(self._counters)
            traces: Dict[str, dict] = {}
            for ev in self._events:
                if ev.trace_id is None:
                    continue
                t = traces.setdefault(
                    ev.trace_id, {"name": ev.trace_name, "spans": []})
                rec = {"name": ev.qual, "span_id": ev.span_id,
                       "parent_id": ev.parent_id, "start_s": ev.start_s,
                       "dur_s": ev.dur_s, "thread": ev.native_tid}
                if ev.error:
                    rec["error"] = ev.error
                t["spans"].append(rec)
            return {
                "spans": spans,
                "counters": counters,
                "gauges": reg["gauges"],
                "histograms": reg["histograms"],
                "traces": traces,
            }

    def format_report(self) -> str:
        rep = self.report()
        lines = [f"{'span':<44} {'calls':>6} {'total_s':>9} "
                 f"{'p50_s':>8} {'p95_s':>8} {'max_s':>8}"]
        for n, s in sorted(rep["spans"].items(),
                           key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"{n:<44} {s['calls']:>6} "
                         f"{s['total_s']:>9.4f} {s['p50_s']:>8.4f} "
                         f"{s['p95_s']:>8.4f} {s['max_s']:>8.4f}")
        for tid, t in sorted(rep["traces"].items()):
            errs = sum(1 for s in t["spans"] if s.get("error"))
            lines.append(f"trace {tid} ({t['name']}): "
                         f"{len(t['spans'])} spans"
                         + (f", {errs} errored" if errs else ""))
        for n, v in sorted(rep["counters"].items()):
            lines.append(f"counter {n} = {v:g}")
        for n, v in sorted(rep["gauges"].items()):
            lines.append(f"gauge {n} = {v:g}")
        for n, h in sorted(rep["histograms"].items()):
            lines.append(f"hist {n}: count={h['count']} "
                         f"p50={h['p50']:g} p95={h['p95']:g} "
                         f"p99={h['p99']:g}")
        return "\n".join(lines)


tracer = Tracer()


# -- raster-op provenance (reference: GDALCalc.scala:39-55 records
#    last_command / last_error / full_error into tile metadata)

def record_command(tile, command: str) -> None:
    tile.meta["last_command"] = command
    metrics.count("raster/commands")


def record_error(tile, err: BaseException) -> None:
    tile.meta["last_error"] = f"{type(err).__name__}: {err}"[:200]
    tile.meta["full_error"] = repr(err)
    metrics.count(f"raster/errors/{type(err).__name__}")


@contextlib.contextmanager
def device_trace(logdir: str, host_tracer_level: int = 2):
    """Capture an XLA/TPU profiler timeline into ``logdir`` (reference
    analogue: the Spark UI stage timeline).  View with xprof/tensorboard.

    ``host_tracer_level`` is the profiler's host level: 1 keeps user
    annotations only (the program's ``mosaic/*`` spans among them), 2
    adds the runtime's own host events, 3 is verbose.  The Python
    function tracer stays off."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = host_tracer_level
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()

"""Double-buffered host↔device streaming executor.

The chipping/join hot path repeats one shape: a big host batch is cut
into chunks, each chunk goes device-side, a jitted kernel runs, and a
host pass (f64 recheck, f64 re-rank, plain np.asarray) consumes the
result.  Run naively that is a serial put→compute→fetch→host loop;
every stage idles while the others work.  :func:`stream` overlaps the
three (the 3DPipe join pipeline shape, arxiv 2604.19982):

* ``put(chunk N+1)`` — ``jax.device_put`` is asynchronous, so the
  host→device transfer of the NEXT chunk is issued right after chunk
  N's compute is dispatched and rides along while the device works;
* ``compute(chunk N)`` — jitted dispatch, returns device arrays
  without blocking;
* ``consume(chunk N-1)`` — runs on ONE worker thread; its first act
  (``np.asarray`` on the device result) blocks THAT thread until the
  device finishes, so the device→host copy and the host-side f64 work
  overlap the next chunk's compute.  A single worker keeps results in
  chunk order and the host pass free of locking.

Buffer donation: wrap the kernel with :func:`donate_jit` so each
chunk's device input buffer is donated to its launch — the executor
never reuses a chunk's input, and donation lets XLA alias it instead
of holding both live (halves the steady-state footprint of the
streamed join).  CPU backends ignore donation; the wrapper skips it
there to avoid the per-launch warning.
"""

from __future__ import annotations

from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, List, Optional

import numpy as np

from ..obs import metrics, tracer
from ..resilience import faults

__all__ = ["stream", "chunk_rows", "donate_jit", "staged_put"]

#: fetches allowed in flight before the dispatch loop drains the
#: oldest — double buffering needs exactly one fetch overlapping the
#: next chunk's compute; anything beyond that only accumulates host
#: and device buffers with total stream length
_MAX_INFLIGHT_FETCHES = 2

#: pressure-driven halving floor: a slice this short never splits
#: (guards against a pathological budget dissolving the stream into
#: per-row launches)
_MIN_SHRINK_ROWS = 64

#: iterator-exhaustion sentinel for the lazy chunk pull (``None`` is a
#: legal chunk payload, so exhaustion needs its own marker)
_DONE = object()


def _tree_bytes(x) -> int:
    """Total buffer bytes across a pytree's array leaves (0 for
    leaves with no nbytes — slices, scalars, handles)."""
    import jax
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(x))


def chunk_rows(n: int, chunk: int) -> List[slice]:
    """Row slices cutting ``n`` rows into ``chunk``-sized pieces (the
    last may be short)."""
    chunk = max(1, int(chunk))
    return [slice(s, min(s + chunk, n)) for s in range(0, n, chunk)]


def donate_jit(fn, donate_argnums=(0,)):
    """``jax.jit`` with donated input buffers where the backend honors
    donation (TPU/GPU); plain ``jit`` on CPU, which ignores donation
    and would warn on every launch."""
    import jax
    if jax.devices()[0].platform == "cpu":
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=donate_argnums)


def _to_host(out):
    import jax
    return jax.tree_util.tree_map(np.asarray, out)


def staged_put(payload, site: str = "pipeline.staged",
               put: Optional[Callable] = None):
    """Stage one host batch device-side through the pipeline's
    accounting choke: ``jax.device_put`` (or ``put``), H2D byte
    metrics + per-query ticket charge, and a device-memory ledger
    registration under ``site``.  Returns ``(device_value, token)``;
    the caller owns the token and must ``memwatch.release(token)``
    once the staged buffer is consumed (token is None whenever the
    ledger is off).  This is the single-launch counterpart of
    :func:`stream`'s internal staging — non-streamed call sites (the
    serve layer's micro-batch launch) use it so the jit-raw-device-put
    lint choke and the leak sentinel both see their transfers."""
    import jax
    from ..obs.inflight import charge_h2d_bytes, inflight
    from ..obs.memwatch import device_keys_of, memwatch
    dev = (put or jax.device_put)(payload)
    tok = None
    # the tree walk is skipped entirely when nothing is listening
    if metrics.enabled or inflight._by_trace or memwatch.enabled:
        nb = _tree_bytes(dev)
        if metrics.enabled:         # host->device staging bytes
            metrics.count("pipeline/h2d_bytes", nb)
        charge_h2d_bytes(nb)        # per-query attribution
        if memwatch.enabled:
            tok = memwatch.register(site, nb,
                                    devices=device_keys_of(dev))
    return dev, tok


def stream(chunks: Iterable, compute: Callable,
           put: Optional[Callable] = None,
           consume: Optional[Callable] = None,
           observe: Optional[Callable] = None,
           site: str = "pipeline.stream") -> list:
    """Run ``chunks`` through the double-buffered pipeline; returns the
    per-chunk results in order.

    ``put(payload) -> device input`` (default ``jax.device_put``),
    ``compute(device input) -> device output`` (a jitted fn — must
    dispatch asynchronously), ``consume(i, payload, host output) ->
    result`` (optional; receives the output already fetched to host
    numpy, runs on the worker thread in chunk order).  Without
    ``consume`` the host-fetched outputs themselves are returned.

    ``observe(i, payload, seconds)`` (optional) receives each chunk's
    launch wall time — compute dispatch to host-fetch completion,
    clamped to the previous chunk's completion so the per-chunk spans
    are disjoint and sum to (at most, and in steady state almost
    exactly) the pipeline's busy wall time.  This is the kernel
    ledger's wall-time feed (``obs.profiler``); callbacks run on the
    single worker thread, in chunk order.  An observer that raises
    cannot kill the stream: the call is fenced — the error is counted
    (``pipeline/observe_errors``) and flight-recorded once per stream,
    and the chunk completes normally.

    Stage spans (``obs.tracer``, and ``mosaic/<span>`` in a recording
    ``jax.profiler`` trace): the dispatch loop runs ``pipeline/put``
    (staging, the caller's ``put`` included), ``pipeline/dispatch``
    (``compute``) and ``pipeline/wait`` (blocked on the oldest fetch);
    the worker runs ``pipeline/fetch`` (the device finishing the chunk,
    then the device->host copy) and ``pipeline/consume``.  With the
    metrics registry on, each stream adds to ``pipeline/head_s`` (entry
    to the first dispatch), ``pipeline/tail_s`` (the last fetch's return
    to the stream's), ``pipeline/put_s`` and ``pipeline/wait_s`` (the
    sums of those spans).

    ``site`` names this stream in the device-memory ledger
    (``obs.memwatch``): each chunk's staged input registers as
    ``<site>/staged`` and its device output as ``<site>/out``, both
    released when the worker's host fetch completes — so the ledger's
    live-bytes gauges track the pipeline's true in-flight footprint
    and the leak sentinel can name the site that failed to release.

    Memory footprint is bounded two ways:

    * the dispatch loop keeps at most ``_MAX_INFLIGHT_FETCHES``
      fetches outstanding, resolving the oldest before dispatching
      further — completed host chunks and queued work items no longer
      accumulate with total stream length (double buffering is
      preserved: the next chunk's compute still overlaps the previous
      chunk's drain);
    * under memory pressure (``obs.memwatch.mem_budget`` past
      ``mosaic.mem.pressure.high``), the NEXT chunk — when it is a
      row ``slice`` — is halved before staging (repeatedly, floor
      ``_MIN_SHRINK_ROWS`` rows), counted in ``mem/chunk_shrink``.
      Results stay bit-identical because consumers key on the slice
      payload, not the chunk index: the same rows arrive, in order,
      across more launches (degrade, not die).

    Cancellation: each loop iteration starts with an
    ``obs.inflight.checkpoint`` probe, so a query cancelled (or past
    its deadline) mid-stream stops within one chunk boundary.
    Exceptions from any stage — including :class:`~..obs.inflight.
    QueryCancelled` from the probe — propagate to the caller; the
    worker is drained first so no device work is abandoned mid-flight
    (the executor's ``with`` block joins the worker on the way out, so
    a cancelled stream leaks no threads or in-flight device buffers).

    ``chunks`` may be any iterable — including a GENERATOR that
    produces chunks lazily (the out-of-core chip store's scan path,
    ``store.reader.ChipStore.iter_chunks``).  The pipeline never
    materializes the chunk list: it pulls exactly one chunk ahead of
    the running compute (the double-buffer look-ahead), so the host
    working set stays bounded by the in-flight window regardless of
    how many chunks — or how many bytes — the source will eventually
    yield."""
    import time as _time
    t_enter = _time.perf_counter()
    import jax
    from ..obs.inflight import charge_d2h_bytes, checkpoint, inflight
    from ..obs.memwatch import device_keys_of, mem_budget, memwatch
    if put is None:
        put = jax.device_put
    obs_state = {"last_done": 0.0, "observe_failed": False,
                 "shrunk": False}
    # stage clocks behind the pipeline/* counters: head and tail of
    # the stream, and the dispatch loop's put and wait seconds
    clock = {"head_s": 0.0, "fetched": 0.0, "put_s": 0.0, "wait_s": 0.0}

    def fetch(i, payload, out, dispatch_t, tok_in, tok_out):
        try:
            faults.maybe_fail("pipeline.fetch")
            with tracer.span("pipeline/fetch"):
                host = _to_host(out)    # blocks the WORKER until ready
        finally:
            # the chunk's device buffers are drained — input consumed
            # by the launch, output copied out — and both must leave
            # the ledger even when the fetch itself unwinds (fault,
            # cancel): a raise above this line used to strand both
            # tokens until the query-complete sentinel swept them
            memwatch.release(tok_out)
            memwatch.release(tok_in)
        now = _time.perf_counter()
        clock["fetched"] = now      # single worker: the last is the tail's
        if observe is not None:     # single worker: in-order, race-free
            start = max(dispatch_t, obs_state["last_done"])
            obs_state["last_done"] = now
            try:
                observe(i, payload, now - start)
            except Exception as exc:
                # observability must never take down the data path:
                # count every failure, flight-record the first per
                # stream (single worker, so the flag is race-free)
                metrics.count("pipeline/observe_errors")
                if not obs_state["observe_failed"]:
                    obs_state["observe_failed"] = True
                    from ..obs import recorder
                    recorder.record(
                        "pipeline_observe_error", chunk=i,
                        error=f"{type(exc).__name__}: {exc}")
        if inflight._by_trace:      # per-query device->host attribution
            charge_d2h_bytes(_tree_bytes(host))
        if consume is None:
            return host
        with tracer.span("pipeline/consume"):
            return consume(i, payload, host)

    def staged(payload):
        t0 = _time.perf_counter()
        with tracer.span("pipeline/put"):
            dev = staged_put(payload, site=f"{site}/staged", put=put)
        clock["put_s"] += _time.perf_counter() - t0
        return dev

    def wait(fut):
        t0 = _time.perf_counter()
        with tracer.span("pipeline/wait"):
            results.append(fut.result())
        clock["wait_s"] += _time.perf_counter() - t0

    # lazy source: chunks are pulled one at a time from the iterator —
    # a split pushes its halves back onto the head of this small deque,
    # so the pending window never holds more than one source chunk's
    # worth of slices
    source = iter(chunks)
    pending: deque = deque()

    def pull() -> bool:
        """Ensure at least one chunk is pending; False when the source
        is exhausted."""
        if not pending:
            nxt = next(source, _DONE)
            if nxt is _DONE:
                return False
            pending.append(nxt)
        return True

    def maybe_split():
        # degrade-not-die: while any device sits past the pressure
        # high-water mark, halve the next chunk's rows before staging
        # it.  Only row slices split (the array-backed call sites chunk
        # by slice); consumers key on the slice, so the extra
        # boundaries are invisible in the results.
        while (mem_budget.shrink_needed()
               and pending and isinstance(pending[0], slice)
               and (pending[0].stop - pending[0].start) > _MIN_SHRINK_ROWS):
            sl = pending.popleft()
            mid = (sl.start + sl.stop) // 2
            pending.appendleft(slice(mid, sl.stop))
            pending.appendleft(slice(sl.start, mid))
            if metrics.enabled:
                metrics.count("mem/chunk_shrink")
            if not obs_state["shrunk"]:   # flight-record once per stream
                obs_state["shrunk"] = True
                from ..obs import recorder
                recorder.record("mem_chunk_shrink", site=site,
                                rows=sl.stop - sl.start)

    if not pull():
        return []
    results: list = []
    with ThreadPoolExecutor(max_workers=1) as pool:
        futs: deque = deque()
        maybe_split()
        payload = pending.popleft()
        dev, tok = staged(payload)
        try:
            i = 0
            while payload is not _DONE:
                checkpoint("pipeline.stream")   # chunk-boundary cancel
                # latency chaos: "pipeline.chunk" mode=delay stalls the
                # dispatch loop (the cancellation drill's stall point —
                # a cancel landing mid-stall raises at the NEXT chunk's
                # checkpoint, one boundary later)
                faults.stall("pipeline.chunk")
                dispatch_t = _time.perf_counter()
                if i == 0:
                    clock["head_s"] = dispatch_t - t_enter
                with tracer.span("pipeline/dispatch"):
                    out = compute(dev)
                tok_out = memwatch.register(
                    f"{site}/out", _tree_bytes(out),
                    devices=device_keys_of(out)) \
                    if memwatch.enabled else None
                if pull():
                    maybe_split()
                    nxt_payload = pending.popleft()
                    nxt = staged(nxt_payload)    # overlap H2D w/ compute
                else:
                    nxt_payload, nxt = _DONE, (None, None)
                futs.append(pool.submit(fetch, i, payload, out,
                                        dispatch_t, tok, tok_out))
                (dev, tok), payload = nxt, nxt_payload
                # bounded in-flight window: resolve the oldest fetch
                # once the window fills, so host results and queued
                # work items stop scaling with total stream length
                while len(futs) > _MAX_INFLIGHT_FETCHES:
                    wait(futs.popleft())
                i += 1
            while futs:
                wait(futs.popleft())
        finally:
            # a stream unwinding mid-loop (cancel, deadline, fault)
            # has staged the next chunk without dispatching it — drop
            # its registration so clean cancellation never reads as a
            # leak (in-flight fetches release their own tokens as the
            # executor exit joins the worker)
            memwatch.release(tok)
    if metrics.enabled:
        metrics.count("pipeline/head_s", clock["head_s"])
        metrics.count("pipeline/tail_s",
                      _time.perf_counter() - clock["fetched"])
        metrics.count("pipeline/put_s", clock["put_s"])
        metrics.count("pipeline/wait_s", clock["wait_s"])
    return results

"""Causal trace-context propagation across async engine boundaries.

The PR-2 recorder stamps every event with a thread lane, but a serving
request's life crosses FOUR of them: the client thread that admits it,
the micro-batcher thread that coalesces and dispatches it, the tracing
thread where the collectives are noted, and (for prewarm replays) the
pool worker that first-dispatches the program. Per-thread span stacks
cannot answer "what happened to THIS request" — this module can: a
`TraceContext(trace_id, span_id, parent_id)` minted at admission rides a
`contextvars.ContextVar` through every synchronous hop and is handed
across threads/queues EXPLICITLY (`capture` the context with the work
item, `activate` it where the work runs — contextvars do not cross
thread boundaries by themselves, and implicit inheritance would lie
about fan-in points anyway).

The fan-in is first-class: one coalesced micro-batch flush span records
its N parent request span/trace ids (`fan_in`), and the Chrome-trace
exporter (`_tracefmt`) renders flow arrows (`ph:"s"/"t"/"f"`) from each
admission span through the flush to the dispatch/collective events — so
Perfetto draws the request's causal path across host threads and the
virtual device track.

Hot-path contract (tests/test_obs.py): with the recorder disabled,
`current()` / `mint_request()` / `fan_in()` are no-ops behind one
attribute load — no ContextVar read, no allocation.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

from ._recorder import RECORDER

#: per-process random tag (16 bits) + a 36-bit counter: ids stay inside
#: 2**52 < 2**53 so they survive a JSON round-trip through readers that
#: parse to double, the counter space (~68e9 ids) outlives any serving
#: process, and two processes' bundles merge without collision except at
#: the 1/65536 tag-clash odds — acceptable for display, never used as a
#: key across processes
_PROC_TAG = int.from_bytes(os.urandom(2), "big") << 36
_ids = itertools.count(1)
_ids_lock = threading.Lock()


def _next_id() -> int:
    with _ids_lock:
        return _PROC_TAG | (next(_ids) & 0xFFFFFFFFF)


def hex_id(ident: Optional[int]) -> Optional[str]:
    """Display form of a trace/span id (reports)."""
    return None if ident is None else f"0x{ident:013x}"


@dataclass(frozen=True)
class TraceContext:
    """One logical unit of work's position in the causal tree. A trace
    that has no span yet (`open_trace`) has `span_id` None: the first
    child opened under it is the trace's root, with no parent."""
    trace_id: int
    span_id: Optional[int]
    parent_id: Optional[int] = None

    def child(self) -> "TraceContext":
        """A child unit within the SAME trace (new span id, this span as
        parent) — a dispatch launched on behalf of a request."""
        return TraceContext(self.trace_id, _next_id(), self.span_id)


_CURRENT: contextvars.ContextVar[Optional[TraceContext]] = \
    contextvars.ContextVar("sml_tpu_trace", default=None)


def current() -> Optional[TraceContext]:
    """The active context on this thread (None when the recorder is off
    — the one-attribute-load disabled path — or nothing is active)."""
    if not RECORDER.enabled:
        return None
    return _CURRENT.get()


def new_trace() -> Optional[TraceContext]:
    """Mint a fresh root context (None when the recorder is off)."""
    if not RECORDER.enabled:
        return None
    return TraceContext(_next_id(), _next_id(), None)


def open_trace() -> Optional[TraceContext]:
    """A fresh trace with no span of its own yet (None when the recorder
    is off): the first `PROFILER.span` under it becomes the trace's root
    span, with no parent — how a fit's span tree starts."""
    if not RECORDER.enabled:
        return None
    return TraceContext(_next_id(), None, None)


def mint_request(rows: Optional[int] = None,
                 ts: Optional[float] = None) -> Optional[TraceContext]:
    """Admission point of a serving request: mint a root context AND land
    its admission span (a zero-duration `trace.request` span on the
    admitting thread's lane — the flow arrows' source anchor)."""
    ctx = new_trace()
    if ctx is not None:
        args = {"trace": ctx.trace_id, "span": ctx.span_id}
        if rows is not None:
            args["rows"] = int(rows)
        RECORDER.emit("span", "trace.request", dur=0.0, ts=ts, args=args)
    return ctx


def fan_in(parents: Sequence[TraceContext]) -> Optional[TraceContext]:
    """The coalescing edge: N parent units merge into ONE downstream unit
    (a micro-batch flush). Returns a fresh context for the merged work —
    the caller records the parent span/trace ids on the flush span
    (`parent_traces` / `parent_spans` args) so the exporter can draw one
    arrow per parent into it."""
    if not RECORDER.enabled or not parents:
        return None
    return TraceContext(_next_id(), _next_id(), None)


@contextlib.contextmanager
def activate(ctx: Optional[TraceContext]) -> Iterator[Optional[TraceContext]]:
    """Install a CAPTURED context on the current thread for the duration
    of a block — the explicit cross-thread/cross-queue handoff. A None
    context (recorder off at capture time) is a no-op."""
    if ctx is None:
        yield None
        return
    token = _CURRENT.set(ctx)
    try:
        yield ctx
    finally:
        _CURRENT.reset(token)


def trace_args(args: Optional[dict] = None) -> dict:
    """`args` (or a fresh dict) with the active context's trace/span ids
    folded in — the one-liner for emit sites that should tag their event
    when (and only when) a context is riding the thread."""
    out = dict(args) if args else {}
    ctx = current()
    if ctx is not None:
        out.setdefault("trace", ctx.trace_id)
        out.setdefault("span", ctx.span_id)
    return out


def parent_ids(parents: Sequence[TraceContext]) -> List[int]:
    return [p.span_id for p in parents]


def parent_traces(parents: Sequence[TraceContext]) -> List[int]:
    return [p.trace_id for p in parents]

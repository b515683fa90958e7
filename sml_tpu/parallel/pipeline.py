"""Shared double-buffered staging pipeline.

The `sml.infer.prefetchBatches` pattern from `ml/inference.py` —
prep-on-worker-threads with bounded lookahead, serial dispatch, bounded
in-flight window, ordered drain — generalized so the batch-inference
path and the out-of-core chunked-ingest path (`ml/_chunked.py`) run the
SAME pipeline instead of two hand-rolled deque loops:

    item i+1's PREP (pandas/numpy feature work, chunk quantization —
    C paths that release the GIL) runs on worker threads while item i's
    DISPATCH output (an async device handle: dispatched program, H2D
    put) is still in flight; DRAIN forces/finalizes results in order.

Observability is built in, not bolted on per caller: every dispatch and
drain lands a `<family>.dispatch` / `<family>.drain` recorder event
(`infer.*` for inference, `ingest.*` for the chunk plane) — the
i+1-dispatches-before-i-drains event order IS the pipelining proof the
tests assert — and every in-flight item holds a stall-watchdog ticket
(`obs._watchdog`), so a wedged H2D transfer or lost device is flagged
with stacks instead of hanging silently.

With the recorder disabled the instrumentation costs one attribute load
per item (the PR-2 contract); the pipeline itself runs regardless.
"""

from __future__ import annotations

import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, Optional

_tls = threading.local()


def mark_host_worker() -> None:
    """The `initializer` of the process's host-side worker pools (the two
    below and `ml/_column_plan`'s): code that would fan its own work out
    over the column pool asks `on_host_worker()` first and runs inline on
    a worker, so a task never submits to the pool it runs on and the
    pools do not multiply each other's threads."""
    _tls.host_worker = True


def on_host_worker() -> bool:
    return getattr(_tls, "host_worker", False)


def prefetch_pipeline(items: Iterable, prep: Callable, dispatch: Callable,
                      drain: Callable, *, depth: int, workers: int = 4,
                      family: str = "infer",
                      index_key: str = "batch") -> Iterator:
    """Run `items` through prep → dispatch → drain with `depth` items
    dispatched ahead of the drain point.

    - `prep(item)` runs on one of `workers` threads, at most `workers`
      ahead of the dispatch point (bounded lookahead — an eager
      Executor.map would drain the whole source).
    - `dispatch(i, prepped)` runs serially in submission order and
      returns an in-flight handle (async device work keeps running).
    - `drain(i, handle)` finalizes in order; its results are yielded.
    - `depth` <= 1 is fully synchronous (each item drains before the
      next dispatches).

    Events/tickets use `family` (`<family>.dispatch` / `<family>.drain`
    with args {index_key: i} — both families are registered in
    obs/taxonomy.py).
    """
    from ..obs import note_pipeline
    from ..obs._recorder import RECORDER
    from ..obs._watchdog import WATCHDOG

    depth = max(int(depth), 1)
    pending: deque = deque()

    def drain_one():
        i, handle, ticket = pending.popleft()
        try:
            out = drain(i, handle)
        finally:
            WATCHDOG.close(ticket)
        if RECORDER.enabled:
            note_pipeline(family, "drain", index_key, i)
        return out

    with ThreadPoolExecutor(max_workers=max(int(workers), 1),
                            initializer=mark_host_worker) as ex:
        it = iter(items)
        preps: deque = deque()

        def submit_next() -> bool:
            try:
                item = next(it)
            except StopIteration:
                return False
            preps.append(ex.submit(prep, item))
            return True

        try:
            for _ in range(max(int(workers), 1)):
                submit_next()
            i = 0
            while preps:
                prepped = preps.popleft().result()
                submit_next()
                ticket = WATCHDOG.open(family, f"{family}[{i}]")
                try:
                    handle = dispatch(i, prepped)
                except BaseException:
                    WATCHDOG.close(ticket)
                    raise
                if RECORDER.enabled:
                    note_pipeline(family, "dispatch", index_key, i)
                pending.append((i, handle, ticket))
                i += 1
                if len(pending) >= depth:
                    yield drain_one()
            while pending:
                yield drain_one()
        finally:
            # abandoned generator (caller broke early) or a raised
            # dispatch/drain: every in-flight item still gets its drain —
            # external resources (ledger holds, async buffers) release,
            # and no watchdog ticket is left to rot into a false stall
            while pending:
                j, handle, ticket = pending.popleft()
                WATCHDOG.close(ticket)
                try:
                    drain(j, handle)
                except Exception:
                    pass  # best-effort cleanup; results are discarded


def prefetch_map(items: Iterable, fn: Callable, *, depth: int,
                 workers: Optional[int] = None) -> Iterator:
    """Bounded-lookahead thread-parallel map, results in order — the
    pure-host half of the pattern (the factorized-linear scoring path):
    at most `depth` results outstanding, so the source iterator is never
    drained eagerly. depth <= 1 is synchronous."""
    depth = max(int(depth), 1)
    with ThreadPoolExecutor(max_workers=workers or min(depth, 4),
                            initializer=mark_host_worker) as ex:
        it = iter(items)
        window: deque = deque()

        def pull() -> bool:
            try:
                item = next(it)
            except StopIteration:
                return False
            window.append(ex.submit(fn, item))
            return True

        for _ in range(depth):
            pull()
        while window:
            out = window.popleft().result()
            pull()
            yield out

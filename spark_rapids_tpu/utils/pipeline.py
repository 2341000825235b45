"""Producer/consumer pipelining helpers for the shuffle and exec layers.

Reference idiom: RapidsShuffleThreadedReaderBase's prefetching block fetcher —
the next block's deserialize+upload runs on a pool thread while downstream
consumes the current one, so the upload's latency overlaps host I/O instead
of adding to it.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, TypeVar

T = TypeVar("T")

_DONE = object()


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_iterator(it: Iterator[T], depth: int) -> Iterator[T]:
    """Drive `it` from a worker thread, keeping up to `depth` items ready
    ahead of the consumer. Order is preserved exactly; an exception raised by
    the producer re-raises at the consumer's corresponding `next()`; closing
    the returned generator early stops the worker without leaking it (the
    worker re-checks the stop flag on every bounded put). depth <= 0 is a
    passthrough."""
    if depth <= 0:
        yield from it
        return
    q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
    stop = threading.Event()
    # the producer runs `it`'s frames on the worker thread: inherit the
    # consumer's query tracer (per-query tracing routes by thread — an
    # unbound worker's spans/syncs would vanish from the owning query's
    # record and break bundle reconciliation); a no-op when untraced
    from ..obs import tracer as _obs
    from ..serving import query_context as _qlc
    obs_parent = _obs.current_span()
    # same for the query lifecycle binding: checkpoints inside the
    # producer's frames (reduce fetch, nested operator pulls) must see
    # the consumer's query so a cancel/deadline stops the prefetch too
    qctx = _qlc.current()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def work() -> None:
        try:
            with _obs.inherit(obs_parent), _qlc.bind(qctx):
                for item in it:
                    if not _put(item):
                        return
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            _put(_Err(e))
            return
        _put(_DONE)

    t = threading.Thread(target=work, name="srt-prefetch", daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _DONE:
                break
            if isinstance(item, _Err):
                raise item.exc
            yield item
    finally:
        stop.set()
        t.join()

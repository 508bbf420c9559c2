"""Background-thread iterator prefetch (the reference's multithreaded
reader, GpuParquetScan's MULTITHREADED/COALESCING reader modes, reduced
to its TPU-relevant core): produce the NEXT chunk's host-side decode
while the device consumes the current one.  The H2D transfer over the
host link is a large share of a scan — overlapping it with the next
chunk's control-plane work pipelines the two instead of summing them.

jax is thread-compatible for this use: device_put/eager dispatches from
the producer thread enqueue on the same stream the consumer later
blocks on."""
from __future__ import annotations

import queue
import threading
from typing import Iterator, TypeVar

T = TypeVar("T")

_STOP = object()


class PrefetchIterator:
    """Wraps an iterator; a daemon thread keeps up to `depth` items
    decoded ahead.  Exceptions re-raise at the consumer in order.

    `close()` MUST be called when the consumer stops early (LIMIT,
    exception): it unblocks the pump thread (otherwise parked forever in
    a full-queue put, pinning the buffered batches and the source
    generator) and runs the wrapped generator's finally blocks."""

    def __init__(self, it: Iterator[T], depth: int = 1):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._consumed = False
        self._closed = False
        self._it = it

        def offer(entry) -> bool:
            """put() that gives up once close() is called (a plain put
            can park forever on a queue the consumer stopped draining)."""
            while not self._closed:
                try:
                    self._q.put(entry, timeout=0.25)
                    return True
                except queue.Full:
                    continue  # tpulint: disable=TPU006 bounded-put retry loop; the timeout exists to re-check _closed
            return False

        def pump():
            try:
                for item in it:
                    if not offer((item, None)):
                        break
            except BaseException as e:  # noqa: BLE001 — re-raised below
                offer((None, e))
                return
            finally:
                if self._closed and hasattr(it, "close"):
                    try:
                        it.close()
                    except Exception:  # noqa: BLE001 — teardown
                        pass  # tpulint: disable=TPU006 close() of an abandoned source iterator after the consumer left
            offer((_STOP, None))

        self._thread = threading.Thread(target=pump, daemon=True,
                                        name="scan-prefetch")
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self) -> T:
        if self._consumed:
            raise StopIteration
        item, err = self._q.get()
        if err is not None:
            self._consumed = True
            raise err
        if item is _STOP:
            self._consumed = True
            raise StopIteration
        return item

    def close(self) -> None:
        self._closed = True
        try:  # drop buffered items so a parked put() finds space
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass  # tpulint: disable=TPU006 Empty is the drain loop's termination condition

"""Reduce-driven flow control: windowed in-flight-bytes budgets.

One FlowController per runtime (shared by the shuffle env's fetch and
serve sides).  The reduce side reports every consumed batch via
`on_consumed`; the controller derives a consumption rate over a short
sliding span and turns it into an admission window

    window_bytes = max(minWindowBytes, rate * horizon)

so a producer may hold at most ~horizon's worth of un-consumed bytes in
flight.  Two admission points ride the window:

  * `AsyncFetchIterator._admit` (shuffle/fetch.py) caps its in-flight
    bytes at min(maxReceiveInflightBytes, fetch_window_bytes) — a
    stalled consumer shrinks the window to the floor and the producer
    waits (resumable: admission re-checks on every consumption notify,
    and the oversized-batch-alone rule is preserved, so a stalled
    reducer is back-pressured, never deadlocked).  The fetch window is
    additionally POOL-AWARE when a headroom provider is attached: it
    never exceeds current device headroom, so under memory pressure
    readahead collapses toward one-partition-at-a-time — each fetched
    partition is consumed (and early-released) before the next one
    materializes, instead of fetched-ahead partitions evicting each
    other (measured as respill churn);
  * `ShuffleServer._leaves` (map-side serve staging) takes a BOUNDED
    `serve_acquire` before staging bytes for a peer: when in-flight
    served bytes exceed the window the serve stalls up to
    maxServeStallMs and then proceeds anyway — soft backpressure, by
    construction deadlock-free.  `done_serving` (the reader's release,
    i.e. reduce-side consumption evidence crossing the wire) releases
    the bytes and feeds the rate.

Stalls are counted (numBackpressureStalls) and journaled (kind `policy`,
name `backpressure`) so the memory CLI can attribute them.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict

from ..metrics import names as MN
from ..metrics.journal import journal_event


class FlowController:
    """Consumption-rate-windowed in-flight-bytes budget (see module doc)."""

    def __init__(self, min_window_bytes: int, horizon_s: float,
                 max_stall_s: float, metrics=None, headroom=None):
        self.min_window = max(1, int(min_window_bytes))
        self.horizon_s = max(0.0, float(horizon_s))
        self.max_stall_s = max(0.0, float(max_stall_s))
        self.metrics = metrics
        # optional device-headroom provider (callable -> free pool
        # bytes); clamps the FETCH window only — the serve side stages
        # host bytes and is not bounded by device headroom
        self._headroom = headroom
        self._cv = threading.Condition()
        # (monotonic, nbytes) consumption events inside the rate span
        self._events: deque = deque()
        self._serve_inflight = 0
        self._serve_sizes: Dict[int, int] = {}

    # ---- reduce-side signal -------------------------------------------------

    def on_consumed(self, nbytes: int) -> None:
        """One consumed batch: feeds the rate and wakes stalled admits."""
        now = time.monotonic()
        with self._cv:
            self._events.append((now, int(nbytes)))
            self._trim_locked(now)
            self._cv.notify_all()

    def _trim_locked(self, now: float) -> None:
        span = max(1.0, 5.0 * self.horizon_s)
        while self._events and now - self._events[0][0] > span:
            self._events.popleft()

    def rate_bytes_per_s(self) -> float:
        now = time.monotonic()
        with self._cv:
            self._trim_locked(now)
            if not self._events:
                return 0.0
            total = sum(nb for _, nb in self._events)
            return total / max(now - self._events[0][0], 1e-3)

    def window_bytes(self) -> int:
        return max(self.min_window,
                   int(self.rate_bytes_per_s() * self.horizon_s))

    def fetch_window_bytes(self) -> int:
        """The reduce-side fetch admission window: the rate window,
        clamped to present device headroom when a provider is attached
        (never below 1 — the oversized-batch-alone rule in _admit keeps
        a zero-headroom pool progressing serially)."""
        window = self.window_bytes()
        if self._headroom is None:
            return window
        try:
            free = int(self._headroom())
        except Exception:  # noqa: BLE001 — a dead provider never stalls
            return window
        return max(1, min(window, free))

    # ---- map-side serve window ----------------------------------------------

    def serve_acquire(self, buffer_id: int, nbytes: int) -> bool:
        """Admit `nbytes` of serve staging; bounded wait when in-flight
        served bytes exceed the window (proceeds after maxServeStallMs —
        soft backpressure, never a deadlock).  Returns whether it
        stalled."""
        deadline = time.monotonic() + self.max_stall_s
        stalled = False
        with self._cv:
            while self._serve_inflight > 0 and \
                    self._serve_inflight + nbytes > self.window_bytes():
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                stalled = True
                self._cv.wait(timeout=min(left, 0.05))
            self._serve_inflight += int(nbytes)
            self._serve_sizes[buffer_id] = \
                self._serve_sizes.get(buffer_id, 0) + int(nbytes)
        if stalled:
            self.note_stall("serve")
        return stalled

    def serve_release(self, buffer_id: int) -> int:
        """Release a served buffer's staged bytes (the reader's
        done_serving ack); returns the bytes released (0 when the id was
        never acquired — every cache-removal path calls this, balanced
        by the per-id size ledger)."""
        with self._cv:
            nb = self._serve_sizes.pop(buffer_id, 0)
            if nb:
                self._serve_inflight = max(0, self._serve_inflight - nb)
                self._cv.notify_all()
        return nb

    def serve_inflight_bytes(self) -> int:
        with self._cv:
            return self._serve_inflight

    # ---- observability ------------------------------------------------------

    def note_stall(self, where: str) -> None:
        if self.metrics is not None:
            self.metrics.add(MN.NUM_BACKPRESSURE_STALLS, 1)
        journal_event("policy", "backpressure", where=where,
                      window=self.window_bytes())

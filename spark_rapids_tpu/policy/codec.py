"""Runtime codec re-selection: wire-bound exchanges flip the shuffle
codec at runtime.

The static plan fixes the wire codec at session start
(spark.rapids.shuffle.compression.codec, default none).  An exchange whose
read phase moved bytes at a significant fraction of the wire peak is
wire-bound — exactly the regime where paying codec CPU to shrink wire
bytes wins.  `CodecAdvisor` watches each exchange's observed read
throughput against the wire peak (roofline.peakWireGBs, 1 GB/s unless
set) and, once an exchange crosses the wire-bound threshold at
sufficient volume, advises the configured candidate codec
(none->lz4/zstd) for that shuffle id AND for subsequent exchanges of the
session (sticky, the same way AQE re-plans on observed sizes).

The advice rides the existing PR 5 negotiation path end to end: the
reader names the advised codec in its MetadataRequest, the server
answers with what it will actually frame (raw when the library is
missing there — graceful fallback, counted), and fetches pull framed
compressed leaves through the same verify-before/after ladder.  The
override is attached per-client (`compression_override` on the
transport client), so only policy-advised fetches negotiate — a session
with compression explicitly configured is never second-guessed.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from ..metrics import names as MN
from ..metrics.journal import journal_event


class CodecAdvisor:
    """Per-runtime wire-codec re-selection (see module doc)."""

    def __init__(self, conf, metrics=None):
        from .. import config as C
        self.candidate = str(conf.get(C.POLICY_CODEC)).lower()
        self.min_bytes = int(conf.get(C.POLICY_CODEC_MIN_BYTES))
        self.bound_fraction = float(conf.get(C.POLICY_CODEC_WIRE_BOUND))
        # bytes/s; the default is a loopback socket's rate
        peak_gbs = float(conf.get(C.POLICY_CODEC_WIRE_PEAK))
        self.wire_peak = (peak_gbs if peak_gbs > 0 else 1.0) * 1e9
        self.metrics = metrics
        self._conf = conf
        self._lock = threading.Lock()
        self._overrides: Dict[int, str] = {}
        self._sticky: Optional[str] = None
        self._reader_policy = None

    def observe_exchange(self, shuffle_id: int, wire_bytes: int,
                         seconds: float) -> bool:
        """Runtime evidence from one exchange's read phase; returns
        whether it (newly) triggered a re-selection for this shuffle."""
        if self.candidate in ("", "none") or seconds <= 0 \
                or wire_bytes < self.min_bytes:
            return False
        utilization = (wire_bytes / seconds) / self.wire_peak
        if utilization < self.bound_fraction:
            return False
        from ..compress import is_codec_available
        if not is_codec_available(self.candidate):
            return False
        with self._lock:
            fresh = shuffle_id not in self._overrides
            self._overrides[shuffle_id] = self.candidate
            self._sticky = self.candidate
        if fresh:
            if self.metrics is not None:
                self.metrics.add(MN.NUM_CODEC_RESELECTIONS, 1)
            journal_event("policy", "codec", shuffle=shuffle_id,
                          codec=self.candidate,
                          wire_bytes=int(wire_bytes),
                          seconds=float(seconds),
                          utilization=float(utilization))
        return fresh

    def wire_codec(self, shuffle_id: int) -> Optional[str]:
        """The advised codec for a shuffle's fetches, or None.  Falls
        back to the session-sticky advice (a later exchange of a
        wire-bound session starts compressed from its first fetch)."""
        with self._lock:
            return self._overrides.get(shuffle_id) or self._sticky

    def shuffle_released(self, shuffle_id: int) -> None:
        with self._lock:
            self._overrides.pop(shuffle_id, None)

    def reader_policy(self):
        """The reader-side CompressionPolicy that rides advised fetches
        as the client's `compression_override` — built once, framed with
        the session's shuffle chunking parameters."""
        with self._lock:
            if self._reader_policy is None:
                from .. import config as C
                from ..compress.framed import CompressionPolicy
                self._reader_policy = CompressionPolicy(
                    self.candidate,
                    int(self._conf.get(C.SHUFFLE_COMPRESSION_CHUNK_SIZE)),
                    int(self._conf.get(C.SHUFFLE_COMPRESSION_MIN_SIZE)),
                    metrics=self.metrics)
            return self._reader_policy

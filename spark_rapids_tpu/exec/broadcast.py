"""Broadcast exchange + broadcast hash join.

TPU-native analogue of GpuBroadcastExchangeExec / GpuBroadcastHashJoinExec
(org/.../execution/GpuBroadcastExchangeExec.scala:47-391 — the child is
collected ONCE as serialized host buffers and lazily re-uploaded per
executor; GpuBroadcastHashJoinExec.scala:115-151 — each task reconstitutes
the device build table from the broadcast).  Here: the child is drained
once, concatenated, pulled to host leaves (the serialized form), and every
consumer re-uploads lazily — one H2D per process, cached, registered as a
spillable buffer so broadcast data participates in memory pressure
handling.
"""
from __future__ import annotations

import threading
from typing import Iterator, Optional

import numpy as np

from ..columnar import ColumnarBatch, concat_batches
from ..mem.buffer import SpillPriorities, batch_to_host, host_to_batch
from .base import (CpuExec, ExecContext, ExecNode, TpuExec,
                   record_movement)
from .join import TpuHashJoinExec
from ..metrics import names as MN
from ..utils.tracing import named_range


class TpuBroadcastExchangeExec(TpuExec):
    """Collect once to host; serve a device batch to every consumer."""

    def __init__(self, child: ExecNode):
        super().__init__(child)
        self._host_form = None       # (leaves, meta) — the broadcast value
        self._buffer_id: Optional[int] = None
        self._lock = threading.Lock()

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return "TpuBroadcastExchangeExec"

    def _collect(self, ctx: ExecContext):
        """The async driver job of the reference (collect + serialize),
        run once (GpuBroadcastExchangeExec.scala:215-391)."""
        with named_range("broadcast_collect"):
            with self.metrics.timer(MN.COLLECT_TIME):
                batches = list(self.children[0].execute(ctx))
            with self.metrics.timer(MN.BUILD_TIME):
                if batches:
                    batch = batches[0] if len(batches) == 1 \
                        else concat_batches(batches)
                else:
                    from .join import _empty_batch
                    batch = _empty_batch(self.schema)
                leaves, meta = batch_to_host(batch)
        self.metrics.add(MN.DATA_SIZE, meta.size_bytes)
        # what the exchange ships, both ways, and how much of it is alive:
        # the batch travels at its capacity; the selection is the last
        # leaf and is on the host already
        self.metrics.add(MN.BROADCAST_BYTES, meta.size_bytes)
        self.metrics.add(MN.BROADCAST_ROWS, int(np.count_nonzero(leaves[-1])))
        # the broadcast payload left the device (d2h) and is
        # re-published to every executor over the wire
        record_movement(self.metrics, d2h=meta.size_bytes,
                        wire=meta.size_bytes)
        return leaves, meta

    def materialize_host(self, ctx: ExecContext):
        """Collect the child ONCE and return the host form (leaves, meta)
        — the adaptive demotion check reads `meta.size_bytes` here BEFORE
        the join instantiates, and a kept broadcast reuses the same
        cached collect through `broadcast_batch`."""
        with self._lock:
            if self._host_form is None:
                self._host_form = self._collect(ctx)
            return self._host_form

    def broadcast_batch(self, ctx: ExecContext) -> ColumnarBatch:
        """Device view of the broadcast value; lazy re-upload, spillable."""
        with self._lock:
            if self._host_form is None:
                self._host_form = self._collect(ctx)
            leaves, meta = self._host_form
            runtime = ctx.runtime
            if runtime is not None and self._buffer_id is not None:
                try:
                    return runtime.get_batch(self._buffer_id)
                except KeyError:
                    self._buffer_id = None
            with named_range("broadcast_upload", bytes=meta.size_bytes):
                batch = host_to_batch(leaves, meta)
            if runtime is not None:
                self._buffer_id = runtime.add_batch(
                    batch, SpillPriorities.ACTIVE_ON_DECK_PRIORITY)
            return batch

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        yield self.broadcast_batch(ctx)


class CpuBroadcastExchangeExec(CpuExec):
    """Host fallback: collect once, replay the cached arrow table."""

    def __init__(self, child: ExecNode):
        super().__init__(child)
        self._table = None
        self._lock = threading.Lock()

    @property
    def schema(self):
        return self.children[0].schema

    def execute_cpu(self, ctx: ExecContext):
        import pyarrow as pa
        with self._lock:
            if self._table is None:
                tables = list(self.children[0].execute_cpu(ctx))
                if tables:
                    self._table = pa.concat_tables(tables)
                else:
                    from ..types import to_arrow
                    self._table = pa.table(
                        {f.name: pa.array([], type=to_arrow(f.dtype))
                         for f in self.schema})
        yield self._table


class TpuBroadcastHashJoinExec(TpuHashJoinExec):
    """Hash join whose build side is a broadcast exchange
    (GpuBroadcastHashJoinExec.scala:115-151).  The probe kernels are
    identical to the shuffled hash join; only the build-side source
    differs."""

    def describe(self):
        return (f"TpuBroadcastHashJoinExec[{self.join_type}, "
                f"keys={len(self.left_keys)}]")

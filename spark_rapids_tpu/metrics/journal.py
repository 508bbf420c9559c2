"""Per-query structured event journal.

JSON-lines spans and instant events with monotonic timestamps, stable ids
and parent links — the machine-readable twin of the Spark SQL UI timeline.
The engine opens one journal per query (QueryExecution); operators, retry
blocks, the spill cascade and the shuffle transport append to whichever
journal is ACTIVE (module-scoped stack, so deep layers like
mem/runtime.py's event handler need no plumbed-through handle).

Record schema (one JSON object per line):

  ts     monotonic nanoseconds (time.monotonic_ns; per-process clock)
  ev     "B" (span begin) | "E" (span end) | "I" (instant event)
  kind   query|stage|operator|retry|spill|fetch|metric|fallback|replan|
         corruption|refetch|recompute|compress|compile|collective|...
         (EVENT_KINDS below is the authoritative list)
  name   human label (operator describe(), retry block name, ...)
  id     span/event id, unique within the journal, increasing
  parent parent span id or null (operator spans parent to the enclosing
         operator's span; top-level spans parent to the query span)
  span   (E records only) the id of the B record being closed
  attrs  everything else: node ids, byte counts, metric dumps, ...

The journal is either file-backed (`spark.rapids.sql.tpu.metrics.journal
.dir`, one file per query) or in-memory (DEBUG level with no dir
configured); `events()` parses it back either way, and `validate_events`
is the schema check the round-trip tests run.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

EVENT_KINDS = ("query", "stage", "operator", "retry", "spill", "fetch",
               "metric", "fallback", "replan",
               # data-integrity recovery ladder (docs/tuning-guide.md):
               # corruption = a checksum mismatch (with its writer-side
               # classification), refetch = a transient-corruption retry,
               # recompute = a lost map output being rebuilt from lineage
               "corruption", "refetch", "recompute",
               # compress = a buffer (de)compressed at the shuffle-serve
               # or spill boundary, with codec + raw/physical bytes
               "compress",
               # compile = a whole-stage XLA program was built for a new
               # (stage, batch-shape) pair, with the trace-vs-compile
               # time split (exec/whole_stage.py stage_executable)
               "compile",
               # collective = one mesh-exchange collective dispatch (the
               # compiled shard_map all-to-all of a lowered shuffle
               # exchange; attrs shuffle/map/devices/quota) — the mesh
               # tier's twin of the socket tier's fetch/serve spans
               "collective",
               # distributed tracing (metrics/timeline.py):
               # task = one map/reduce fragment executed on a worker
               # (attrs query/stage/executor), serve = this process served
               # a shuffle buffer/metadata to a peer, carrying the
               # REQUESTER's trace context (o_q/o_st/o_sp/o_ex) so the
               # merged timeline flow-links it to the reducer's fetch
               # span, heartbeat = a live progress snapshot
               "task", "serve", "heartbeat",
               # mem = one memory-ledger record (mem/ledger.py): an
               # allocation-boundary event (reserve/alloc/free/spill/
               # unspill/oomSpill/oomFail) causally linked by reservation
               # id, or a sampled per-tier 'pressure' snapshot — the
               # input of `python -m spark_rapids_tpu.metrics --memory`
               "mem",
               # sched = one serving-tier scheduling decision for THIS
               # query (serve/scheduler.py): the 'admitted' instant
               # carries queue time, priority, declared memory need and
               # the plan-cache outcome, journaled into the query's own
               # journal under its trace context
               "sched",
               # spec = one distributed task-recovery decision
               # (cluster._run_tasks_with_retry): speculativeLaunch /
               # speculationWin (straggler re-execution races),
               # taskAbandoned (attempt past its deadline), workerEvicted
               # (wedged-but-alive replacement), clusterShrunk (graceful
               # degradation after the replacement budget) — attrs name
               # the stage, task index, attempt id and executor
               "spec",
               # policy = one data-movement policy decision (policy/):
               # victim (scored spill pick, with the baseline choice it
               # kept or overrode), unspill (proactive re-materialize,
               # attrs buffer/bytes/owner), backpressure (a flow-control
               # admission stall, attrs where/window), codec (an
               # exchange proven wire-bound at runtime flipping the fetch
               # codec) —
               # replayed by `python -m spark_rapids_tpu.metrics --memory`
               "policy",
               # lifecycle = one query-lifecycle decision
               # (serve/lifecycle.py): cancel (a QueryFuture.cancel or
               # token-routed shutdown observed at a checkpoint),
               # deadline (a submit deadline_ms= enforced mid-run), shed
               # (rejected at admission: remaining deadline under the
               # estimated plan+compile cost), preemptSuspend /
               # preemptResume (a victim parking at a stage boundary and
               # continuing bit-for-bit), ownerCleanup (the freed-bytes
               # accounting of a killed query's owner-confined release)
               "lifecycle",
               # epoch = one streaming micro-batch epoch
               # (streaming/query.py): slice (unread offsets planned
               # into a micro-batch, attrs source/start/end/rows),
               # commit (offsets + state snapshot atomically durable,
               # attrs epoch/state_bytes/rows), recover (a restarted
               # query resuming from the last committed checkpoint
               # instead of a cold recompute, attrs epoch/offsets)
               "epoch")

# --- flight-recorder taps ----------------------------------------------------
# Process-wide observers of EVERY journal record emitted by ANY journal in
# this process (metrics/ring.py's FlightRecorder is the one registrant).
# A tap runs UNDER the emitting journal's lock, so it must do nothing but
# O(1) bookkeeping on its own structures (a deque append) — no journal
# writes, no store-lock acquisition, no I/O.  Registration is list-swap
# (copy-on-write) so the hot emit path reads one tuple with no lock.
_TAPS: tuple = ()
_TAPS_LOCK = threading.Lock()


def add_tap(fn) -> None:
    """Register fn(line: str) to observe every emitted journal line."""
    global _TAPS
    with _TAPS_LOCK:
        if fn not in _TAPS:
            _TAPS = _TAPS + (fn,)


def remove_tap(fn) -> None:
    global _TAPS
    with _TAPS_LOCK:
        # equality, not identity: a bound method is a fresh object per
        # attribute access, but compares equal by (__self__, __func__)
        _TAPS = tuple(t for t in _TAPS if t != fn)


class EventJournal:
    def __init__(self, path: Optional[str] = None,
                 query_id: Optional[str] = None,
                 anchor: bool = False, label: Optional[str] = None,
                 mirror: bool = False, max_lines: Optional[int] = None,
                 is_shard: bool = False):
        """`anchor=True` writes one `{"ev":"A","wall_ns":...,"mono_ns":...}`
        record at open so shards written by different processes (and even
        before a driver ever connects) can be aligned on wall clock
        offline.  `mirror=True` keeps an in-memory copy of every line even
        when file-backed, bounded by `max_lines`, for `drain()` — the
        incremental rpc_drain_journal feed.  `is_shard` marks a
        process-lifetime worker trace shard: query executions ADOPT it
        instead of opening their own journal (metrics/query.py)."""
        self.path = path
        self.query_id = query_id
        self.label = label
        self.is_shard = is_shard
        self._mirror = mirror or path is None
        self._max_lines = max_lines
        # in-memory mirror: the journal's readable copy when path is None,
        # and the undrained drain() buffer for shards (bounded).  A deque
        # so at-cap eviction is O(1) per event — a full 64k-line list
        # would memmove its whole front on EVERY append, under the lock,
        # on the per-batch instrumentation path
        self._lines: "deque[str]" = deque()
        self.dropped_lines = 0
        self._file = None
        if path is not None:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._file = open(path, "w")
        self._lock = threading.Lock()
        self._next_id = 0
        self._open_spans: Dict[int, dict] = {}
        self.closed = False
        self.anchor: Optional[dict] = None
        if anchor:
            # wall-clock anchor: maps this journal's monotonic timestamps
            # to wall time (wall_ns + (ts - mono_ns)); sampled as one
            # atomic pair so the mapping error is bounded by the gap
            # between the two clock reads
            self.anchor = {"ev": "A", "wall_ns": time.time_ns(),
                           "mono_ns": time.monotonic_ns(),
                           "pid": os.getpid()}
            if label is not None:
                self.anchor["label"] = label
            with self._lock:
                self._emit_locked(self.anchor)

    # -- writing -------------------------------------------------------------

    def _emit_locked(self, rec: dict) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str)
        for tap in _TAPS:
            try:
                tap(line)
            except Exception:
                from .registry import count_swallowed
                count_swallowed("numTelemetryTapErrors", __name__,
                                "journal tap failed")
        if self._file is not None:
            self._file.write(line + "\n")
            self._file.flush()
        if self._mirror or self._file is None:
            if self._max_lines is not None \
                    and len(self._lines) >= self._max_lines:
                # bound undrained shard memory: evict oldest, count loss
                while len(self._lines) >= self._max_lines:
                    self._lines.popleft()
                    self.dropped_lines += 1
            self._lines.append(line)

    def _record(self, ev: str, kind: str, name: str,
                parent: Optional[int], attrs: dict) -> int:
        with self._lock:
            if self.closed:
                return -1
            self._next_id += 1
            rid = self._next_id
            rec = {"ts": time.monotonic_ns(), "ev": ev, "kind": kind,
                   "name": name, "id": rid, "parent": parent}
            if attrs:
                rec.update(attrs)
            if ev == "B":
                self._open_spans[rid] = rec
            self._emit_locked(rec)
            return rid

    def begin(self, kind: str, name: str, parent: Optional[int] = None,
              **attrs) -> int:
        """Open a span; returns the span id to close with `end()`."""
        return self._record("B", kind, name, parent, attrs)

    def end(self, span_id: int, **attrs) -> None:
        with self._lock:
            if self.closed or span_id not in self._open_spans:
                return  # idempotent: double-close / close-after-finish
            opened = self._open_spans.pop(span_id)
            self._next_id += 1
            rec = {"ts": time.monotonic_ns(), "ev": "E",
                   "kind": opened["kind"], "name": opened["name"],
                   "id": self._next_id, "parent": opened["parent"],
                   "span": span_id}
            if attrs:
                rec.update(attrs)
            self._emit_locked(rec)

    def instant(self, kind: str, name: str, parent: Optional[int] = None,
                **attrs) -> int:
        return self._record("I", kind, name, parent, attrs)

    @contextlib.contextmanager
    def span(self, kind: str, name: str, parent: Optional[int] = None,
             **attrs):
        sid = self.begin(kind, name, parent, **attrs)
        try:
            yield sid
        finally:
            self.end(sid)

    def close(self) -> None:
        """Close any dangling spans (abandoned generators) and the file."""
        with self._lock:
            for sid in sorted(self._open_spans):
                opened = self._open_spans[sid]
                self._next_id += 1
                self._emit_locked({"ts": time.monotonic_ns(), "ev": "E",
                            "kind": opened["kind"], "name": opened["name"],
                            "id": self._next_id, "parent": opened["parent"],
                            "span": sid, "dangling": True})
            self._open_spans.clear()
            self.closed = True
            if self._file is not None:
                self._file.close()
                self._file = None

    # -- reading -------------------------------------------------------------

    def events(self) -> List[dict]:
        if self.path is not None:
            return read_journal(self.path)
        with self._lock:
            return [json.loads(ln) for ln in self._lines]

    def event_count(self) -> int:
        """Records written over this journal's lifetime (span begins and
        instants; unaffected by mirror eviction or drains) — a cheap
        monotonic activity signal (engine.TpuSession.progress)."""
        with self._lock:
            return self._next_id

    def drain(self) -> dict:
        """Take (and clear) the undrained in-memory mirror — the
        incremental feed the driver pulls over rpc_drain_journal.  Always
        carries the anchor so the first drain of a shard is alignable;
        `dropped` counts events evicted by the memory bound since open."""
        with self._lock:
            lines, self._lines = self._lines, deque()
            dropped = self.dropped_lines
        events = [json.loads(ln) for ln in lines]
        # the anchor rides every drain response (it is also the first
        # mirrored line of the first drain; consumers dedup on "ev"=="A")
        return {"anchor": self.anchor, "label": self.label,
                "events": [e for e in events if e.get("ev") != "A"],
                "dropped": dropped}


def read_journal(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def validate_events(events: List[dict]) -> List[str]:
    """Schema check; returns a list of problems (empty = valid)."""
    errors: List[str] = []
    seen_ids = set()
    begun: Dict[int, dict] = {}
    last_ts = None
    for i, e in enumerate(events):
        where = f"event {i}"
        if e.get("ev") == "A":
            # wall-clock anchor record (shard alignment): no id/kind/name,
            # just the wall<->monotonic clock pair sampled at journal open
            for field in ("wall_ns", "mono_ns"):
                if field not in e:
                    errors.append(f"{where}: anchor missing {field!r}")
            continue
        for field in ("ts", "ev", "kind", "name", "id"):
            if field not in e:
                errors.append(f"{where}: missing field {field!r}")
        if e.get("ev") not in ("B", "E", "I"):
            errors.append(f"{where}: bad ev {e.get('ev')!r}")
        if e.get("kind") not in EVENT_KINDS:
            errors.append(f"{where}: unknown kind {e.get('kind')!r}")
        eid = e.get("id")
        if eid in seen_ids:
            errors.append(f"{where}: duplicate id {eid}")
        seen_ids.add(eid)
        ts = e.get("ts")
        if last_ts is not None and isinstance(ts, int) and ts < last_ts:
            errors.append(f"{where}: timestamp went backwards")
        if isinstance(ts, int):
            last_ts = ts
        parent = e.get("parent")
        if parent is not None and parent not in seen_ids:
            errors.append(f"{where}: parent {parent} not seen before it")
        if e.get("ev") == "B":
            begun[eid] = e
        elif e.get("ev") == "E":
            sid = e.get("span")
            if sid not in begun:
                errors.append(f"{where}: E for unknown span {sid}")
            else:
                del begun[sid]
    for sid in begun:
        errors.append(f"span {sid} never closed")
    return errors


# -- active-journal plumbing -------------------------------------------------
# Deep layers (the spill event handler, socket fetch loops, retry blocks)
# observe whichever query journal is active without threading a handle
# through every signature.  A stack supports nested queries (a CPU-fallback
# re-execution inside a parent query keeps appending to the parent's
# journal once its own finishes).
#
# Thread routing (serving tier): with N queries in flight each pushes its
# journal from its own worker thread, so "top of one global stack" would
# interleave every query's deep-layer events into whichever journal was
# pushed last.  Entries therefore remember their pushing thread:
# active_journal() prefers the innermost journal pushed by the CALLING
# thread, then the process trace shard (which serves every thread by
# design), then — preserving the old behavior for helper threads that
# journal on a query's behalf (codec pools, async verifiers) — the
# newest entry overall.

_ACTIVE: List[tuple] = []  # (pushing thread id, journal)
_ACTIVE_LOCK = threading.Lock()


def push_active(journal: Optional[EventJournal]) -> None:
    if journal is not None:
        with _ACTIVE_LOCK:
            _ACTIVE.append((threading.get_ident(), journal))


def pop_active(journal: Optional[EventJournal]) -> None:
    if journal is not None:
        with _ACTIVE_LOCK:
            for i in range(len(_ACTIVE) - 1, -1, -1):
                if _ACTIVE[i][1] is journal:
                    del _ACTIVE[i]
                    break


def active_journal() -> Optional[EventJournal]:
    tid = threading.get_ident()
    with _ACTIVE_LOCK:
        if not _ACTIVE:
            return None
        shard = None
        for ent_tid, j in reversed(_ACTIVE):
            if ent_tid == tid:
                return j
            if shard is None and j.is_shard:
                shard = j
        return shard if shard is not None else _ACTIVE[-1][1]


def journal_event(kind: str, name: str, **attrs) -> None:
    """Fire-and-forget instant event into the active journal (no-op when
    no query journal is open) — the hook deep layers call."""
    j = active_journal()
    if j is not None:
        j.instant(kind, name, **attrs)


@contextlib.contextmanager
def journal_span(kind: str, name: str, **attrs):
    """Span in the active journal (yields the span id, or None when no
    journal is open) — the deep-layer twin of journal_event for
    operations whose DURATION matters to the timeline (remote fetches,
    buffer serves)."""
    j = active_journal()
    if j is None:
        yield None
        return
    sid = j.begin(kind, name, **attrs)
    try:
        yield sid
    finally:
        j.end(sid)


# -- distributed trace context ------------------------------------------------
# The (query, stage, span, executor) tuple stamped on shuffle wire requests
# so the SERVING side can journal who it served (metrics/timeline.py
# flow-links the reducer's fetch span to the mapper's serve span).  Kept in
# a thread-local: the worker's task dispatch sets (query, stage), the
# fetch path narrows `span` to its own fetch-span id for the duration of
# the wire ops.  Tuple layout on the wire: (query, stage, span, executor).

_TRACE = threading.local()


def current_trace() -> Optional[tuple]:
    return getattr(_TRACE, "ctx", None)


@contextlib.contextmanager
def trace_context(query=None, stage=None, span=None, executor=None):
    """Install a trace context for the calling thread, inheriting unset
    fields from the enclosing context."""
    prev = current_trace()
    base = prev or (None, None, None, None)
    ctx = (query if query is not None else base[0],
           stage if stage is not None else base[1],
           span if span is not None else base[2],
           executor if executor is not None else base[3])
    _TRACE.ctx = ctx
    try:
        yield ctx
    finally:
        _TRACE.ctx = prev


def trace_attrs(trace: Optional[tuple], prefix: str = "o_") -> dict:
    """Journal attrs for a wire-carried trace context: o_q/o_st/o_sp/o_ex
    (origin query/stage/span/executor) — what serve events record."""
    if not trace:
        return {}
    q, st, sp, ex = (tuple(trace) + (None,) * 4)[:4]
    out = {}
    if q is not None:
        out[prefix + "q"] = q
    if st is not None:
        out[prefix + "st"] = st
    if sp is not None:
        out[prefix + "sp"] = sp
    if ex is not None:
        out[prefix + "ex"] = ex
    return out


# -- worker trace shard -------------------------------------------------------
# One process-lifetime journal per executor worker: task spans, fetch/serve
# spans and deep-layer events all land here (query executions ADOPT it, so
# operator spans do too), and the driver drains it incrementally over
# rpc_drain_journal into the merged cluster timeline.

_SHARD: List[Optional[EventJournal]] = [None]


def open_shard(executor_id: str, path: Optional[str] = None,
               max_events: int = 65536) -> EventJournal:
    """Open (or return) this process's trace shard and push it as the
    bottom-of-stack active journal so every deep-layer event has a home
    even outside query execution (serve threads, idle heartbeats)."""
    if _SHARD[0] is not None:
        return _SHARD[0]
    shard = EventJournal(path, anchor=True, label=executor_id,
                         mirror=True, max_lines=max_events, is_shard=True)
    _SHARD[0] = shard
    with _ACTIVE_LOCK:
        # bottom of stack; is_shard makes it reachable from EVERY thread
        _ACTIVE.insert(0, (threading.get_ident(), shard))
    return shard


def process_shard() -> Optional[EventJournal]:
    return _SHARD[0]


def close_shard() -> None:
    """Tear down the process shard (tests; workers die with theirs)."""
    shard = _SHARD[0]
    _SHARD[0] = None
    if shard is not None:
        pop_active(shard)
        shard.close()

"""Memory-pressure observability: the allocation ledger (mem/ledger.py),
its offline analyzer (metrics/memledger.py + the --memory CLI), per-store
watermarks, and the heartbeat peak roll-up.

Acceptance surface (ISSUE 8):

  * causal chains — on the spill-cascade slice every `oomSpill` ledger
    record links to a triggering reservation (site + cause id that
    resolves to a `reserve` record in the same journal) and, whenever
    bytes actually moved, to >= 1 victim buffer id;
  * deterministic injectOom at every reserve site of a join+agg+sort
    slice leaves results bit-for-bit identical with the ledger on;
  * watermark monotonicity + reset-aware peaks in pool_stats();
  * churn detection on a forced spill->unspill->respill;
  * trace-context stamping of ledger records;
  * the --memory CLI reconstructs the analysis from journal files alone.
"""
from __future__ import annotations

import json
import time
import types

import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.metrics import names as MN
from spark_rapids_tpu.metrics.memledger import analyze_shards, render
from spark_rapids_tpu.metrics.timeline import load_journal_dir
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.utils import faults

pytestmark = pytest.mark.memledger

# the spill-cascade slice: partitioned join -> grouped agg -> sort with a
# pool budget far below the working set, so the device->host->disk
# cascade genuinely engages
_CASCADE_CONF = {
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.memory.tpu.poolSizeBytes": str(2 << 20),
    "spark.rapids.memory.host.spillStorageSize": str(1 << 20),
    "spark.rapids.sql.batchSizeBytes": str(512 << 10),
    "spark.rapids.sql.reader.batchSizeRows": "16384",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.rapids.sql.tpu.shuffle.partitions": "8",
}


def _slice_query(s, n=60_000):
    fact = s.from_pydict({"k": [i % 7 for i in range(n)],
                          "v": [float(i) for i in range(n)],
                          "q": [i % 3 for i in range(n)]})
    dim = s.from_pydict({"k": list(range(7)),
                         "name": [f"g{j}" for j in range(7)]})
    return (fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"), F.count(lit(1)).alias("c"))
            .order_by(col("name")).collect())


def _run_cascade(tmp_path, extra=None, n=60_000):
    faults.INJECTOR.reset()
    jdir = str(tmp_path / f"journal_{time.monotonic_ns()}")
    conf = dict(_CASCADE_CONF,
                **{"spark.rapids.sql.tpu.metrics.journal.dir": jdir})
    conf.update(extra or {})
    s = TpuSession(conf)
    rows = _slice_query(s, n)
    return rows, jdir, s


def _mem_events(jdir):
    out = []
    for sh in load_journal_dir(jdir):
        out += [e for e in sh["events"]
                if e.get("kind") == "mem" and e.get("ev") == "I"]
    return out


# --------------------------------------------------------------------------
# causal chain: reserve -> oomSpill -> victims
# --------------------------------------------------------------------------

def test_oom_spill_records_link_site_cause_and_victims(tmp_path):
    """Every oomSpill ledger record that moved bytes names its
    reservation site, a cause id resolving to a reserve record in the
    same journal, and the exact victim buffer ids."""
    _rows, jdir, s = _run_cascade(tmp_path)
    assert s.runtime.pool_stats().get(MN.OOM_SPILL_RETRIES, 0) > 0, \
        "the cascade conf did not actually engage the spill handler"
    ev = _mem_events(jdir)
    rids = {e.get("rid") for e in ev if e.get("name") == "reserve"}
    ooms = [e for e in ev if e.get("name") == "oomSpill"]
    assert ooms, "no oomSpill ledger records on the cascade slice"
    moved = [e for e in ooms if int(e.get("spilled_bytes") or 0) > 0]
    assert moved, "no oomSpill round spilled bytes"
    for e in moved:
        assert e.get("site"), e
        assert e.get("cause") in rids, \
            f"cause {e.get('cause')} has no reserve record: {e}"
        assert len(e.get("victims") or []) >= 1, e
    # the legacy spill/oomSpill journal record mirrors the site too
    # (satellite: site-attributable even without the full ledger)
    legacy = []
    for sh in load_journal_dir(jdir):
        legacy += [e for e in sh["events"]
                   if e.get("kind") == "spill"
                   and e.get("name") == "oomSpill"]
    assert legacy and all(e.get("site") for e in legacy)
    # every victim's own spill record carries the same cause id, so the
    # cascade is traversable from either end
    spills = {e.get("cause") for e in ev if e.get("name") == "spill"}
    assert {e["cause"] for e in moved} <= spills


def test_cascade_chain_reconstructed_offline(tmp_path):
    """analyze_shards reconstructs the full cascade chains and per-query
    peak attribution from the journal files alone."""
    _rows, jdir, s = _run_cascade(tmp_path)
    rep = analyze_shards(load_journal_dir(jdir))
    assert rep["totals"]["oom_spills"] > 0
    assert rep["cascades"], "no cascade chains reconstructed"
    for c in rep["cascades"]:
        assert c["site"]
        assert c["cause"]
        if c["spilled_bytes"] > 0:
            assert c["victims"]
    # peak attribution: the driver's queries appear with a real footprint
    assert rep["peak_by_query"], rep
    assert max(rep["peak_by_query"].values()) > 0
    # per-site allocation attribution: alloc records carry the explicit
    # registration-path site (the admitting reserve() has already closed
    # by registration time, so the label must not depend on it)
    assert "add_batch" in rep["alloc_by_site"], rep["alloc_by_site"]
    assert rep["alloc_by_site"]["add_batch"] > 0
    # pool limit was 2MB; the analyzer's replayed peak must be in a sane
    # band around it (admission happens before the spill trims back)
    peaks = [i["device_peak"] for i in rep["executors"].values()]
    assert max(peaks) > 0
    # headroom: the constrained run must report a shortfall
    assert rep["headroom"]["bytes"] > 0
    # pressure lane sampled
    assert any(i["pressure"]["samples"] > 0
               for i in rep["executors"].values())


def test_injectoom_composes_with_real_cascade(tmp_path):
    """The acceptance composition: the cascade slice under injectOom
    still returns bit-for-bit results, and the surviving oomSpill
    records still carry site + victims."""
    baseline, _j, _s = _run_cascade(tmp_path)
    out, jdir, _s = _run_cascade(
        tmp_path, extra={"spark.rapids.tpu.test.injectOom": "5"})
    assert out == baseline
    assert faults.INJECTOR.injected_log, "ordinal 5 never fired"
    ev = _mem_events(jdir)
    moved = [e for e in ev if e.get("name") == "oomSpill"
             and int(e.get("spilled_bytes") or 0) > 0]
    assert moved
    assert all(e.get("site") and e.get("victims") for e in moved)


def test_cascade_downstream_legs_attach_despite_record_order():
    """The victims' spill records are journaled BEFORE the oomSpill
    record that opens the chain (synchronous_spill runs first): the
    analyzer must still attach host->disk downstream legs sharing the
    cause id — a single-round device->host->disk cascade reports its
    disk leg."""
    ev = [
        {"ev": "I", "kind": "mem", "name": "reserve", "id": 1,
         "ts": 1, "rid": 7, "site": "agg.update", "bytes": 100},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 2, "ts": 2,
         "buffer": 1, "bytes": 100, "src": "DEVICE", "dst": "HOST",
         "cause": 7, "cause_site": "agg.update"},
        # host overflow to disk, journaled BEFORE the oomSpill record
        {"ev": "I", "kind": "mem", "name": "spill", "id": 3, "ts": 3,
         "buffer": 2, "bytes": 80, "src": "HOST", "dst": "DISK",
         "cause": 7, "cause_site": "agg.update"},
        {"ev": "I", "kind": "mem", "name": "oomSpill", "id": 4, "ts": 4,
         "site": "agg.update", "cause": 7, "victims": [1],
         "alloc_size": 100, "spilled_bytes": 100, "store_size": 150,
         "limit": 120},
    ]
    rep = analyze_shards([{"label": "exec-0", "events": ev}])
    assert len(rep["cascades"]) == 1
    chain = rep["cascades"][0]
    assert chain["victims"] == [1]
    assert chain["downstream"] == [
        {"buffer": 2, "bytes": 80, "src": "HOST", "dst": "DISK"}]
    assert rep["headroom"]["bytes"] == 130  # 150 + 100 - 120


def test_oom_victims_exclude_downstream_legs():
    """oomSpill victims are the DEVICE evictions synchronous_spill
    chose; a host tier overflowing to disk under the same reservation is
    a downstream cascade leg, not a victim (and must not duplicate a
    buffer already listed)."""
    from spark_rapids_tpu.mem.buffer import StorageTier
    from spark_rapids_tpu.mem.ledger import MemoryLedger
    led = MemoryLedger(enabled=True)
    with led.reservation("agg.update", 100):
        led.on_spill(1, 100, StorageTier.DEVICE, StorageTier.HOST)
        led.on_spill(1, 80, StorageTier.HOST, StorageTier.DISK)
        led.on_spill(2, 50, StorageTier.HOST, StorageTier.DISK)
        attrs = led.on_oom_spill(100, 100, 150, limit=120)
    assert attrs["victims"] == [1]


def test_unspill_of_unknown_buffer_does_not_inflate_peaks():
    """A buffer allocated before this journal opened (the runtime
    outlives per-query journals) that unspills mid-journal must be
    registered by the replay, so its later spill subtracts the bytes —
    otherwise peaks inflate permanently."""
    ev = [
        {"ev": "I", "kind": "mem", "name": "unspill", "id": 1, "ts": 1,
         "buffer": 7, "bytes": 1000, "src": "HOST", "q": "q2"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 2, "ts": 2,
         "buffer": 7, "bytes": 1000, "src": "DEVICE", "dst": "HOST"},
        {"ev": "I", "kind": "mem", "name": "alloc", "id": 3, "ts": 3,
         "buffer": 8, "bytes": 600, "site": "add_batch", "q": "q2"},
    ]
    rep = analyze_shards([{"label": "exec-0", "events": ev}])
    # with the ghost bytes stuck on-device the alloc would read 1600
    assert rep["executors"]["exec-0"]["device_peak"] == 1000
    assert rep["peak_by_query"]["q2"] == 1000


def test_unspill_rebases_buffer_size_in_replay():
    """Spilling rebases a buffer's meta to host-leaf bytes, so an
    unspill legitimately carries a DIFFERENT size than the alloc; the
    replay must subtract what the unspill added (not the stale alloc
    size) on the next spill, or device accounting drifts per thrash
    cycle."""
    ev = [
        {"ev": "I", "kind": "mem", "name": "alloc", "id": 1, "ts": 1,
         "buffer": 1, "bytes": 100, "site": "add_batch", "q": "q1"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 2, "ts": 2,
         "buffer": 1, "bytes": 100, "src": "DEVICE", "dst": "HOST"},
        # host-leaf form is smaller than the device form
        {"ev": "I", "kind": "mem", "name": "unspill", "id": 3, "ts": 3,
         "buffer": 1, "bytes": 60, "src": "HOST"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 4, "ts": 4,
         "buffer": 1, "bytes": 60, "src": "DEVICE", "dst": "HOST"},
        # device must now read EMPTY: an alloc of 70 peaks at 70, not
        # 70 + a 40-byte residual from the stale alloc size
        {"ev": "I", "kind": "mem", "name": "alloc", "id": 5, "ts": 5,
         "buffer": 2, "bytes": 70, "site": "add_batch", "q": "q1"},
    ]
    rep = analyze_shards([{"label": "exec-0", "events": ev}])
    assert rep["executors"]["exec-0"]["device_peak"] == 100
    assert rep["peak_by_query"]["q1"] == 100


def test_churn_ratio_denominator_is_device_spills_only():
    """A thrashing buffer whose cascade reaches disk must still report
    100% churn on its re-spill: host->disk migration legs do not belong
    in the denominator (they would deflate the ratio most at exactly the
    tightest budgets)."""
    ev = [
        {"ev": "I", "kind": "mem", "name": "alloc", "id": 1, "ts": 1,
         "buffer": 1, "bytes": 20, "site": "add_batch", "q": "q1"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 2, "ts": 2,
         "buffer": 1, "bytes": 20, "src": "DEVICE", "dst": "HOST"},
        {"ev": "I", "kind": "mem", "name": "unspill", "id": 3, "ts": 3,
         "buffer": 1, "bytes": 20, "src": "HOST"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 4, "ts": 4,
         "buffer": 1, "bytes": 20, "src": "DEVICE", "dst": "HOST"},
        {"ev": "I", "kind": "mem", "name": "spill", "id": 5, "ts": 5,
         "buffer": 1, "bytes": 15, "src": "HOST", "dst": "DISK"},
    ]
    rep = analyze_shards([{"label": "exec-0", "events": ev}])
    ch = rep["churn"]
    assert ch["spilled_bytes"] == 40          # device legs only
    assert ch["respill_bytes"] == 20
    assert ch["churn_ratio"] == 0.5
    assert rep["totals"]["spilled_bytes"] == 55  # all legs, totals line


# --------------------------------------------------------------------------
# injectOom sweep: results bit-for-bit with the ledger on
# --------------------------------------------------------------------------

def test_injectoom_every_site_bit_for_bit_with_ledger(tmp_path):
    """Deterministic OOM at EVERY reserve site of the slice (discovered
    fault-free, replayed one ordinal at a time) with the ledger + file
    journal on: results identical to the fault-free baseline."""
    def q(extra=None):
        faults.INJECTOR.reset()
        jdir = str(tmp_path / f"sweep_{time.monotonic_ns()}")
        conf = {
            "spark.rapids.sql.variableFloatAgg.enabled": "true",
            "spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
            "spark.rapids.sql.tpu.shuffle.partitions": "4",
            "spark.rapids.sql.tpu.metrics.journal.dir": jdir,
        }
        conf.update(extra or {})
        s = TpuSession(conf)
        return _slice_query(s, n=400)

    baseline = q()
    n_ops = faults.INJECTOR.oom_ops
    assert n_ops > 5, "slice exposed too few reserve sites"
    for ordinal in range(1, n_ops + 1):
        out = q({"spark.rapids.tpu.test.injectOom": str(ordinal)})
        assert out == baseline, f"ordinal {ordinal} changed the result"
        assert faults.INJECTOR.injected_log, \
            f"ordinal {ordinal} never fired"


# --------------------------------------------------------------------------
# watermarks
# --------------------------------------------------------------------------

def test_watermark_monotonic_and_reset_aware(tmp_path):
    """device/host/disk peaks only ever grow during a run, survive the
    spill that empties a tier, and reset_peaks() rebases them."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.mem.runtime import TpuRuntime

    rt = TpuRuntime(TpuConf({}), pool_limit_bytes=1 << 30,
                    spill_dir=str(tmp_path))
    last = {"device_peak": 0, "host_peak": 0, "disk_peak": 0}
    bids = []
    for i in range(4):
        t = pa.table({"v": np.arange(1000, dtype=np.float64)})
        bids.append(rt.add_batch(ColumnarBatch.from_arrow(t)))
        ps = rt.pool_stats()
        for k in last:
            assert ps[k] >= last[k], f"{k} regressed"
            last[k] = ps[k]
    assert last["device_peak"] >= rt.device_store.current_size > 0
    # spill everything: device empties but its peak must NOT move
    rt.device_store.synchronous_spill(0)
    ps = rt.pool_stats()
    assert ps["device_used"] == 0
    assert ps["device_peak"] == last["device_peak"]
    assert ps["host_peak"] > 0
    # host -> disk
    rt.host_store.synchronous_spill(0)
    ps = rt.pool_stats()
    assert ps["disk_peak"] > 0
    assert ps["host_peak"] >= ps["host_used"]
    # reset-aware: peaks rebase to CURRENT usage, not zero
    rt.reset_peaks()
    ps = rt.pool_stats()
    assert ps["device_peak"] == ps["device_used"]
    assert ps["host_peak"] == ps["host_used"]
    assert ps["disk_peak"] == ps["disk_used"]
    for b in bids:
        rt.free_batch(b)


# --------------------------------------------------------------------------
# churn + trace stamping (bare runtime, file journal)
# --------------------------------------------------------------------------

def _bare_runtime_with_journal(tmp_path):
    from spark_rapids_tpu.mem.runtime import TpuRuntime
    from spark_rapids_tpu.metrics.journal import EventJournal, push_active
    path = str(tmp_path / "query-77.jsonl")
    j = EventJournal(path, query_id=77, anchor=True, label="driver")
    push_active(j)
    rt = TpuRuntime(TpuConf({}), pool_limit_bytes=1 << 30,
                    spill_dir=str(tmp_path / "spill"))
    return rt, j, path


def test_churn_detected_on_forced_respill(tmp_path):
    """spill -> unspill -> spill again of one buffer is thrash: the live
    numBufferRespills counter fires and the analyzer's churn section
    names the buffer with a non-zero churn ratio."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.metrics.journal import pop_active

    rt, j, _path = _bare_runtime_with_journal(tmp_path)
    try:
        t = pa.table({"v": np.arange(4000, dtype=np.float64)})
        bid = rt.add_batch(ColumnarBatch.from_arrow(t))
        rt.device_store.synchronous_spill(0)    # spill 1
        rt.get_batch(bid)                       # unspill (re-touch)
        rt.device_store.synchronous_spill(0)    # spill 2 = respill
        assert rt.pool_stats().get(MN.NUM_BUFFER_RESPILLS, 0) >= 1
    finally:
        pop_active(j)
        j.close()
    rep = analyze_shards(load_journal_dir(str(tmp_path)))
    ch = rep["churn"]
    assert ch["churn_ratio"] > 0
    assert any(b["buffer"] == bid for b in ch["respilled_buffers"])
    # victim quality saw the re-touch within the window
    assert rep["victim_quality"]["retouched"] >= 1


def test_ledger_records_carry_trace_context(tmp_path):
    """Ledger records inherit the installed (query, stage, executor)
    trace context — what lets worker-side mem events attribute to the
    driver's query in the merged timeline."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.metrics.journal import pop_active, trace_context

    rt, j, path = _bare_runtime_with_journal(tmp_path)
    try:
        with trace_context(query="q-test", stage="s9.map",
                           executor="exec-9"):
            t = pa.table({"v": np.arange(2000, dtype=np.float64)})
            bid = rt.add_batch(ColumnarBatch.from_arrow(t))
            rt.device_store.synchronous_spill(0)
            rt.free_batch(bid)
    finally:
        pop_active(j)
        j.close()
    ev = [e for e in map(json.loads, open(path))
          if e.get("kind") == "mem"]
    stamped = [e for e in ev if e.get("name") in ("alloc", "spill",
                                                  "free")]
    assert stamped
    for e in stamped:
        assert e.get("q") == "q-test", e
        assert e.get("st") == "s9.map", e
        assert e.get("ex") == "exec-9", e


def test_no_active_journal_counts_nothing():
    """With no journal open a ledger record has nowhere to land:
    memLedgerEvents must stay zero (it counts exactly what a --memory
    replay will find) while the live respill counter still works."""
    from spark_rapids_tpu.mem.buffer import StorageTier
    from spark_rapids_tpu.mem.ledger import MemoryLedger
    from spark_rapids_tpu.metrics.registry import Metrics
    m = Metrics()
    led = MemoryLedger(enabled=True, metrics=m)
    led.on_alloc(1, 100, site="add_batch")
    led.on_spill(1, 100, StorageTier.DEVICE, StorageTier.HOST)
    led.on_unspill(1, 100, StorageTier.HOST)
    led.on_spill(1, 100, StorageTier.DEVICE, StorageTier.HOST)
    vals = m.snapshot()
    assert vals.get(MN.MEM_LEDGER_EVENTS, 0) == 0
    assert vals.get(MN.NUM_BUFFER_RESPILLS, 0) == 1


def test_ledger_disabled_is_silent(tmp_path):
    """Kill switch: ledger off -> zero mem records, query unaffected."""
    rows, jdir, s = _run_cascade(
        tmp_path,
        extra={"spark.rapids.sql.tpu.memory.ledger.enabled": "false"})
    assert rows  # the query still ran (and still spilled, silently)
    assert s.runtime.pool_stats().get(MN.OOM_SPILL_RETRIES, 0) > 0
    assert _mem_events(jdir) == []


def test_debug_level_journals_every_reserve(tmp_path):
    """At metrics.level=DEBUG every reserve() is a ledger record, not
    just the pressured ones."""
    _rows, jdir, _s = _run_cascade(
        tmp_path, extra={"spark.rapids.sql.tpu.metrics.level": "DEBUG"},
        n=20_000)
    ev = _mem_events(jdir)
    reserves = [e for e in ev if e.get("name") == "reserve"]
    assert len(reserves) >= faults.INJECTOR.oom_ops - 1, \
        (len(reserves), faults.INJECTOR.oom_ops)


# --------------------------------------------------------------------------
# --memory CLI on journal files alone
# --------------------------------------------------------------------------

def test_memory_cli_offline_from_journal_files(tmp_path, capsys):
    """The --memory CLI reconstructs the whole analysis from the journal
    directory with no live session/cluster."""
    from spark_rapids_tpu.metrics.__main__ import memory_main
    _rows, jdir, _s = _run_cascade(tmp_path)
    rc = memory_main([jdir])
    out = capsys.readouterr().out
    assert rc == 0
    assert "memory ledger analysis" in out
    assert "spill cascades" in out
    assert "churn:" in out
    assert "victim quality:" in out
    assert "headroom:" in out
    # flag handling: bad args are usage errors, not tracebacks
    assert memory_main([]) == 2
    assert memory_main([jdir, "--retouch-window"]) == 2
    assert memory_main([str(tmp_path / "empty_nonexistent")]) == 1


def test_memory_cli_render_roundtrip(tmp_path):
    """render() consumes exactly what analyze_shards produces (the CLI
    body) even for a journal with no pressure at all."""
    _rows, jdir, _s = _run_cascade(
        tmp_path,
        extra={"spark.rapids.memory.tpu.poolSizeBytes": str(1 << 30)},
        n=5_000)
    rep = analyze_shards(load_journal_dir(jdir))
    text = render(rep)
    assert "no OOM event recorded a shortfall" in text
    assert rep["totals"]["oom_spills"] == 0


# --------------------------------------------------------------------------
# chrome trace memory lane + timeline surface
# --------------------------------------------------------------------------

def test_chrome_trace_renders_memory_counter_lane(tmp_path):
    """Pressure samples render as Chrome counter (ph C) events in both
    the single-journal and the merged-cluster trace writers."""
    from spark_rapids_tpu.metrics.timeline import merge_shards
    from spark_rapids_tpu.utils.tracing import (journal_to_trace_events,
                                                timeline_to_trace_events)
    _rows, jdir, _s = _run_cascade(tmp_path)
    shards = load_journal_dir(jdir)
    all_events = [e for sh in shards for e in sh["events"]]
    counters = [r for r in journal_to_trace_events(all_events)
                if r.get("ph") == "C"]
    assert counters and all(r["name"] == "memory" for r in counters)
    assert all({"device", "host", "disk"} <= set(r["args"])
               for r in counters)
    tl = merge_shards(shards)
    ctr2 = [r for r in timeline_to_trace_events(tl)
            if r.get("ph") == "C"]
    assert ctr2
    # the merged timeline's report carries the memory summary
    rep = tl.report()
    assert rep["memory"]
    assert any(m["samples"] > 0 for m in rep["memory"].values())
    assert "memory pressure" in tl.render()


# --------------------------------------------------------------------------
# heartbeat peak roll-up (restart-aware)
# --------------------------------------------------------------------------

def test_heartbeat_monitor_rolls_up_peaks_restart_aware():
    """Worker pool peaks roll up into cluster peak memory with the same
    monotonic restart semantics as the counter totals: a replaced
    worker's reset peaks never regress the roll-up."""
    from spark_rapids_tpu.cluster import HeartbeatMonitor

    fake = types.SimpleNamespace(workers=[], _transport=None)
    mon = HeartbeatMonitor(fake, interval_s=3600, hung_timeout_s=0)
    try:
        def hb(pid, dev, host, disk):
            return {"pid": pid, "tasks_completed": 0, "rows_written": 0,
                    "counters": {}, "active_tasks": [],
                    "wall_ns": time.time_ns(),
                    "pool": {"device_peak": dev, "host_peak": host,
                             "disk_peak": disk}}

        mon._ingest("exec-0", hb(100, 1000, 50, 0), 0, 1)
        mon._ingest("exec-1", hb(101, 700, 0, 20), 2, 3)
        pm = mon.peak_memory()
        assert pm["device_peak"] == 1700
        assert pm["host_peak"] == 50
        assert pm["disk_peak"] == 20
        # exec-0 advances
        mon._ingest("exec-0", hb(100, 1500, 60, 0), 4, 5)
        assert mon.peak_memory()["device_peak"] == 2200
        # exec-0 replaced: NEW pid, peaks reset low — roll-up must not
        # regress (restart-aware max)
        mon._ingest("exec-0", hb(200, 10, 0, 0), 6, 7)
        pm = mon.peak_memory()
        assert pm["device_peak"] == 2200
        assert pm["per_worker"]["exec-0"]["device_peak"] == 1500
        # and progress() carries the roll-up
        assert mon.progress()["peak_memory"]["device_peak"] == 2200
    finally:
        mon.stop()


# --------------------------------------------------------------------------
# ProcCluster acceptance (slow tier): worker-side mem events with the
# driver's trace context, cluster peak roll-up over real heartbeats
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_proc_cluster_worker_mem_events_and_peak_rollup(tmp_path):
    """On a 2-worker ProcCluster with constrained worker pools, worker
    shards carry mem records stamped with the driver's trace query, the
    --memory analysis reconstructs worker-side pressure offline, and
    cluster.progress() reports a non-zero restart-aware peak roll-up."""
    import pyarrow as pa

    from spark_rapids_tpu.cluster import ProcCluster
    from spark_rapids_tpu.engine import DataFrame
    from spark_rapids_tpu.plan import logical as L

    jdir = str(tmp_path / "journal")
    session = TpuSession()
    rows, n_workers = 60_000, 2
    table = pa.table({"k": [i % 16 for i in range(rows)],
                      "v": [float(i) for i in range(rows)]})
    step = (rows + n_workers - 1) // n_workers
    map_plans = [session.from_arrow(table.slice(i * step, step)).plan
                 for i in range(n_workers)]
    map_schema = DataFrame(session, map_plans[0]).schema
    reduce_plan = (DataFrame(session, L.LogicalPlaceholder(map_schema))
                   .group_by(col("k"))
                   .agg(F.sum(col("v")).alias("sv"),
                        F.count(lit(1)).alias("c"))).plan
    cluster = ProcCluster(
        n_workers,
        conf={"spark.rapids.sql.tpu.metrics.journal.dir": jdir,
              "spark.rapids.sql.tpu.trace.heartbeatIntervalMs": "100",
              "spark.rapids.memory.tpu.poolSizeBytes": str(256 << 10),
              "spark.rapids.memory.host.spillStorageSize": str(128 << 10),
              "spark.rapids.sql.batchSizeBytes": str(128 << 10),
              "spark.rapids.sql.reader.batchSizeRows": "8192"},
        cpu=True, session=session)
    try:
        result, _stats = cluster.run_map_reduce(
            map_plans, ["k"], 4, reduce_plan, trace_query="mem-q")
        shards = [dict(rec) for rec in cluster.drain_journals().values()]
        # wait for a heartbeat to sample the worker pools
        deadline = time.monotonic() + 10
        while (cluster.progress()["peak_memory"]["device_peak"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.1)
        progress = cluster.progress()
    finally:
        cluster.shutdown()

    res = result.to_pydict()
    assert sorted(res["k"]) == list(range(16))
    assert sum(res["c"]) == rows

    mem = [e for sh in shards for e in sh["events"]
           if e.get("kind") == "mem"]
    assert mem, "worker shards carry no ledger records"
    stamped = [e for e in mem if e.get("q") == "mem-q"]
    assert stamped, f"no mem record stamped with the driver query: " \
                    f"{mem[:3]}"
    # offline: the worker shard FILES alone reconstruct the analysis
    rep = analyze_shards(load_journal_dir(jdir))
    assert rep["totals"]["events"] > 0
    assert any(i["pressure"]["samples"] > 0
               for i in rep["executors"].values())
    # cluster roll-up over real heartbeats
    assert progress["peak_memory"]["device_peak"] > 0
    assert set(progress["peak_memory"]["per_worker"]) >= \
        {"exec-0", "exec-1"}

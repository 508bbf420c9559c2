"""End-to-end /verify drive for the streaming micro-batch engine (PR 20).

Drives the PUBLIC streaming API against both oracles at once: an
incremental grouped aggregation over a MemoryStream must, at EVERY
epoch, match (a) the batch query over all rows appended so far run
through the same engine, bit-for-bit, and (b) a numpy hand oracle
(exact on int64 sum/count, 1e-12 relative on the float average).  Warm
epochs must compile zero new kernels or stages and hit the plan cache.
A query killed mid-stream and restarted from its checkpoint must drain
the remaining epochs and land bit-for-bit on the uninterrupted result,
bumping numStateRecoveries.  stop() must free every owner-stamped
state byte in every tier.

CPU-forced standalone (never takes an accelerator); safe under
`timeout 600`.  Run: `python scripts/verify_streaming_drive.py`.
"""
import os
import struct
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
force_cpu_backend()

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.engine import DataFrame, TpuSession
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import col, functions as F
from spark_rapids_tpu.streaming import MemoryStream, stream_query
from spark_rapids_tpu.utils import kernel_cache as KC

EPOCH_ROWS = 500
N_EPOCHS = 6
CONF = {
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.sql.reader.batchSizeRows": str(EPOCH_ROWS),
    "spark.rapids.sql.tpu.streaming.maxBatchRows": str(EPOCH_ROWS),
}

rng = np.random.RandomState(7)
K = rng.randint(0, 13, EPOCH_ROWS * N_EPOCHS).astype(np.int64)
V = rng.randint(-1000, 1000, EPOCH_ROWS * N_EPOCHS).astype(np.int64)
X = rng.uniform(-10.0, 10.0, EPOCH_ROWS * N_EPOCHS)
CHUNKS = [pa.table({"k": K[i * EPOCH_ROWS:(i + 1) * EPOCH_ROWS],
                    "v": V[i * EPOCH_ROWS:(i + 1) * EPOCH_ROWS],
                    "x": X[i * EPOCH_ROWS:(i + 1) * EPOCH_ROWS]})
          for i in range(N_EPOCHS)]


def build(df):
    return df.group_by(col("k")).agg(
        F.sum(col("v")).alias("sv"),
        F.count(col("v")).alias("cv"),
        F.avg(col("x")).alias("ax"))


def canon(table):
    rows = []
    for row in table.to_pylist():
        rows.append(tuple(
            struct.pack("<d", v) if isinstance(v, float) else v
            for v in (row[name] for name in sorted(row))))
    return sorted(rows, key=repr)


def batch_oracle(session, source):
    scan = L.LogicalScan(source.rows_between(0, source.latest_offset()),
                         source.schema, "memory")
    return build(DataFrame(session, scan)).to_arrow()


def hand_oracle(n_rows):
    k, v, x = K[:n_rows], V[:n_rows], X[:n_rows]
    out = {}
    for key in np.unique(k):
        m = k == key
        out[int(key)] = (int(v[m].sum()), int(m.sum()), float(x[m].mean()))
    return out


def check_hand(table, n_rows):
    want = hand_oracle(n_rows)
    got = {row["k"]: (row["sv"], row["cv"], row["ax"])
           for row in table.to_pylist()}
    assert set(got) == set(want), (set(got), set(want))
    for key, (sv, cv, ax) in want.items():
        gsv, gcv, gax = got[key]
        assert gsv == sv and gcv == cv, (key, got[key], want[key])
        assert abs(gax - ax) <= 1e-12 * max(1.0, abs(ax)), (key, gax, ax)


def owner_bytes(session, owner):
    rt = session.runtime
    return sum(st.owner_size(owner) for st in
               (rt.device_store, rt.host_store, rt.disk_store))


def main():
    ckpt = tempfile.mkdtemp(prefix="verify_stream_ck_")

    # -- incremental vs both oracles at every epoch, zero warm compiles --
    s = TpuSession(dict(CONF))
    src = MemoryStream(CHUNKS[0].slice(0, 0), name="drive")
    q = stream_query(s, src, build, name="drive", checkpoint_dir=ckpt)
    warm_deltas = []
    for i, chunk in enumerate(CHUNKS[:4]):
        src.append(chunk)
        before = KC.stats()
        assert q.trigger_once(), f"epoch {i + 1} did not commit"
        after = KC.stats()
        if i >= 1:
            warm_deltas.append(
                (after["builds"] - before["builds"],
                 after["stage_compiles"] - before["stage_compiles"]))
        inc = q.result()
        assert canon(inc) == canon(batch_oracle(s, src)), f"epoch {i + 1}"
        check_hand(inc, (i + 1) * EPOCH_ROWS)
    assert warm_deltas and all(d == (0, 0) for d in warm_deltas), warm_deltas
    pc = s.scheduler.stats()["plan_cache"]
    assert pc["hits"] >= 3, pc
    print(f"epochs 1-4 bit-for-bit vs engine + numpy oracles; warm "
          f"compile deltas {warm_deltas}, plan cache {pc['hits']} hits")

    # -- kill mid-stream, restart from checkpoint, drain the rest --------
    owner = q._state.owner
    assert owner_bytes(s, owner) > 0
    q._state.release()          # simulate a hard kill: no stop() cleanup
    del q
    s2 = TpuSession(dict(CONF))
    before_rec = s2.runtime.metrics.snapshot().get("numStateRecoveries", 0)
    q2 = stream_query(s2, src, build, name="drive", checkpoint_dir=ckpt)
    assert s2.runtime.metrics.snapshot()["numStateRecoveries"] == \
        before_rec + 1
    for chunk in CHUNKS[4:]:
        src.append(chunk)
    assert q2.process_available() == 2
    final = q2.result()
    assert canon(final) == canon(batch_oracle(s2, src)), "post-restart"
    check_hand(final, N_EPOCHS * EPOCH_ROWS)
    print(f"restart recovered epoch 4, drained 2 more epochs, final "
          f"bit-for-bit over {N_EPOCHS * EPOCH_ROWS} rows")

    # -- stop() frees every owner byte in every tier ---------------------
    owner2 = q2._state.owner
    held = owner_bytes(s2, owner2)
    freed = q2.stop()
    assert freed > 0 and held > 0 and owner_bytes(s2, owner2) == 0, \
        (held, freed)
    print(f"stop() freed {freed} owner bytes; zero residual")

    s.shutdown_serving()
    s2.shutdown_serving()
    print("VERIFY STREAMING DRIVE OK")


if __name__ == "__main__":
    main()

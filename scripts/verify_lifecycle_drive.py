"""End-to-end /verify drive for the query lifecycle layer (PR 19).

Drives the PUBLIC serving API against a hand-computed numpy oracle:
a submitted aggregation must match the oracle bit-for-bit; a cancelled
running query must fail with the typed QueryCancelled and leave zero
owner-stamped bytes in any tier; an expired deadline must shed at
admission with the typed QueryDeadlineExceeded; with preemption on, a
high-priority arrival must suspend the low-priority victim and the
victim must still produce the oracle's bytes after resuming; with the
lifecycle kill switch off, cancel() is a False no-op and results are
identical.

CPU-forced standalone (never takes an accelerator); safe under
`timeout 600`.  Run: `python scripts/verify_lifecycle_drive.py`.
"""
import sys
import os
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
force_cpu_backend()

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.serve.lifecycle import (QueryCancelled,
                                              QueryDeadlineExceeded)

N = 200_000
rng = np.random.RandomState(11)
A = rng.uniform(0.0, 100.0, N)
B = rng.randint(0, 50, N).astype(np.int64)
TABLE = pa.table({"a": A, "b": B})

CONF = {
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.sql.reader.batchSizeRows": "2000",
}


def q_agg(df):
    return (df.filter(col("a") > 5.0)
            .group_by(col("b")).agg(F.count(lit(1)).alias("n"))
            .order_by("b"))


def hand_oracle():
    mask = A > 5.0
    keys, counts = np.unique(B[mask], return_counts=True)
    return pa.table({"b": keys, "n": counts.astype(np.int64)})


def owner_bytes(s, qid):
    rt = s.runtime
    return sum(st.owner_size(f"q{qid}") for st in
               (rt.device_store, rt.host_store, rt.disk_store))


def main():
    oracle = hand_oracle()

    # 1. submitted query vs hand oracle (exact: int64 counts)
    s = TpuSession(dict(CONF))
    got = s.submit(q_agg(s.from_arrow(TABLE))).result(300)
    assert got.equals(oracle), "submit() result diverged from hand oracle"
    print("1. submit vs hand oracle: bit-for-bit OK")

    # 2. cancel a running query: typed error, zero residual owner bytes
    df = s.from_arrow(TABLE)
    f = s.submit(df.select((col("a") * lit(2.0)).alias("x"), col("b")))
    while f.admitted_ns is None:
        time.sleep(0.002)
    time.sleep(0.03)
    f.cancel("verify drive")
    err = f.exception(120)
    assert err is None or isinstance(err, QueryCancelled), repr(err)
    assert owner_bytes(s, f.query_id) == 0, "residual owner bytes"
    print(f"2. cancel running: typed={type(err).__name__ if err else 'finished first'}, owner bytes 0 OK")

    # 3. expired deadline sheds at admission, typed
    f = s.submit(q_agg(df), deadline_ms=0.001)
    err = f.exception(60)
    assert isinstance(err, QueryDeadlineExceeded), repr(err)
    assert "shed at admission" in str(err)
    print("3. deadline shed: typed QueryDeadlineExceeded OK")
    s.shutdown_serving()

    # 4. preemption: victim suspends for the high-priority arrival and
    # still returns the oracle's bytes
    # wholeStage off keeps the agg victim on its streaming per-batch
    # update loop — the fused probe drain's suspend window is too narrow
    # to hit deterministically (same shape tests/test_lifecycle.py uses)
    s = TpuSession({**CONF,
                    "spark.rapids.sql.tpu.serve.maxConcurrentQueries": "2",
                    "spark.rapids.sql.concurrentTpuTasks": "1",
                    "spark.rapids.sql.tpu.serve.preemption.enabled": "true",
                    "spark.rapids.sql.tpu.wholeStage.enabled": "false"})
    df = s.from_arrow(TABLE)
    preempted = False
    for _ in range(3):
        victim = s.submit(q_agg(df), priority=0)
        while victim.admitted_ns is None:
            time.sleep(0.002)
        hi = s.submit(df.limit(5), priority=10)
        hi.result(300)
        assert victim.result(300).equals(oracle), \
            "preempted victim diverged from hand oracle"
        st = s.scheduler.stats()["lifecycle"]
        if st["preemptions"] > 0:
            assert st["preemption_resumes"] == st["preemptions"]
            preempted = True
            break
    assert preempted, "no preemption observed in 3 attempts"
    print(f"4. preemption: {st['preemptions']} suspend/resume, victim bit-for-bit OK")
    s.shutdown_serving()

    # 5. kill switch: no token, cancel() False, identical bytes
    s = TpuSession({**CONF,
                    "spark.rapids.sql.tpu.serve.lifecycle.enabled": "false"})
    f = s.submit(q_agg(s.from_arrow(TABLE)), deadline_ms=0.001)
    assert f.lifecycle is None
    assert f.cancel("ignored") is False
    assert f.result(300).equals(oracle), "kill-switch result diverged"
    print("5. kill switch: no token, cancel()=False, bit-for-bit OK")
    s.shutdown_serving()

    print("verify_lifecycle_drive: ALL OK")


if __name__ == "__main__":
    main()

"""End-to-end /verify drive for the data-movement policy engine (PR 18).

Runs the spill-cascade slice (join+filter+agg+sort under a 2MB pool)
three ways — policy ON, policy OFF, and unconstrained oracle — asserts
bit-for-bit equality, live policy counters, and that the --memory CLI
replays the decision stream from journal shards alone.

CPU-forced standalone (never takes an accelerator); safe under
`timeout 300`.  Run: `python scripts/verify_policy_drive.py`.
"""
import sys
import os
import subprocess
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
force_cpu_backend()

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.metrics import names as MN
from spark_rapids_tpu.metrics.export import session_observability
from spark_rapids_tpu.plan.logical import col, functions as F, lit

CASCADE = {
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.memory.tpu.poolSizeBytes": str(2 << 20),
    "spark.rapids.memory.host.spillStorageSize": str(1 << 20),
    "spark.rapids.sql.batchSizeBytes": str(512 << 10),
    "spark.rapids.sql.reader.batchSizeRows": "16384",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.rapids.sql.tpu.shuffle.partitions": "8",
}
N = 60_000


def run(conf):
    s = TpuSession(conf)
    fact = s.from_pydict({"k": [i % 7 for i in range(N)],
                          "v": [float(i) for i in range(N)],
                          "q": [i % 3 for i in range(N)]})
    dim = s.from_pydict({"k": list(range(7)),
                         "name": [f"g{j}" for j in range(7)]})
    rows = (fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"), F.count(lit(1)).alias("c"))
            .order_by(col("name")).collect())
    return rows, s


def main():
    with tempfile.TemporaryDirectory() as td:
        jdir = os.path.join(td, "journal")
        on_conf = dict(CASCADE, **{
            "spark.rapids.sql.tpu.metrics.journal.dir": jdir})
        rows_on, s_on = run(on_conf)
        rows_off, s_off = run(dict(
            CASCADE, **{"spark.rapids.sql.tpu.policy.enabled": "false"}))
        rows_oracle, _ = run({})

        assert rows_on == rows_off == rows_oracle, "results diverge"
        print(f"bit-for-bit: OK ({len(rows_on)} rows, sv[0]={rows_on[0]})")

        # hand oracle on the aggregate itself
        sv = {}
        cnt = {}
        for i in range(N):
            if i % 3 < 2:
                g = f"g{i % 7}"
                sv[g] = sv.get(g, 0.0) + float(i)
                cnt[g] = cnt.get(g, 0) + 1
        for name, got_sv, got_c in rows_on:
            assert abs(got_sv - sv[name]) < 1e-6, (name, got_sv)
            assert got_c == cnt[name], (name, got_c)
        print("hand oracle: OK")

        obs = session_observability(s_on)
        assert obs["numPolicyVictimPicks"] > 0, obs
        obs_off = session_observability(s_off)
        assert obs_off["numPolicyVictimPicks"] == 0, obs_off
        print(f"policy counters: victimPicks={obs['numPolicyVictimPicks']} "
              f"earlyReleases={obs['numPolicyEarlyReleases']} "
              f"unspills={obs['numProactiveUnspills']} (OFF session: all 0)")

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t0 = time.monotonic()
        cp = subprocess.run(
            [sys.executable, "-m", "spark_rapids_tpu.metrics",
             "--memory", jdir],
            capture_output=True, text=True, env=env, timeout=240)
        assert cp.returncode == 0, cp.stderr
        assert "policy decisions:" in cp.stdout, cp.stdout[-2000:]
        assert "scored picks" in cp.stdout, cp.stdout[-2000:]
        print(f"--memory replay: OK ({time.monotonic() - t0:.1f}s)")
        for line in cp.stdout.splitlines():
            if "policy" in line or "scored" in line or "release" in line:
                print("  " + line.strip())
    print("VERIFY_POLICY_DRIVE_PASS")


if __name__ == "__main__":
    main()

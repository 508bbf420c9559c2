"""`run.py` end to end off the chip: it fails at once without `--rows`, and
with `--rows` it runs the whole control flow and still ends
`correct: false` with no number under a metric's name."""
import json
import os
import subprocess
import sys

import pytest

import cells


def run_py(*args, bench_dir=cells.BENCH_DIR):
    # a copy of the benchmark elsewhere still finds the program under test
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(cells.BENCH_DIR))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(bench_dir, "run.py"), "--seed",
         str(2**31 + 11), *args], env=env, capture_output=True, text=True,
        timeout=600)


def test_without_a_chip_it_fails_at_once_and_prints_no_result():
    p = run_py("--workload", "tpch_q6_resident", "--seconds", "1")
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 tpu device" in p.stderr


@pytest.mark.parametrize("workload, trace, devices", [
    ("tpch_q6_resident", 0, 1), ("tpch_q6_parquet", 1, 1),
    ("tpch_q3_join_mesh4", 0, 4)])
def test_rehearsal_runs_the_control_flow_and_ends_false(
        workload, trace, devices, pending_bench_dir):
    p = run_py("--workload", workload, "--seconds", "0.5", "--trace",
               str(trace), "--rows", "50000", bench_dir=pending_bench_dir)
    assert p.returncode == 1, p.stderr[-2000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert line["correct"] is False
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"]["answers_right"] is True
    assert line["numCpuFallbacks"] == 0
    assert ("breakdown" in line) is False
    if trace:
        assert "plan_ms" in line["rehearsal"]["would_report"]
    else:
        assert "query_s" in line["rehearsal"]["would_report"]

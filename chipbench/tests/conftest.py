"""The benchmark's own tests run on the CPU backend and never take a chip:
`python -m pytest chipbench/tests -q`."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

import glob  # noqa: E402


@pytest.fixture(scope="session")
def pending_bench_dir(tmp_path_factory):
    """A copy of the benchmark in which the cells that wait under
    `pending/` are listed in `BENCHMARK.json`, as the PR that proves one on
    the chip will list it."""
    root = tmp_path_factory.mktemp("with_pending")
    bench_dir = root / "chipbench"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in glob.glob(os.path.join(BENCH_DIR, "pending", "*.json")):
        with open(path) as f:
            pending = json.load(f)
        for key in ("configs", "workloads", "per_layer"):
            bench[key] += pending[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(bench_dir)

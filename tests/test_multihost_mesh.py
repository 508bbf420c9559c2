"""Two-PROCESS jax.distributed mesh bring-up (VERDICT r3 item 8).

`init_distributed` (parallel/mesh.py) is the multi-host entry: it joins
the jax.distributed coordination service so jax.devices() becomes the
global pod list and the SPMD mesh spans hosts.  This test exercises it
FOR REAL: two local processes on the CPU backend (2 virtual devices
each), a coordinator on a loopback port, a 4-device global mesh, and a
psum collective whose result proves cross-process reduction happened.

Reference analogue: the reference's multi-executor bring-up over
NCCL/UCX bootstrap; here the coordination service + collectives are
jax.distributed over TCP (the DCN path).
"""
import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = str(Path(__file__).resolve().parent.parent)

_WORKER = r"""
import json, os, sys
proc_id, n_proc, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_ENABLE_X64"] = "1"
sys.path.insert(0, %(repo)r)
# workers run on the CPU backend with two virtual devices each
from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
force_cpu_backend(n_devices=2)
from spark_rapids_tpu import config as C
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.parallel.mesh import (DATA_AXIS, init_distributed,
                                            make_mesh)

conf = TpuConf({C.MESH_COORDINATOR.key: coord,
                C.MESH_NUM_PROCESSES.key: str(n_proc),
                C.MESH_PROCESS_ID.key: str(proc_id)})
assert init_distributed(conf), "init_distributed returned False"
# idempotency: a second call with the same coordinator is a no-op
assert init_distributed(conf)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
try:
    from jax import shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map

assert jax.process_count() == n_proc, jax.process_count()
assert jax.local_device_count() == 2
assert jax.device_count() == 2 * n_proc, jax.device_count()

mesh = make_mesh(jax.device_count())
n = jax.device_count() * 4
sharding = NamedSharding(mesh, P(DATA_AXIS))
host = np.arange(n, dtype=np.float64)
arr = jax.make_array_from_callback((n,), sharding, lambda idx: host[idx])

f = jax.jit(shard_map(lambda x: jax.lax.psum(jnp.sum(x), DATA_AXIS),
                      mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P()))
out = f(arr)
total = float(np.asarray(out.addressable_shards[0].data)) \
    if hasattr(out, "addressable_shards") else float(out)
print(json.dumps({"proc": proc_id, "total": total,
                  "devices": jax.device_count(),
                  "processes": jax.process_count()}), flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_mesh_bringup(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER % {"repo": REPO})
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers set their own device count
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), "2", coord],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        # a failed assert/timeout must not orphan the OTHER worker (it
        # would block on the dead coordinator for minutes)
        for q in procs:
            if q.poll() is None:
                q.kill()

    n = 4 * 4  # devices * rows per device
    want = float(sum(range(n)))
    for rec in outs:
        assert rec["devices"] == 4 and rec["processes"] == 2, rec
        assert rec["total"] == want, (rec, want)

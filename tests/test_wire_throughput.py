"""The shuffle wire between two PROCESSES: shuffle/net.py fetches a 128MB
partition (four times the bounce-buffer pool) over every path it has and
every byte has to arrive right (reference: the UCX transport's zero-copy
RDMA path, UCX.scala:54-533 — this is the TCP/DCN stand-in):

  * the same-host shared-memory path and the chunked TCP loopback stream;
  * the stream with reader-side crc32c verification on (ISSUE 4);
  * the verified stream with lz4/zstd/snappy negotiated (ISSUE 5): the
    fetch has to ride compressed and the codec has to engage.

What the wire's MB/s is belongs to a benchmark on a quiet machine, not to
a test under a six-worker suite: this file asserts no rate."""
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

_SERVER = r"""
import sys, struct
import numpy as np
sys.path.insert(0, %(root)r)
from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
force_cpu_backend()
from spark_rapids_tpu.compress import CompressedServeCache, CompressionPolicy
from spark_rapids_tpu.mem.integrity import ChecksumPolicy
from spark_rapids_tpu.shuffle.net import ShuffleSocketServer, SocketTransport

NBYTES = %(nbytes)d
DATA = np.arange(NBYTES, dtype=np.uint8)  # wraps mod 256
POLICY = ChecksumPolicy(True, "crc32c")
DIGEST = POLICY.checksum_one(DATA)
# framed compressed serves, built once per codec and cached (the
# production ShuffleServer path); capacity covers every (bid, codec)
# pair the test touches
CACHE = CompressedServeCache(
    CompressionPolicy("none", chunk_size=1 << 20, min_size=0),
    integrity=POLICY, capacity=64)


class OneBufferServer:
    def handle_metadata_request(self, req):
        raise NotImplementedError

    def buffer_layout(self, bid):
        return [((NBYTES,), "uint8", NBYTES)], {"bid": bid}

    def buffer_checksums(self, bid):
        return (POLICY.algorithm, (DIGEST,))

    def compressed_layout(self, bid, codec):
        entry = CACHE.get(bid, codec, [DATA])
        return entry.descriptor() if entry is not None else None

    def copy_compressed_chunk(self, bid, leaf_idx, off, length, dest,
                              codec):
        entry = CACHE.get(bid, codec, [DATA])
        dest[:length] = entry.leaves[leaf_idx][off:off + length]

    def copy_leaf_chunk(self, bid, leaf_idx, off, length, view):
        view[:length] = memoryview(DATA)[off:off + length]

    def done_serving(self, bid):
        pass


transport = SocketTransport(pool_size=32 << 20, chunk_size=4 << 20,
                            max_inflight_bytes=1 << 40)
server = ShuffleSocketServer(transport, OneBufferServer())
print(f"PORT {server.address[1]}", flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
"""


def test_wire_throughput_two_process():
    nbytes = 128 << 20
    want = np.arange(nbytes, dtype=np.uint8)
    proc = subprocess.Popen(
        [sys.executable, "-u", "-c",
         _SERVER % {"root": str(ROOT), "nbytes": nbytes}],
        stdout=subprocess.PIPE, stdin=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT "), line
        port = int(line.split()[1])

        from spark_rapids_tpu.shuffle.net import SocketTransport
        transport = SocketTransport(pool_size=32 << 20,
                                    chunk_size=4 << 20,
                                    max_inflight_bytes=1 << 40)
        client = transport.make_client_addr(("127.0.0.1", port)) \
            if hasattr(transport, "make_client_addr") else None
        if client is None:
            transport.set_peers({"peer": ("127.0.0.1", port)})
            client = transport.make_client("peer")

        bid_counter = [1]

        def fetch(what):
            bid = bid_counter[0]
            bid_counter[0] += 1
            got, _ = client.fetch_buffer(bid)
            assert got[0].nbytes == nbytes, what
            assert np.array_equal(np.asarray(got[0]).view(np.uint8), want), \
                f"{what}: the bytes that arrived are not the bytes served"

        from spark_rapids_tpu.compress import CompressionPolicy
        from spark_rapids_tpu.mem.integrity import ChecksumPolicy
        fetch("default transport")

        transport.integrity = ChecksumPolicy(False, "crc32c")
        transport.shm_local = True                # force the shm path
        fetch("shm")
        transport.shm_local = False               # default: stream path
        fetch("stream")
        # same stream, reader-side crc32c verification on — the
        # AsyncLeafVerifier hashes chunks overlapped with the recv loop
        transport.integrity = ChecksumPolicy(True, "crc32c")
        fetch("verified stream")
        # the verified stream with a negotiated codec
        for codec in ("lz4", "zstd", "snappy"):
            transport.compression = CompressionPolicy(codec, min_size=0)
            before = transport.counters.get("compressed_bytes_received", 0)
            fetch(codec)
            wire_bytes = transport.counters.get(
                "compressed_bytes_received", 0) - before
            assert wire_bytes > 0, f"{codec} fetch never rode compressed"
            ratio = nbytes / wire_bytes
            assert ratio > 1.5, \
                f"{codec} ratio {ratio:.2f} on periodic data — " \
                "compression never engaged"
        transport.compression = CompressionPolicy("none")
        assert transport.counters.get("bytes_received", 0) > 0
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()

"""DCN wire throughput: measure shuffle/net.py between two PROCESSES with
a 128MB partition and record the number (VERDICT r4 item 8; reference:
the UCX transport's zero-copy RDMA path, UCX.scala:54-533 — this is the
TCP/DCN stand-in, so the recorded MB/s is the honest budget a 2-host mesh
shuffle has to live inside).

Also records the per-codec compressed-stream numbers (ISSUE 5): the same
fetch with lz4/zstd/snappy negotiated, reported as EFFECTIVE (uncompressed
payload) MB/s plus the achieved compression ratio — the number that says
whether a codec pays for itself on a given wire.

Writes the measured MB/s as BENCH_WIRE.json into the test's tmp_path: a
test run leaves the checkout clean, and the tracked BENCH_WIRE.json at the
repo root stays the record it was committed as (numbers taken under a
6-worker suite are load-depressed).  Artifact metadata (host_cpus,
available_codecs, single_core) is MEASURED at write time, never
hand-maintained."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

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
DATA = np.arange(NBYTES, dtype=np.uint8)  # wraps mod 256; cheap checksum
POLICY = ChecksumPolicy(True, "crc32c")
DIGEST = POLICY.checksum_one(DATA)
# framed compressed serves, built once per codec and cached (the
# production ShuffleServer path); capacity covers every (bid, codec)
# pair the bench touches
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


def test_wire_throughput_two_process(tmp_path):
    nbytes = 128 << 20
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

        # warmup (connection + first-touch allocations)
        out, meta = client.fetch_buffer(1)
        assert out[0].nbytes == nbytes
        # spot-check content (full compare would time the checker, not
        # the wire)
        assert out[0][12345] == (12345 % 256)

        n_runs = 3
        bid_counter = [2]

        def measure():
            t0 = time.time()
            for _ in range(n_runs):
                bid = bid_counter[0]
                bid_counter[0] += 1
                got, _ = client.fetch_buffer(bid)
                assert got[0].nbytes == nbytes
                assert got[0][777] == (777 % 256)
            return nbytes * n_runs / (time.time() - t0) / 1e6

        from spark_rapids_tpu.compress import (CompressionPolicy,
                                               available_codecs)
        from spark_rapids_tpu.mem.integrity import ChecksumPolicy
        verified = ChecksumPolicy(True, "crc32c")
        unverified = ChecksumPolicy(False, "crc32c")

        transport.integrity = unverified
        transport.shm_local = True                # force the shm path
        shm_mb_s = measure()
        transport.shm_local = False               # default: stream path
        stream_mb_s = measure()
        # integrity tax (ISSUE 4 acceptance): same stream, reader-side
        # crc32c verification on — the AsyncLeafVerifier hashes chunks
        # overlapped with the recv loop
        transport.integrity = verified
        stream_verified_mb_s = measure()
        # per-codec compressed stream (ISSUE 5): the verified stream with
        # a negotiated codec — effective (uncompressed-payload) MB/s and
        # the achieved ratio.  First fetch per buffer id pays the
        # server-side compression; that cost is deliberately inside the
        # measurement (it is what a real serve pays).
        stream_compressed_mb_s = {}
        compression_ratio = {}
        for codec in ("lz4", "zstd", "snappy"):
            transport.compression = CompressionPolicy(codec, min_size=0)
            before = transport.counters.get("compressed_bytes_received", 0)
            stream_compressed_mb_s[codec] = round(measure(), 1)
            wire_bytes = transport.counters.get(
                "compressed_bytes_received", 0) - before
            assert wire_bytes > 0, f"{codec} fetch never rode compressed"
            compression_ratio[codec] = round(
                nbytes * n_runs / wire_bytes, 2)
        transport.compression = CompressionPolicy("none")

        overhead_pct = (stream_mb_s - stream_verified_mb_s) \
            / stream_mb_s * 100 if stream_mb_s > 0 else 0.0
        host_cpus = os.cpu_count() or 1
        single_core = host_cpus <= 1
        result = {"metric": "shuffle_wire_fetch_throughput",
                  "value": round(shm_mb_s, 1), "unit": "MB/s",
                  "stream_mb_s": round(stream_mb_s, 1),
                  "stream_verified_mb_s": round(stream_verified_mb_s, 1),
                  "stream_compressed_mb_s": stream_compressed_mb_s,
                  "compression_ratio": compression_ratio,
                  "checksum_overhead_pct": round(overhead_pct, 2),
                  "checksum_algorithm": verified.algorithm,
                  # measured at artifact-write time (never hand-edited):
                  # the single_core label derives from host_cpus, and
                  # available_codecs is what THIS host could negotiate
                  "host_cpus": host_cpus,
                  "single_core": single_core,
                  "available_codecs": available_codecs(),
                  "nbytes": nbytes, "runs": n_runs,
                  "chunk_size": 4 << 20,
                  "note": "two-process 128MB partition fetch; value = "
                          "same-host shared-memory path, stream_mb_s = "
                          "TCP loopback chunked path (UCX.scala:54-533 "
                          "stand-in); stream_verified adds reader-side "
                          "crc32c (overlapped with recv when >1 core); "
                          "stream_compressed_mb_s = verified stream with "
                          "a negotiated codec, EFFECTIVE uncompressed "
                          "MB/s (server-side compression cost included)"}
        with open(tmp_path / "BENCH_WIRE.json", "w") as f:
            json.dump(result, f, indent=1)
        assert transport.counters.get("bytes_received", 0) > 0
        # floors far below expectation; the artifact records the real
        # numbers (shm should be multi-GB/s, stream several-hundred MB/s)
        assert stream_mb_s > 100, f"stream collapsed: {stream_mb_s:.0f}"
        assert shm_mb_s > 100, f"shm collapsed: {shm_mb_s:.0f}"
        assert stream_verified_mb_s > 100, \
            f"verified stream collapsed: {stream_verified_mb_s:.0f}"
        for codec, mbs in stream_compressed_mb_s.items():
            # effective floor: codec overhead can cost wall clock on a
            # loopback wire (the ratio is what it buys on a REAL wire),
            # but a collapse below this means the pipeline serialized
            assert mbs > 30, f"{codec} stream collapsed: {mbs:.0f}"
            assert compression_ratio[codec] > 1.5, \
                f"{codec} ratio {compression_ratio[codec]} on periodic " \
                "data — compression never engaged"
        # acceptance: <=5% with crc32c when the verifier thread has a
        # core to hide on; a single-core host cannot overlap the hash
        # with the wire, so the floor there is ~wire_rate/hash_rate
        # (~10% at 1 GB/s vs 10 GB/s crc32c) plus measurement noise
        bound = 30.0 if single_core else 5.0
        assert overhead_pct <= bound, \
            f"checksum overhead {overhead_pct:.1f}% exceeds {bound}%"
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()

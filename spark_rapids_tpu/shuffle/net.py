"""Cross-process shuffle transport over TCP sockets (the DCN wire).

TPU-native analogue of the reference's UCX network stack
(shuffle-plugin/.../ucx/UCX.scala:54-533 — endpoint bring-up + tagged
sends over a management-port handshake; UCXShuffleTransport.scala:47-507 —
client/server factory, bounce-buffer pools, inflight throttle).  On TPU
pods the *intra-query* exchange rides ICI collectives inside the mesh
program (shuffle/ici.py); this socket transport is the host-side DCN path
between executor PROCESSES — the role UCX-over-IB plays for the reference —
so shuffle bytes genuinely cross a process/host boundary.

Wire protocol: length-prefixed frames `u32 length | u8 opcode | payload`.
Control payloads (metadata request/response, buffer layouts) are pickled
dataclasses — this is a Python-to-Python control plane, the analogue of
the reference's flatbuffers messages (shuffle-plugin/.../fbs).  Data moves
as raw frames in bounce-buffer-sized chunks: the serving side stages every
chunk through its BounceBufferPool slice before the socket send, and the
receiving side caps concurrent fetch bytes with the InflightThrottle, so
both ends keep the reference's flow-control structure on a real wire.

The same port also carries a tiny RPC opcode used by the worker control
plane (shuffle/worker.py) — the analogue of UCX's management port.
"""
from __future__ import annotations

import logging
import os
import pickle
import random
import socket
import struct
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..compress import CompressionPolicy, resolve_codec
from ..mem.integrity import (BufferGone, CorruptBuffer, CorruptShuffleBlock)
from ..utils import faults
from .transport import (AsyncFramedReader, AsyncLeafVerifier,
                        BounceBufferPool, ChecksumPolicy,
                        InflightThrottle, MetadataRequest, MetadataResponse,
                        ShuffleTransport, ShuffleTransportClient, Transaction,
                        TransactionCancelled, TransactionStatus,
                        decode_compressed_leaves, verify_fetched_leaf)

log = logging.getLogger("spark_rapids_tpu.shuffle")

# opcodes
OP_META, OP_META_RESP = 1, 2
OP_LAYOUT, OP_LAYOUT_RESP = 3, 4
OP_FETCH, OP_DATA, OP_END = 5, 6, 7
OP_DONE, OP_ACK = 8, 9
OP_FETCH_SHM = 10
# typed "this buffer no longer exists / cannot be served" frame: legal at
# any point a serving opcode's response or stream is expected, so a fetch
# racing remove_shuffle gets a clean error instead of a hang or a
# poisoned half-frame (payload: pickled {"reason": "gone"|"corrupt",
# "msg": str})
OP_GONE = 11
# writer-side corruption diagnosis (SPARK-36206): re-hash the live buffer
# against its recorded digests
OP_DIAG, OP_DIAG_RESP = 12, 13
# same-host segment path prefix; the server refuses to open anything else
SHM_PREFIX = "/dev/shm/srtpu_shm_"
OP_RPC, OP_RPC_RESP, OP_RPC_ERR = 20, 21, 22

_HDR = struct.Struct(">IB")


def send_frame(sock: socket.socket, op: int, payload) -> None:
    """payload: bytes-like (memoryview over a bounce slice for data)."""
    sock.sendall(_HDR.pack(len(payload), op))
    if len(payload):
        sock.sendall(payload)


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed mid-frame")
        got += r
    return buf


def recv_frame(sock: socket.socket) -> Tuple[int, bytes]:
    hdr = _recv_exact(sock, _HDR.size)
    length, op = _HDR.unpack(hdr)
    payload = _recv_exact(sock, length) if length else b""
    return op, bytes(payload)


def recv_frame_into(sock: socket.socket, dest: np.ndarray, offset: int
                    ) -> Tuple[int, int, Optional[bytes]]:
    """Receive one frame; DATA payload lands directly in dest[offset:].
    Returns (opcode, payload_length, payload) — payload is None for DATA
    frames (it went into dest) and the raw bytes otherwise (an OP_GONE
    mid-stream carries its typed reason there)."""
    hdr = _recv_exact(sock, _HDR.size)
    length, op = _HDR.unpack(hdr)
    if op != OP_DATA:
        payload = bytes(_recv_exact(sock, length)) if length else b""
        return op, len(payload), payload
    view = memoryview(dest)[offset:offset + length]
    got = 0
    while got < length:
        r = sock.recv_into(view[got:], length - got)
        if r == 0:
            raise ConnectionError("peer closed mid-data")
        got += r
    return op, length, None


def _unpack_fetch(payload: bytes
                  ) -> Tuple[int, Optional[str], Optional[tuple]]:
    """OP_LAYOUT/OP_FETCH/OP_DIAG payload: a bare big-endian u64 buffer
    id (the raw wire format, and what pre-compression peers send), a
    pickled (buffer_id, codec_name) pair (pre-trace peers), or a pickled
    (buffer_id, codec_name, trace) triple carrying the requesting task's
    distributed-trace context — parsed back-compat like PR 5's codec
    field."""
    if len(payload) == 8:
        return struct.unpack(">Q", payload)[0], None, None
    rec = pickle.loads(payload)
    bid, codec = rec[0], rec[1]
    trace = rec[2] if len(rec) > 2 else None
    return int(bid), codec, trace


def _pack_fetch(buffer_id: int, codec: Optional[str],
                trace: Optional[tuple] = None) -> bytes:
    if codec in (None, "none") and trace is None:
        return struct.pack(">Q", buffer_id)
    if trace is None:
        return pickle.dumps((buffer_id, codec))
    return pickle.dumps((buffer_id, codec, tuple(trace)))


def _raise_gone(payload: bytes, buffer_id: int) -> None:
    """Decode an OP_GONE frame into its typed error."""
    try:
        rec = pickle.loads(payload) if payload else {}
    except Exception:  # noqa: BLE001 — a garbled reason is still "gone"
        rec = {}
    reason = rec.get("reason", "gone")
    msg = rec.get("msg", f"buffer {buffer_id} gone at the peer")
    if reason == "corrupt":
        # the PEER found its own stored copy failing verification while
        # serving: writer-site corruption, refetching cannot help
        raise CorruptShuffleBlock(msg, buffer_id=buffer_id, site="writer")
    raise BufferGone(msg)


class ShuffleSocketServer:
    """Serves one executor's shuffle buffers on a TCP port.

    Each accepted connection gets a handler thread (the reference's UCX
    progress thread pool; RapidsShuffleServer.scala:67-150).  Data chunks
    are staged through the transport's BounceBufferPool before each send,
    so serving a spilled buffer never inflates memory beyond the pool."""

    def __init__(self, transport: "SocketTransport", server_obj,
                 rpc_handler: Optional[Callable] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.transport = transport
        self.server_obj = server_obj
        self.rpc_handler = rpc_handler
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(16)
        self.address = self._listener.getsockname()
        self._closing = False
        self._threads: List[threading.Thread] = []
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="shuffle-accept")
        t.start()
        self._threads.append(t)

    def _accept_loop(self) -> None:
        consecutive_errors = 0
        while not self._closing:
            try:
                conn, _ = self._listener.accept()
            except OSError as e:
                if self._closing:
                    return
                # transient accept failures (ECONNABORTED from a client
                # abort, EMFILE during an fd burst) must not kill the
                # server while the executor lives on looking healthy —
                # count, log, and keep accepting; only a persistently
                # broken listener stops the loop
                self.transport.count("accept_errors")
                consecutive_errors += 1
                # generous tolerance: reconnect-per-retry clients churn
                # connections during fault storms, and an fd burst
                # (EMFILE) can persist for seconds — an executor that
                # stops accepting while "looking healthy" costs every
                # peer ioTimeout * maxAttempts per fetch until restart
                if consecutive_errors > 20 or self._listener.fileno() < 0:
                    log.error("shuffle server %s stopping after repeated "
                              "accept failures: %r", self.address, e)
                    return
                log.warning("shuffle server %s accept failed "
                            "(%d consecutive): %r", self.address,
                            consecutive_errors, e)
                time.sleep(min(1.0, 0.05 * consecutive_errors))
                continue
            consecutive_errors = 0
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True, name="shuffle-serve")
            t.start()
            # prune finished handlers: reconnect-per-retry clients churn
            # connections, and retaining every dead Thread forever is an
            # unbounded leak in exactly the fault-heavy regime
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _serve(self, conn: socket.socket) -> None:
        try:
            peer = conn.getpeername()
        except OSError:
            peer = "<unknown>"
        try:
            while True:
                op, payload = recv_frame(conn)
                if op == OP_META:
                    req: MetadataRequest = pickle.loads(payload)
                    resp = self.server_obj.handle_metadata_request(req)
                    # advertise trace capability: the client stamps trace
                    # context on later per-buffer ops only after seeing
                    # this (back-compat with pre-trace peers both ways)
                    resp.traced = True
                    self.transport.count("metadata_served")
                    self._journal_serve("serveMetadata",
                                        getattr(req, "trace", None),
                                        shuffle=req.shuffle_id,
                                        reduce=req.reduce_id)
                    send_frame(conn, OP_META_RESP, pickle.dumps(resp))
                elif op == OP_LAYOUT:
                    bid, codec, _trace = _unpack_fetch(payload)
                    try:
                        layout, meta = self.server_obj.buffer_layout(bid)
                        sums = self._checksums_of(bid)
                        comp = self._compressed_of(bid, codec)
                    except (KeyError, CorruptBuffer) as e:
                        self._send_gone(conn, bid, e)
                        continue
                    send_frame(conn, OP_LAYOUT_RESP,
                               pickle.dumps((layout, meta, sums, comp)))
                elif op == OP_FETCH:
                    bid, codec, trace = _unpack_fetch(payload)
                    self._stream_buffer(conn, bid, codec, trace)
                elif op == OP_FETCH_SHM:
                    rec = pickle.loads(payload)
                    bid, shm_name = rec[0], rec[1]
                    codec = rec[2] if len(rec) > 2 else None
                    trace = rec[3] if len(rec) > 3 else None
                    self._fill_shm(conn, bid, shm_name, codec, trace)
                elif op == OP_DIAG:
                    bid, _codec, trace = _unpack_fetch(payload)
                    self._journal_serve("serveDiagnosis", trace,
                                        buffer=bid)
                    self._handle_diag(conn, bid)
                elif op == OP_DONE:
                    (bid,) = struct.unpack(">Q", payload)
                    self.server_obj.done_serving(bid)
                    send_frame(conn, OP_ACK, b"")
                elif op == OP_RPC:
                    self._handle_rpc(conn, payload)
                else:
                    raise ValueError(f"bad opcode {op}")
        except (ConnectionError, OSError) as e:
            # peer went away; its requests die with the connection — but
            # the event is counted and logged with the peer address, not
            # silently dropped (a flapping peer shows up in the counters)
            self.transport.count("peer_disconnects")
            if not self._closing:
                log.info("shuffle peer %s disconnected: %r", peer, e)
        finally:
            try:
                conn.close()
            except OSError as e:
                log.debug("closing connection from %s: %r", peer, e)

    def _server_executor(self) -> str:
        env = getattr(self.server_obj, "env", None)
        return getattr(env, "executor_id", "?")

    def _journal_serve(self, name: str, trace, **attrs) -> None:
        """Instant serve record carrying the REQUESTER's wire trace
        context (o_q/o_st/o_sp/o_ex) — the mapper-side half of the
        fetch<->serve flow link (metrics/timeline.py)."""
        from ..metrics.journal import journal_event, trace_attrs
        journal_event("serve", name, executor=self._server_executor(),
                      **{k: v for k, v in attrs.items() if v is not None},
                      **trace_attrs(trace))

    def _checksums_of(self, bid: int):
        """The server's recorded (algorithm, per-leaf digests) for a
        buffer, or None for servers without integrity support (the wire
        benchmark's bare fixture)."""
        get = getattr(self.server_obj, "buffer_checksums", None)
        return get(bid) if get is not None else None

    def _compressed_of(self, bid: int, codec: Optional[str]):
        """Negotiation answer: the framed-compression descriptor
        ({codec, sizes, checksums, algorithm}) when the reader asked for
        a codec this server can encode, else None — the reader falls
        back to the raw wire format and counts the miss."""
        if codec in (None, "none"):
            return None
        get = getattr(self.server_obj, "compressed_layout", None)
        # the fallback is counted by the CLIENT (the side whose request
        # went unmet, matching the counter's documented semantics) —
        # counting here too would double cluster-wide rollups
        return get(bid, codec) if get is not None else None

    def _send_gone(self, conn: socket.socket, bid: int,
                   err: Exception) -> None:
        """Typed buffer-gone/corrupt frame for a serve that raced
        remove_shuffle (or found its own copy corrupt at serve time)."""
        reason = "corrupt" if isinstance(err, CorruptBuffer) else "gone"
        self.transport.count("buffer_gone")
        log.info("shuffle buffer %d unservable (%s): %r", bid, reason, err)
        send_frame(conn, OP_GONE,
                   pickle.dumps({"reason": reason,
                                 "msg": f"buffer {bid}: {err}"}))

    def _handle_diag(self, conn: socket.socket, bid: int) -> None:
        diag = getattr(self.server_obj, "diagnose_buffer", None)
        try:
            result = diag(bid) if diag is not None else None
        except KeyError:
            result = None
        except CorruptBuffer:
            # re-hashing tripped the serve-time verify: conclusive
            # writer-side evidence (and the connection must survive to
            # carry the verdict — a crashed handler would misclassify
            # this as a wire fault after client timeouts)
            result = {"writer_ok": False}
        self.transport.count("corruption_diagnoses")
        send_frame(conn, OP_DIAG_RESP, pickle.dumps(result))

    def _stream_buffer(self, conn: socket.socket, bid: int,
                       codec: Optional[str] = None,
                       trace: Optional[tuple] = None) -> None:
        """Send every leaf of a buffer as bounce-buffer-sized DATA frames,
        in leaf order, then END (BufferSendState: acquire buffer from any
        tier -> stage through send bounce buffers -> tagged sends).  With
        a negotiated codec the staged chunks come out of each leaf's
        FRAMED COMPRESSED form (built once per buffer+codec, served for
        every chunk and refetch) — the layout response already told the
        reader the framed sizes and frame digests.

        A KeyError from the server object mid-stream (the buffer's shuffle
        was removed while we were serving it) becomes a typed OP_GONE
        frame — the client sees a clean `BufferGone` instead of a
        half-frame crash or a hang."""
        from ..metrics.journal import journal_span, trace_attrs
        try:
            layout, _meta = self.server_obj.buffer_layout(bid)
            comp = self._compressed_of(bid, codec)
        except (KeyError, CorruptBuffer) as e:
            self._send_gone(conn, bid, e)
            return
        with journal_span("serve", "serveBuffer",
                          executor=self._server_executor(), buffer=bid,
                          **trace_attrs(trace)):
            self._stream_buffer_body(conn, bid, layout, comp)

    def _stream_buffer_body(self, conn, bid, layout, comp) -> None:
        if comp is not None:
            wire_sizes = comp["sizes"]

            def copy_chunk(leaf_idx, off, length, view):
                self.server_obj.copy_compressed_chunk(
                    bid, leaf_idx, off, length, view, comp["codec"])
        else:
            wire_sizes = [nbytes for _shape, _dtype, nbytes in layout]

            def copy_chunk(leaf_idx, off, length, view):
                self.server_obj.copy_leaf_chunk(bid, leaf_idx, off,
                                                length, view)
        pool = self.transport.pool
        chunk = self.transport.chunk_size
        sent = 0
        for leaf_idx, nbytes in enumerate(wire_sizes):
            off = 0
            while off < nbytes:
                length = min(chunk, nbytes - off)
                addr = pool.acquire(length)
                try:
                    view = pool.view(addr, length)
                    try:
                        copy_chunk(leaf_idx, off, length, view)
                    except (KeyError, CorruptBuffer) as e:
                        self._send_gone(conn, bid, e)
                        return
                    # corruption injection point: the staged chunk IS the
                    # wire payload (anything flipped here crosses the
                    # socket and must be caught by the reader's verify —
                    # with compression on, a flipped COMPRESSED byte must
                    # fail the frame digest before any decompressor)
                    faults.INJECTOR.on_corruptible("wire", view[:length])
                    send_frame(conn, OP_DATA, memoryview(view))
                finally:
                    pool.release(addr)
                off += length
                sent += length
                self.transport.count("bytes_sent", length)
        if comp is not None:
            self.transport.count("compressed_bytes_sent", sent)
        send_frame(conn, OP_END, b"")

    def _fill_shm(self, conn: socket.socket, bid: int,
                  shm_path: str, codec: Optional[str] = None,
                  trace: Optional[tuple] = None) -> None:
        """Same-host fast path: copy each leaf ONCE into the client-owned
        /dev/shm segment instead of chunking through bounce buffers and
        the socket (the local-peer analogue of the reference's UCX
        zero-copy RDMA).  The socket carries only the END ack.  A plain
        tmpfs file + mmap, NOT multiprocessing.shared_memory — the stdlib
        resource tracker logs a KeyError per cross-process segment on
        this python version."""
        import mmap

        from ..metrics.journal import journal_span, trace_attrs
        if not shm_path.startswith(SHM_PREFIX):
            send_frame(conn, OP_RPC_ERR,
                       pickle.dumps(f"bad shm path {shm_path!r}"))
            return
        try:
            fd = os.open(shm_path, os.O_RDWR)
            try:
                mm = mmap.mmap(fd, 0)
            finally:
                os.close(fd)
        except OSError as e:
            send_frame(conn, OP_RPC_ERR, pickle.dumps(f"shm open: {e!r}"))
            return
        try:
            try:
                layout, _meta = self.server_obj.buffer_layout(bid)
                comp = self._compressed_of(bid, codec)
            except (KeyError, CorruptBuffer) as e:
                self._send_gone(conn, bid, e)
                return
            if comp is not None:
                wire_sizes = comp["sizes"]

                def copy_leaf(leaf_idx, nbytes, view):
                    self.server_obj.copy_compressed_chunk(
                        bid, leaf_idx, 0, nbytes, view, comp["codec"])
            else:
                wire_sizes = [nb for _shape, _dtype, nb in layout]

                def copy_leaf(leaf_idx, nbytes, view):
                    self.server_obj.copy_leaf_chunk(bid, leaf_idx, 0,
                                                    nbytes, view)
            off = 0
            with journal_span("serve", "serveBuffer",
                              executor=self._server_executor(),
                              buffer=bid, path="shm",
                              **trace_attrs(trace)):
                for leaf_idx, nbytes in enumerate(wire_sizes):
                    view = np.frombuffer(mm, np.uint8, count=nbytes,
                                         offset=off)
                    try:
                        try:
                            copy_leaf(leaf_idx, nbytes, view)
                        except (KeyError, CorruptBuffer) as e:
                            self._send_gone(conn, bid, e)
                            return
                        # corruption injection point for the shared-memory
                        # leaf fill (the same-host zero-copy "wire")
                        faults.INJECTOR.on_corruptible("shm", view)
                    finally:
                        # the view exports the mmap; it must die before
                        # mm.close() (BufferError otherwise)
                        del view
                    off += nbytes
            self.transport.count("bytes_sent", off)
            if comp is not None:
                self.transport.count("compressed_bytes_sent", off)
            self.transport.count("shm_fills")
            send_frame(conn, OP_END, b"")
        finally:
            mm.close()

    def _handle_rpc(self, conn: socket.socket, payload: bytes) -> None:
        if self.rpc_handler is None:
            send_frame(conn, OP_RPC_ERR,
                       pickle.dumps("no rpc handler registered"))
            return
        try:
            method, kwargs = pickle.loads(payload)
            result = self.rpc_handler(method, kwargs)
            send_frame(conn, OP_RPC_RESP, pickle.dumps(result))
        except Exception as e:  # noqa: BLE001 — crosses the wire
            import traceback
            # counted and logged server-side too: the client may be gone
            # by the time the error frame would reach it
            self.transport.count("rpc_errors")
            log.warning("shuffle rpc failed server-side: %r", e)
            send_frame(conn, OP_RPC_ERR,
                       pickle.dumps(f"{e!r}\n{traceback.format_exc()}"))

    def close(self) -> None:
        self._closing = True
        # shutdown() BEFORE close(): on Linux, close() does not wake a
        # thread blocked in accept() — the kernel keeps the listening
        # socket alive for the in-flight syscall and KEEPS ACCEPTING,
        # so a "closed" server would silently serve forever.  shutdown
        # forces the blocked accept to return.
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # not connected / already gone — nothing to wake  # tpulint: disable=TPU006 shutdown of an unconnected listener is the idle-server close path, not a failure
        try:
            self._listener.close()
        except OSError as e:
            log.debug("closing shuffle listener %s: %r", self.address, e)


class SocketClient(ShuffleTransportClient):
    """Fetch path to one remote executor over its TCP port.  One socket,
    requests serialized under a lock (the reference serializes per-endpoint
    through UCX's tag space).

    Robustness contract (reference: UCX endpoint error handler + the
    RapidsShuffleClient retry/reissue path):

      * every DATA-plane operation (metadata, layout, fetch, done) runs
        under a per-op I/O deadline (`spark.rapids.shuffle.ioTimeoutMs`),
        so a dead peer surfaces as a timeout instead of a hang;
      * failed operations reconnect and retry with exponential backoff +
        deterministic jitter, up to `spark.rapids.shuffle.retry.maxAttempts`
        (requests restart from scratch on a FRESH socket — a half-read
        frame poisons the stream);
      * a whole fetch runs as a Transaction with an overall deadline
        (`transactionTimeoutMs`); past it the transaction is CANCELLED and
        no further retries are attempted;
      * control-plane RPCs are exempt from the I/O deadline: task dispatch
        legitimately blocks on the peer's first-query compilation.
    """

    def __init__(self, transport: "SocketTransport",
                 addr: Tuple[str, int], inject_faults: bool = True,
                 connect_timeout: Optional[float] = None):
        self.transport = transport
        self.addr = tuple(addr)
        # inject_faults=False exempts this client from the deterministic
        # net-fault injector: background pollers (the heartbeat monitor)
        # must not consume test-armed ordinals out from under the
        # data-plane ops the test aimed them at
        self.inject_faults = inject_faults
        # per-client connect bound override: liveness pollers cannot
        # afford the transport's data-plane default (30s) — one
        # blackholed worker would starve every other worker's heartbeat
        self.connect_timeout = connect_timeout
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()
        # trace capability of the peer, learned from the metadata
        # handshake (MetadataResponse.traced): until a trace-aware server
        # confirms, per-buffer ops ride the pre-trace wire shapes — a
        # pre-trace peer cannot parse the pickled trace triple
        self._peer_traced = False
        # deterministic jitter: seeded per peer address, not wall clock
        self._rng = random.Random(f"shuffle-retry:{self.addr}")

    def _conn_locked(self) -> socket.socket:
        if self._sock is None:
            t = self.transport
            s = socket.create_connection(
                self.addr,
                timeout=(self.connect_timeout
                         if self.connect_timeout is not None
                         else t.connect_timeout))
            # the connect bound above is per-attempt; steady-state requests
            # run under the (configurable) I/O deadline so a peer that dies
            # mid-request raises instead of blocking forever
            s.settimeout(t.io_timeout if t.io_timeout > 0 else None)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._sock = s
        return self._sock

    def _drop_socket_locked(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError as e:
                log.debug("closing shuffle socket to %s: %r", self.addr, e)
            self._sock = None

    def _backoff(self, attempt: int) -> float:
        t = self.transport
        raw = min(t.backoff_cap, t.backoff_base * (2 ** attempt))
        return raw * (0.5 + self._rng.random() / 2)  # jittered

    def _retrying(self, label: str, body, deadline: Optional[float] = None,
                  txn: Optional[Transaction] = None):
        """Run `body(sock)` with reconnect-and-retry.  Takes self._lock
        per ATTEMPT and sleeps the backoff unlocked, so a concurrent
        control-plane rpc() or close() to the same peer fails/finishes
        fast instead of stalling behind the backoff series.  `deadline`
        (monotonic) bounds the WHOLE operation including retries;
        crossing it cancels the transaction."""
        attempts = max(1, self.transport.max_attempts)
        last: Optional[BaseException] = None
        for attempt in range(attempts):
            if deadline is not None and time.monotonic() > deadline:
                with self._lock:
                    self._drop_socket_locked()
                raise (txn.cancel(f"{label} to {self.addr} exceeded "
                                  "the transaction deadline") if txn
                       else TransactionCancelled(
                           f"{label} to {self.addr} exceeded deadline"))
            try:
                with self._lock:
                    if self.inject_faults:
                        faults.INJECTOR.on_net_op(label)
                    return body(self._conn_locked())
            except TransactionCancelled:
                with self._lock:
                    self._drop_socket_locked()  # the stream is poisoned mid-frame
                raise
            except (TimeoutError, ConnectionError, OSError) as e:
                # socket.timeout is a TimeoutError (itself an OSError);
                # injected faults are ConnectionErrors.  All of them tear
                # the socket down so the next attempt starts clean.
                with self._lock:
                    self._drop_socket_locked()
                last = e
                self.transport.count("net_op_failures")
                log.warning("shuffle %s to %s failed "
                            "(attempt %d/%d): %r", label, self.addr,
                            attempt + 1, attempts, e)
                if attempt + 1 >= attempts:
                    break
                self.transport.count("net_op_retries")
                time.sleep(self._backoff(attempt))
        if txn is not None:
            txn.fail(repr(last))
        raise ConnectionError(
            f"shuffle {label} to {self.addr} failed after "
            f"{attempts} attempts: {last!r}") from last

    def _request_locked(self, op: int, payload, expect: int,
                 buffer_id: int = -1) -> bytes:
        sock = self._conn_locked()
        send_frame(sock, op, payload)
        got, resp = recv_frame(sock)
        if got == OP_RPC_ERR:
            raise RuntimeError(f"remote error: {pickle.loads(resp)}")
        if got == OP_GONE:
            _raise_gone(resp, buffer_id)
        if got != expect:
            raise ConnectionError(f"expected opcode {expect}, got {got}")
        return resp

    def fetch_metadata(self, request: MetadataRequest) -> MetadataResponse:
        if getattr(request, "trace", None) is None \
                and getattr(self.transport, "trace_enabled", True):
            from ..metrics.journal import current_trace
            request.trace = current_trace()
        blob = pickle.dumps(request)
        resp = self._retrying(
            "metadata", lambda _s: self._request_locked(OP_META, blob,
                                                 OP_META_RESP))
        self.transport.count("metadata_fetched")
        meta = pickle.loads(resp)
        with self._lock:
            self._peer_traced = bool(getattr(meta, "traced", False))
        return meta

    def _wire_trace(self):
        """Trace context to stamp on per-buffer ops: only once the peer
        advertised trace support through the metadata handshake."""
        if not self._peer_traced \
                or not getattr(self.transport, "trace_enabled", True):
            return None
        from ..metrics.journal import current_trace
        return current_trace()

    def _fetch_buffer_shm(self, layout, meta, buffer_id: int, total: int,
                          sums=None, comp=None, comp_sums=None):
        """Local-peer fetch through a client-owned /dev/shm segment: one
        server-side copy per leaf, no socket data frames.  With a
        negotiated codec the segment holds FRAMED COMPRESSED leaves
        (`total` is the framed size); frames verify against their
        compression-boundary digests BEFORE decompression, and the
        decompressed bytes against the canonical digests after.  Returns
        (leaves, meta) or None when shm is unavailable (caller streams)."""
        import mmap
        import tempfile
        try:
            fd, path = tempfile.mkstemp(prefix=os.path.basename(SHM_PREFIX),
                                        dir=os.path.dirname(SHM_PREFIX))
        except OSError as e:
            log.info("shm fetch unavailable (%r); falling back to the "
                     "socket stream", e)
            self.transport.count("shm_unavailable")
            return None
        mm = None
        try:
            os.ftruncate(fd, max(total, 1))
            mm = mmap.mmap(fd, max(total, 1))
            trace = self._wire_trace()
            try:
                with self._lock:
                    faults.INJECTOR.on_net_op("fetch_shm")
                    sock = self._conn_locked()
                    send_frame(sock, OP_FETCH_SHM,
                               pickle.dumps(
                                   (buffer_id, path,
                                    comp["codec"] if comp is not None
                                    else None, trace)
                                   if trace is not None
                                   else ((buffer_id, path, comp["codec"])
                                         if comp is not None
                                         else (buffer_id, path))))
                    op, resp = recv_frame(sock)
            except (TimeoutError, ConnectionError, OSError) as e:
                # single attempt: the caller streams over the socket
                # instead (which carries the full retry machinery)
                log.warning("shm fetch of buffer %d from %s failed: %r",
                            buffer_id, self.addr, e)
                self.transport.count("net_op_failures")
                with self._lock:
                    self._drop_socket_locked()
                return None
            if op == OP_GONE:
                _raise_gone(resp, buffer_id)
            if op != OP_END:
                return None
            wire_sizes = (comp["sizes"] if comp is not None
                          else [nb for _, _, nb in layout])
            # copy out of the segment: a zero-copy variant (arrays
            # viewing the mmap with finalizer-managed lifetime) measured
            # no faster on loopback and leaked one fd per fetch — one
            # bounded memcpy per leaf is the honest cost
            flats: List[np.ndarray] = []
            off = 0
            for leaf_idx, nbytes in enumerate(wire_sizes):
                a = np.empty(nbytes, dtype=np.uint8)
                src = np.frombuffer(mm, np.uint8, count=nbytes,
                                    offset=off)
                try:
                    a[:] = src
                finally:
                    del src  # release the mmap export before mm.close()
                flats.append(a)
                off += nbytes
            self.transport.count("bytes_received", off)
            policy = self.transport.integrity
            out: List[np.ndarray] = []
            if comp is not None:
                # mismatches propagate to fetch_buffer's outer handler
                # (counted + socket dropped there); a corrupt frame
                # never reaches the decompressor
                out = decode_compressed_leaves(
                    flats, layout, resolve_codec(comp["codec"]),
                    comp_sums, sums, policy, self._wire_compression(),
                    buffer_id, "shm")
                self.transport.count("compressed_bytes_received", off)
                cmetrics = self._wire_compression().metrics
                if cmetrics is not None:
                    from ..metrics import names as MN
                    cmetrics.add(MN.COMPRESSED_SHUFFLE_BYTES_READ, off)
                return out, meta
            for leaf_idx, (shape, dtype_str, nbytes) in enumerate(layout):
                a = flats[leaf_idx]
                if sums is not None:
                    # a mismatch propagates to fetch_buffer's outer
                    # handler (counted + socket dropped there)
                    verify_fetched_leaf(policy, a,
                                        sums[leaf_idx], buffer_id,
                                        leaf_idx, "shm")
                out.append(a.view(np.dtype(dtype_str)).reshape(shape))
            return out, meta
        finally:
            if mm is not None:
                mm.close()
            os.close(fd)
            try:
                os.unlink(path)
            except OSError as e:
                log.debug("unlinking shm segment %s: %r", path, e)

    def fetch_buffer(self, buffer_id: int):
        # one fetch == one Transaction: layout + every data frame + END
        # under a single overall deadline, so a peer that dies mid-stream
        # cancels the transaction instead of hanging the reduce task
        txn = self.transport.next_txn()
        deadline = (time.monotonic() + self.transport.txn_timeout
                    if self.transport.txn_timeout > 0 else None)
        cpol = self._wire_compression()
        req_codec = (cpol.codec_name
                     if cpol is not None and cpol.enabled else None)
        # trace context of the requesting task: rides the layout + fetch
        # payloads (once the metadata handshake confirmed the peer parses
        # them) so the peer's serve span links back to our fetch span
        trace = self._wire_trace()
        try:
            resp = self._retrying(
                "layout",
                lambda _s: self._request_locked(OP_LAYOUT,
                                         _pack_fetch(buffer_id, req_codec,
                                                     trace),
                                         OP_LAYOUT_RESP, buffer_id),
                deadline=deadline, txn=txn)
            unpacked = pickle.loads(resp)
            layout, meta = unpacked[0], unpacked[1]
            # pre-integrity peers answer with a 2-tuple — no digests, no
            # verification, same data plane
            rec = unpacked[2] if len(unpacked) > 2 else None
            policy = self.transport.integrity
            sums = None
            if policy is not None and policy.enabled and rec is not None \
                    and rec[0] == policy.algorithm:
                sums = rec[1]
            # codec negotiation outcome: the peer either confirmed our
            # requested codec with framed sizes + frame digests, or it
            # cannot encode it (no compress support / missing library)
            # and we ride the raw wire format — typed fallback, counted
            comp = unpacked[3] if len(unpacked) > 3 else None
            if comp is not None and comp.get("codec") in (None, "none"):
                comp = None
            if req_codec is not None and comp is None:
                self.transport.count("compression_fallbacks")
                if cpol.metrics is not None:
                    from ..metrics import names as MN
                    cpol.metrics.add(MN.NUM_COMPRESSION_FALLBACKS, 1)
            comp_sums = None
            if comp is not None and policy is not None and policy.enabled \
                    and comp.get("checksums") is not None \
                    and comp.get("algorithm") == policy.algorithm:
                comp_sums = comp["checksums"]
            wire_sizes = (comp["sizes"] if comp is not None
                          else [nb for _, _, nb in layout])
            # inflight accounting covers what actually crosses the wire:
            # framed (compressed) bytes when a codec was negotiated
            total = sum(wire_sizes)
            self.transport.throttle.acquire(total)
            try:
                if self.addr[0] in ("127.0.0.1", "localhost", "::1") \
                        and self.transport.shm_local:
                    got = self._fetch_buffer_shm(layout, meta, buffer_id,
                                                 total, sums, comp,
                                                 comp_sums)
                    if got is not None:
                        txn.complete(total)
                        return got

                def stream(sock) -> List[np.ndarray]:
                    send_frame(sock, OP_FETCH,
                               _pack_fetch(buffer_id,
                                           comp["codec"]
                                           if comp is not None else None,
                                           trace))
                    # chunk hashing (and, with a codec, per-leaf verify +
                    # decompress) rides a side thread, overlapped with
                    # the recv loop — verification still completes BEFORE
                    # the bytes become a batch (finish() below), it just
                    # never serializes behind the wire; a corrupt frame
                    # is rejected before any decompressor touches it
                    if comp is not None:
                        sink = AsyncFramedReader(
                            policy, comp_sums, sums,
                            resolve_codec(comp["codec"]), buffer_id,
                            "wire")
                    elif sums is not None:
                        sink = AsyncLeafVerifier(policy, sums, buffer_id,
                                                 "wire")
                    else:
                        sink = None
                    dests: List[np.ndarray] = []
                    try:
                        for leaf_idx, nbytes in enumerate(wire_sizes):
                            dest = np.empty(nbytes, dtype=np.uint8)
                            off = 0
                            while off < nbytes:
                                if deadline is not None \
                                        and time.monotonic() > deadline:
                                    raise txn.cancel(
                                        f"fetch of buffer {buffer_id} "
                                        f"from {self.addr} mid-stream at "
                                        f"{off}/{nbytes}")
                                op, length, payload = recv_frame_into(
                                    sock, dest, off)
                                if op == OP_GONE:
                                    _raise_gone(payload, buffer_id)
                                if op != OP_DATA:
                                    raise ConnectionError(
                                        f"short buffer stream (op {op} "
                                        f"at {off}/{nbytes})")
                                if sink is not None:
                                    sink.feed(leaf_idx,
                                              dest[off:off + length])
                                off += length
                                self.transport.count("bytes_received",
                                                     length)
                            if sink is not None:
                                sink.leaf_done(leaf_idx, dest)
                            dests.append(dest)
                        op, _ = recv_frame(sock)
                        if op != OP_END:
                            raise ConnectionError(
                                f"expected END, got {op}")
                        if comp is not None:
                            flats = sink.finish()  # raises on mismatch
                            sink = None
                            self.transport.count(
                                "compressed_bytes_received", total)
                            if cpol.metrics is not None:
                                from ..metrics import names as MN
                                cpol.metrics.add(
                                    MN.COMPRESSED_SHUFFLE_BYTES_READ,
                                    total)
                            return [flats[i].view(np.dtype(ds))
                                    .reshape(sh)
                                    for i, (sh, ds, _nb)
                                    in enumerate(layout)]
                        if sink is not None:
                            sink.finish()  # raises on mismatch
                            sink = None
                        return [d.view(np.dtype(ds)).reshape(sh)
                                for d, (sh, ds, _nb)
                                in zip(dests, layout)]
                    finally:
                        if sink is not None:
                            sink.abort()

                out = self._retrying("fetch", stream, deadline=deadline,
                                     txn=txn)
                txn.complete(total)
                return out, meta
            finally:
                self.transport.throttle.release(total)
        except CorruptShuffleBlock as e:
            # remaining stream frames are unread: the socket is poisoned
            # for any next request — tear it down before escalating to
            # the refetch/diagnosis ladder (manager._fetch_remote)
            self.transport.count("checksum_mismatches")
            txn.fail(repr(e))
            with self._lock:
                self._drop_socket_locked()
            raise
        except BufferGone as e:
            txn.fail(repr(e))
            raise

    def release_buffer(self, buffer_id: int) -> None:
        # done_serving is idempotent at the server, so the retry is safe
        self._retrying(
            "done", lambda _s: self._request_locked(
                OP_DONE, struct.pack(">Q", buffer_id), OP_ACK))

    def diagnose_buffer(self, buffer_id: int):
        """Writer-side corruption diagnosis (SPARK-36206): the peer
        re-hashes its live copy against its recorded digests.  Returns the
        diagnosis dict or None — never raises; a peer too broken to answer
        is classified by the caller from the absence of evidence."""
        try:
            resp = self._retrying(
                "diag", lambda _s: self._request_locked(
                    OP_DIAG,
                    _pack_fetch(buffer_id, None, self._wire_trace()),
                    OP_DIAG_RESP, buffer_id))
            return pickle.loads(resp)
        except (ConnectionError, OSError, RuntimeError) as e:
            log.warning("corruption diagnosis of buffer %d at %s "
                        "unavailable: %r", buffer_id, self.addr, e)
            return None

    def rpc(self, method: str, _rpc_timeout: Optional[float] = None,
            **kwargs):
        """Control-plane call (worker management; UCX mgmt-port analogue).

        Deliberately NOT retried (run_map/run_reduce are not idempotent)
        and exempt from the data-plane I/O deadline: the first dispatch of
        a plan fragment blocks on the PEER's query compilation, which can
        legitimately exceed any fixed bound.  `_rpc_timeout` opts back
        INTO a deadline for calls that must never hang — the heartbeat
        monitor's liveness polls ride a dedicated client with one."""
        with self._lock:
            if self.inject_faults:
                # method-qualified site so the injectNetFault sweep can
                # aim at ONE control-plane rpc ('rpc:run_reduce@1')
                faults.INJECTOR.on_net_op(f"rpc:{method}")
            try:
                sock = self._conn_locked()
                # compile-friendly: no I/O deadline unless opted in
                sock.settimeout(_rpc_timeout)
                try:
                    send_frame(sock, OP_RPC, pickle.dumps((method, kwargs)))
                    op, resp = recv_frame(sock)
                finally:
                    if self._sock is not None:
                        try:
                            self._sock.settimeout(
                                self.transport.io_timeout
                                if self.transport.io_timeout > 0 else None)
                        except OSError:
                            self._drop_socket_locked()  # broken mid-rpc
            except (TimeoutError, ConnectionError, OSError) as e:
                self._drop_socket_locked()
                self.transport.count("net_op_failures")
                log.warning("shuffle rpc %s to %s failed: %r", method,
                            self.addr, e)
                raise
        if op == OP_RPC_ERR:
            raise RuntimeError(f"worker rpc {method} failed: "
                               f"{pickle.loads(resp)}")
        if op != OP_RPC_RESP:
            raise ConnectionError(f"expected RPC_RESP, got {op}")
        return pickle.loads(resp)

    def close(self) -> None:
        with self._lock:
            self._drop_socket_locked()


class SocketTransport(ShuffleTransport):
    """Client/server factory over TCP (UCXShuffleTransport analogue).

    Peers are discovered through an explicit address map (executor_id ->
    (host, port)) distributed by the cluster driver — the role MapStatus /
    the UCX management handshake plays for the reference."""

    def __init__(self, pool_size: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 max_inflight_bytes: int = 4 << 20,
                 host: str = "127.0.0.1", port: int = 0,
                 rpc_handler: Optional[Callable] = None,
                 shm_local: bool = False,
                 connect_timeout: float = 30.0, io_timeout: float = 60.0,
                 max_attempts: int = 4, backoff_base: float = 0.05,
                 backoff_cap: float = 2.0, txn_timeout: float = 600.0):
        # bounce-pool geometry: ONE source of truth, the conf registry
        # (spark.rapids.shuffle.bounce.poolSizeBytes/chunkSizeBytes);
        # explicit arguments (tests, pinned-pool override) still win
        from .. import config as C
        if pool_size is None:
            pool_size = int(C.SHUFFLE_BOUNCE_POOL_SIZE.default)
        if chunk_size is None:
            chunk_size = int(C.SHUFFLE_BOUNCE_CHUNK_SIZE.default)
        # measured on 128MB partitions on a CPU host: the pipelined
        # chunked stream does ~1.05 GB/s on loopback while the serial
        # fill-then-copy shm path does ~0.7 GB/s — so the stream is the
        # default and shm stays an option for CPU-constrained hosts
        # (2 copies + no socket syscalls vs 3 copies through the kernel)
        self.shm_local = shm_local
        self.pool = BounceBufferPool(pool_size, chunk_size)
        self.chunk_size = chunk_size
        self.throttle = InflightThrottle(max_inflight_bytes)
        self._host, self._port = host, port
        self.rpc_handler = rpc_handler
        # retry/deadline policy (seconds); configure(conf) overrides from
        # the spark.rapids.shuffle.* knobs
        self.connect_timeout = connect_timeout
        self.io_timeout = io_timeout
        self.max_attempts = max_attempts
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.txn_timeout = txn_timeout
        self._peers: Dict[str, Tuple[str, int]] = {}
        self._clients: Dict[str, SocketClient] = {}
        self._server: Optional[ShuffleSocketServer] = None
        self.address: Optional[Tuple[str, int]] = None
        self._lock = threading.Lock()
        self._txn_counter = 0
        self.counters: Dict[str, int] = {}
        # end-to-end wire integrity (mem/integrity.py): the client
        # verifies every received leaf against the digests the layout
        # response carries; configure() adopts the session's conf
        self.integrity = ChecksumPolicy()
        # wire compression (compress/): what this side's fetches request
        # from peers; default none, configure() adopts
        # spark.rapids.shuffle.compression.codec
        self.compression = CompressionPolicy()
        # distributed-trace wire stamping (spark.rapids.sql.tpu.trace.
        # enabled): clients attach the current trace context to fetch
        # requests; configure() adopts the conf
        self.trace_enabled = True

    def configure(self, conf) -> None:
        """Adopt retry/deadline knobs from a TpuConf (and arm the fault
        injector from its test confs)."""
        from .. import config as C
        from ..compress import compression_from_conf
        from ..mem.integrity import policy_from_conf
        faults.INJECTOR.configure_from_conf(conf)
        self.connect_timeout = int(conf.get(C.SHUFFLE_CONNECT_TIMEOUT)) / 1e3
        self.io_timeout = int(conf.get(C.SHUFFLE_IO_TIMEOUT)) / 1e3
        self.max_attempts = int(conf.get(C.SHUFFLE_RETRY_ATTEMPTS))
        self.backoff_base = int(conf.get(C.SHUFFLE_RETRY_BACKOFF_BASE)) / 1e3
        self.backoff_cap = int(conf.get(C.SHUFFLE_RETRY_BACKOFF_CAP)) / 1e3
        self.txn_timeout = int(conf.get(C.SHUFFLE_TXN_TIMEOUT)) / 1e3
        self.integrity = policy_from_conf(conf)
        self.compression = compression_from_conf(
            conf, metrics=self.compression.metrics)
        self.trace_enabled = bool(conf.get(C.TRACE_ENABLED))

    def next_txn(self) -> Transaction:
        with self._lock:
            self._txn_counter += 1
            return Transaction(self._txn_counter,
                               TransactionStatus.IN_PROGRESS)

    def count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def register_server(self, executor_id: str, server) -> None:
        # single-owner wiring: runs once at worker startup, before any
        # serve/fetch thread exists (the server it builds STARTS them)
        # tpulint: disable=TPU009 startup wiring precedes every thread that could race it
        self._server = ShuffleSocketServer(self, server, self.rpc_handler,
                                           self._host, self._port)
        self.address = self._server.address  # tpulint: disable=TPU009 startup wiring precedes every thread that could race it
        self._peers[executor_id] = self.address

    def set_peers(self, peers: Dict[str, Tuple[str, int]],
                  replace: bool = False) -> None:
        """Adopt a peer address map.  `replace=True` additionally PRUNES
        peers absent from the new map (a worker slot the driver shrunk
        away under graceful degradation) — their cached clients close so
        no future fetch dials the dead address.  The transport's OWN
        entry survives a replace: the driver's full map always names
        every live worker including the recipient."""
        stale = []
        with self._lock:
            for k, v in peers.items():
                addr = tuple(v)
                if self._peers.get(k) not in (None, addr):
                    # peer re-addressed (executor-loss replacement): any
                    # cached client holds a socket to the DEAD process
                    stale.append(self._clients.pop(k, None))
                self._peers[k] = addr
            if replace:
                for k in [k for k in self._peers if k not in peers]:
                    del self._peers[k]
                    stale.append(self._clients.pop(k, None))
        for client in stale:
            if client is not None:
                client.close()

    def make_client(self, peer_executor_id: str) -> SocketClient:
        with self._lock:
            client = self._clients.get(peer_executor_id)
            if client is None:
                addr = self._peers.get(peer_executor_id)
                if addr is None:
                    raise KeyError(
                        f"no address for peer {peer_executor_id}; "
                        f"known: {sorted(self._peers)}")
                client = SocketClient(self, addr)
                self._clients[peer_executor_id] = client
            return client

    def drop_client(self, peer_executor_id: str) -> None:
        """Forget a peer's cached client (executor-loss recovery: the
        replacement worker listens on a NEW port; the stale client holds
        a socket to the dead one)."""
        with self._lock:
            client = self._clients.pop(peer_executor_id, None)
        if client is not None:
            client.close()

    def shutdown(self) -> None:
        for c in list(self._clients.values()):
            c.close()
        self._clients.clear()
        if self._server is not None:
            self._server.close()

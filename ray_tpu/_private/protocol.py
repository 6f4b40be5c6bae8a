"""Socket transport: length-prefixed pickled messages with request/reply.

Transport parity note: the reference's control plane is gRPC + asio Unix
sockets (`src/ray/rpc/grpc_server.cc`, `src/ray/common/client_connection.cc`).
Here every process exposes one socket server; peers hold direct persistent
connections (the "direct call" topology of the reference's
`direct_task_transport.h` / `direct_actor_transport.h`). Messages are Python
dicts with a `kind` field, serialized with pickle protocol 5. Requests carry
a `seq`; replies echo it as `reply_to`.

Addressing: a plain filesystem path binds an AF_UNIX socket (intra-node);
`tcp://host:port` binds AF_INET (the inter-node plane, standing in for the
reference's gRPC services — `node_manager.proto:78`, `core_worker.proto:150`).
Both address forms speak the identical framed protocol, so a worker talks to
a same-node peer over Unix sockets and a remote-node peer over TCP with no
code change above this module.

Object-distribution plane messages (runtime.py <-> head.py; parity: the
reference ObjectDirectory's location pub/sub, `object_directory.h`):

- ``object_location_add`` / ``object_location_remove`` — a node
  registers/deregisters a sealed fetched copy with the head directory
  (fire-and-forget; stale entries are tolerated, fetch falls back to
  the owner on a miss).
- ``object_locations`` — request/reply resolving an object's replica
  set, least-loaded first. With the sharded head this is the cache-miss
  path only: clients keep a local directory cache (runtime.py) that the
  pub/sub deltas below maintain, so steady-state routed fetches issue
  zero head RPCs.
- ``head_shard_info`` — request/reply returning the head's shard count
  N; the client subscribes to the ``objloc:<k>`` channel for every
  ``k in [0, N)`` before its first directory RPC.
- ``objloc:<k>`` publishes (head -> subscribed clients) — directory
  deltas for shard k: ``{"op": "add", "object_id", "addr", "node"}``
  on a fresh registration, ``{"op": "remove", "object_id", "addr"}``
  on eviction, and ``{"op": "drop_addr", "addr"}`` when a process
  disconnects (clients scrub every cached entry naming the address).
- ``get_object`` may now carry ``no_redirect`` (force the owner to
  serve) and be answered with ``status="redirect"`` + ``addr``/``node``
  when the owner is at its ``RAY_TPU_MAX_UPLOADS_PER_OBJECT`` fan-out
  cap — the bounded-fan-out tree broadcast.

Every Connection additionally keeps ``bytes_sent`` / ``bytes_recv``
payload totals (per-conn wire accounting; the broadcast tests assert
owner egress against these and the pool-level roll-ups).
"""

from __future__ import annotations

import logging
import os
import pickle
import socket
import struct
import threading
import time
from typing import Callable, Dict, Optional

from . import chaos
from .graftcheck.runtime_trace import make_lock

logger = logging.getLogger(__name__)

_LEN = struct.Struct("<Q")
_U32 = struct.Struct("<I")
PICKLE_PROTOCOL = 5

# Frame-length top bit marks an out-of-band frame: a small pickled
# message followed by one raw payload buffer that is NEVER copied
# through pickle on either side (the data plane's chunk bytes). Layout:
#   u64 (body_len | OOB)  |  u32 meta_len | meta | payload...
_OOB_FLAG = 1 << 63

TCP_PREFIX = "tcp://"

# Optional (begin_fn, finish_fn) installed by the runtime: begin_fn()
# runs before a message is pickled, finish_fn(peer_addr) after — used to
# pin owned ObjectRefs exported in the message to their destination
# until the borrower acknowledges (see runtime._register_export_pins).
_serialize_hooks = None


def set_serialize_hooks(begin_fn: Optional[Callable],
                        finish_fn: Optional[Callable]) -> None:
    global _serialize_hooks
    _serialize_hooks = (begin_fn, finish_fn) if begin_fn else None


def is_tcp(addr: str) -> bool:
    return addr.startswith(TCP_PREFIX)


def parse_tcp(addr: str):
    hostport = addr[len(TCP_PREFIX):]
    host, _, port = hostport.rpartition(":")
    return host or "127.0.0.1", int(port)


def _make_client_socket(addr: str):
    """Returns (unconnected socket, connect target) for `addr`."""
    if is_tcp(addr):
        host, port = parse_tcp(addr)
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock, (host, port)
    return socket.socket(socket.AF_UNIX, socket.SOCK_STREAM), addr


class ConnectionClosed(Exception):
    pass


def _send_msg(sock: socket.socket, payload: bytes) -> None:
    header = _LEN.pack(len(payload))
    if len(payload) >= 1 << 16:
        # Scatter-gather: concatenating the length prefix onto a
        # multi-MB chunk payload costs a full copy per message on the
        # data plane's hot path.
        _sendmsg_all(sock, [header, payload])
    else:
        sock.sendall(header + payload)


def _sendmsg_all(sock: socket.socket, parts) -> None:
    mvs = [memoryview(p).cast("B") for p in parts]
    while mvs:
        sent = sock.sendmsg(mvs)
        while sent > 0 and mvs:
            if sent >= mvs[0].nbytes:
                sent -= mvs[0].nbytes
                mvs.pop(0)
            else:
                mvs[0] = mvs[0][sent:]
                sent = 0


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    # recv_into a single pre-sized buffer: no per-recv allocations and
    # no join copy (pickle.loads accepts the bytearray directly).
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            raise ConnectionClosed()
        got += r
    return buf


def _send_msg_oob(sock: socket.socket, meta: bytes, payload) -> None:
    """One frame: pickled meta + a raw payload buffer, scatter-gathered
    so the payload is handed to the kernel without ever being copied
    into a pickle stream or onto a header."""
    pv = memoryview(payload).cast("B")
    body_len = _U32.size + len(meta) + pv.nbytes
    _sendmsg_all(sock, [_LEN.pack(body_len | _OOB_FLAG),
                        _U32.pack(len(meta)), meta, pv])


def _decode_oob(body: bytearray) -> dict:
    """Inverse of _send_msg_oob: the message dict gets the payload as a
    zero-copy memoryview over the receive buffer under `data`."""
    mv = memoryview(body)
    (meta_len,) = _U32.unpack_from(mv, 0)
    pos = _U32.size + meta_len
    msg = pickle.loads(mv[_U32.size:pos])
    msg["data"] = mv[pos:]
    return msg


def _recv_msg(sock: socket.socket):
    """Returns the frame payload: a bytearray (plain pickled message)
    or an already-decoded dict (out-of-band frame)."""
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n & _OOB_FLAG:
        return _decode_oob(_recv_exact(sock, n & ~_OOB_FLAG))
    return _recv_exact(sock, n)


class _ReplyFuture:
    __slots__ = ("_ev", "_value", "_exc")

    def __init__(self):
        self._ev = threading.Event()
        self._value = None
        self._exc = None

    def set(self, value):
        self._value = value
        self._ev.set()

    def set_exception(self, exc):
        self._exc = exc
        self._ev.set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError("rpc timed out")
        if self._exc is not None:
            raise self._exc
        return self._value


class Connection:
    """A bidirectional message channel to one peer.

    One background thread reads messages; `kind == "reply"` resolves pending
    request futures, everything else is dispatched to `handler(conn, msg)`.
    Handlers must be fast or hand off to their own executor.
    """

    def __init__(self, sock: socket.socket, handler: Callable, peer_addr: str = "",
                 on_close: Optional[Callable] = None):
        self.sock = sock
        self.handler = handler
        self.peer_addr = peer_addr  # advertised server address of the peer
        self.on_close = on_close
        self.closed = False
        # Per-conn payload byte totals (monotonic; read without the
        # send lock — torn reads of a counter are harmless).
        self.bytes_sent = 0
        self.bytes_recv = 0
        self._send_lock = make_lock("Connection._send_lock")
        self._seq = 0
        self._seq_lock = make_lock("Connection._seq_lock")
        self._pending: Dict[int, _ReplyFuture] = {}
        self._thread = threading.Thread(
            target=self._recv_loop, daemon=True, name=f"conn-recv-{peer_addr}")
        self._thread.start()

    # -- sending ---------------------------------------------------------
    def send(self, msg: dict, buffer=None) -> None:
        """Ship one message. `buffer` (bytes-like) rides the frame
        OUT-OF-BAND: it is scatter-gathered straight from the caller's
        memory to the socket and surfaces at the receiver as a
        zero-copy view under `msg["data"]` — the data plane's chunk
        payloads never pass through pickle on either side."""
        hooks = _serialize_hooks
        if hooks is not None:
            hooks[0]()
            try:
                payload = pickle.dumps(msg, protocol=PICKLE_PROTOCOL)
            finally:
                hooks[1](self.peer_addr)
        else:
            payload = pickle.dumps(msg, protocol=PICKLE_PROTOCOL)
        c = chaos.controller
        if c is not None:
            rule = c.fire("wire.send", msg.get("kind", ""))
            if rule is not None and self._chaos_send_fault(
                    rule, payload, buffer):
                return
        try:
            with self._send_lock:
                if buffer is not None:
                    _send_msg_oob(self.sock, payload, buffer)
                    self.bytes_sent += len(payload) \
                        + memoryview(buffer).nbytes
                else:
                    _send_msg(self.sock, payload)
                    self.bytes_sent += len(payload)
        except (OSError, ConnectionClosed) as e:
            self._handle_close()
            raise ConnectionClosed(str(e)) from e

    def _chaos_send_fault(self, rule, payload: bytes, buffer) -> bool:
        """Apply an armed wire.send fault. Returns True when the frame
        was consumed by the fault (caller must NOT send it)."""
        if rule.kind == "delay":
            time.sleep(rule.delay)
            return False
        if rule.kind == "drop":
            # The caller believes the message was delivered — exactly
            # the lost-update shape recovery has to survive.
            return True
        if rule.kind == "dup":
            try:
                with self._send_lock:
                    if buffer is not None:
                        _send_msg_oob(self.sock, payload, buffer)
                    else:
                        _send_msg(self.sock, payload)
            except (OSError, ConnectionClosed):
                pass
            return False  # the normal send follows: duplicated delivery
        if rule.kind == "truncate":
            # Claim the full frame length, ship half the body, then
            # close: the peer's recv loop desyncs mid-frame and must
            # treat the connection as dead, never surface a partial
            # message.
            try:
                with self._send_lock:
                    if buffer is None and len(payload) > 1:
                        self.sock.sendall(
                            _LEN.pack(len(payload))
                            + payload[:len(payload) // 2])
            except OSError:
                pass
            self._handle_close()
            raise ConnectionClosed("chaos: frame truncated mid-send")
        # 'close'
        self._handle_close()
        raise ConnectionClosed("chaos: connection closed by schedule")

    def request(self, msg: dict, timeout: Optional[float] = None):
        """Send a message and block for its reply; returns the reply dict."""
        with self._seq_lock:
            self._seq += 1
            seq = self._seq
        fut = _ReplyFuture()
        self._pending[seq] = fut
        msg = dict(msg)
        msg["seq"] = seq
        try:
            self.send(msg)
            reply = fut.result(timeout)
        finally:
            self._pending.pop(seq, None)
        if reply.get("error") is not None:
            raise reply["error"]
        return reply

    def reply(self, req: dict, **fields) -> None:
        self.send({"kind": "reply", "reply_to": req["seq"], **fields})

    def reply_error(self, req: dict, error: BaseException) -> None:
        self.send({"kind": "reply", "reply_to": req["seq"], "error": error})

    # -- receiving -------------------------------------------------------
    def _recv_loop(self):
        try:
            while True:
                payload = _recv_msg(self.sock)
                if isinstance(payload, dict):
                    msg = payload
                    data = msg.get("data")
                    self.bytes_recv += getattr(data, "nbytes", 0) or 0
                else:
                    msg = pickle.loads(payload)
                    self.bytes_recv += len(payload)
                c = chaos.controller
                if c is not None and msg.get("kind") != "reply":
                    # Replies are exempt: dropping them only converts a
                    # blocked request() into its rpc timeout, which the
                    # wire.send faults already cover from the other end.
                    rule = c.fire("wire.recv", msg.get("kind", ""))
                    if rule is not None:
                        if rule.kind == "drop":
                            continue
                        time.sleep(rule.delay)  # 'delay'
                if msg.get("kind") == "reply":
                    fut = self._pending.get(msg["reply_to"])
                    if fut is not None:
                        fut.set(msg)
                else:
                    try:
                        self.handler(self, msg)
                    except Exception:
                        logger.exception("error handling %s", msg.get("kind"))
        except (ConnectionClosed, OSError, EOFError, pickle.UnpicklingError):
            pass
        finally:
            self._handle_close()

    def _handle_close(self):
        if self.closed:
            return
        self.closed = True
        try:
            # close() alone does NOT unblock another thread sitting in
            # recv() on this socket (the fd stays referenced); shutdown
            # forces the recv loop out so it can be joined.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        for fut in list(self._pending.values()):
            fut.set_exception(ConnectionClosed(f"peer {self.peer_addr} closed"))
        if self.on_close is not None:
            try:
                self.on_close(self)
            except Exception:
                logger.exception("on_close callback failed")

    def close(self):
        self._handle_close()
        # The closed socket unblocks the recv loop immediately; join it
        # so repeated connect/close cycles don't accumulate threads
        # (close() may run ON the recv thread via _handle_close's
        # finally — joining yourself is a no-op guard).
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=1.0)


class Server:
    """Unix-socket accept loop; each accepted socket becomes a Connection.

    The first message on every inbound connection must be
    `{"kind": "hello", "addr": <peer server addr>}` so we can key the
    connection by the peer's advertised address.
    """

    def __init__(self, path: str, handler: Callable,
                 on_connect: Optional[Callable] = None,
                 on_close: Optional[Callable] = None):
        self.path = path
        self.handler = handler
        self.on_connect = on_connect
        self.on_close = on_close
        if is_tcp(path):
            host, port = parse_tcp(path)
            self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind((host, port))
            # Resolve an ephemeral port request (port 0) to the real one.
            self.path = f"{TCP_PREFIX}{host}:{self._sock.getsockname()[1]}"
        else:
            if os.path.exists(path):
                os.unlink(path)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self._sock.bind(path)
        self._sock.listen(256)
        self.connections: Dict[str, Connection] = {}
        # Striped data plane: peers may open EXTRA connections for bulk
        # object transfer (hello carries `transfer: True`). They speak
        # the same framed protocol but are kept out of `connections` —
        # keying them by peer addr would shadow the peer's control
        # connection, and their lifecycle (a pool conn dying is a
        # transfer retry, not a peer death) must not trigger the
        # server's on_close peer-cleanup.
        self.transfer_connections: list = []
        self._lock = make_lock("Server._lock")
        self._stopped = False
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True, name=f"server-{path}")
        self._thread.start()

    def _accept_loop(self):
        while not self._stopped:
            try:
                sock, _ = self._sock.accept()
            except OSError:
                break
            threading.Thread(
                target=self._handshake, args=(sock,), daemon=True).start()

    def _handshake(self, sock: socket.socket):
        if sock.family == socket.AF_INET:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            hello = pickle.loads(_recv_msg(sock))
            assert hello.get("kind") == "hello", hello
            peer_addr = hello.get("addr", "")
        except Exception:
            sock.close()
            return
        if hello.get("transfer"):
            conn = Connection(sock, self.handler, peer_addr,
                              on_close=self._on_transfer_conn_close)
            with self._lock:
                self.transfer_connections.append(conn)
            return
        conn = Connection(sock, self.handler, peer_addr, on_close=self._on_conn_close)
        with self._lock:
            self.connections[peer_addr] = conn
        if self.on_connect is not None:
            self.on_connect(conn, hello)

    def _on_transfer_conn_close(self, conn: Connection):
        with self._lock:
            try:
                self.transfer_connections.remove(conn)
            except ValueError:
                pass

    def _on_conn_close(self, conn: Connection):
        with self._lock:
            if self.connections.get(conn.peer_addr) is conn:
                del self.connections[conn.peer_addr]
        if self.on_close is not None:
            self.on_close(conn)

    def close(self):
        self._stopped = True
        try:
            # shutdown() (not just close) is what actually unblocks the
            # accept loop's blocking accept() on Linux.
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        with self._lock:
            conns = list(self.connections.values()) \
                + list(self.transfer_connections)
        for c in conns:
            c.close()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=1.0)
        if not is_tcp(self.path) and os.path.exists(self.path):
            try:
                os.unlink(self.path)
            except OSError:
                pass


def connect(path: str, my_addr: str, handler: Callable,
            hello_extra: Optional[dict] = None,
            on_close: Optional[Callable] = None,
            timeout: float = 30.0) -> Connection:
    """Dial a peer's server (Unix path or tcp://host:port) and perform
    the hello handshake."""
    sock, target = _make_client_socket(path)
    sock.settimeout(timeout)
    sock.connect(target)
    sock.settimeout(None)
    hello = {"kind": "hello", "addr": my_addr}
    if hello_extra:
        hello.update(hello_extra)
    try:
        _send_msg(sock, pickle.dumps(hello, protocol=PICKLE_PROTOCOL))
    except OSError as e:
        # A dying peer's listen backlog accepts the dial and the hello
        # meets a closed socket: that is a closed connection, which
        # every caller handles, not a raw EPIPE for user code.
        sock.close()
        raise ConnectionClosed(
            f"{path} closed the connection during the hello: {e}") from e
    return Connection(sock, handler, peer_addr=path, on_close=on_close)

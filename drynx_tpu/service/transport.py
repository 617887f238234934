"""TCP control plane: length-prefixed frames between node processes.

The reference's onet overlay (TCP + registered-message marshaling,
services/service.go:117-139, SendProtobuf at api.go:110) maps to two planes
on TPU (SURVEY.md §2.3): the *data plane* (ciphertext math) rides XLA
collectives inside the device mesh, while the *control plane* (query
distribution, DP responses from external institutions, proof envelopes) is
host-side networking — this module.

Two wire formats share one outer framing ([u32 length][body]):

  v1 (JSON)    body is a UTF-8 JSON document; binary tensors travel as
               base64 fields (~33% inflation plus codec cost on multi-MB
               ciphertext payloads).
  v2 (binary)  body is [u32 header_len][header JSON][u32 nsegs]
               [u32 seg_len x nsegs][seg bytes...]; every bytes value in
               the message tree (pack_array data, proof blobs) is pulled
               out into a raw segment and referenced from the header as
               {"__seg__": i}. No base64, no JSON-escaping of payload
               bytes.

v2 decode is *device-direct* by default: narrowed integer segments stay
lazy (:class:`LazySeg`) so device-bound handlers upload the raw wire
view and widen on device (``unpack_array_device``), while host consumers
widen on demand to the exact legacy bytes. ``DRYNX_DEVICE_DECODE=off``
restores the eager host widen.

The format is negotiated per connection: a client opens in v1, sends a
``wire_hello`` (handled inside the server accept loop, invisible to the
fault plan and to handlers), and switches to the agreed version. An old
server answers the hello with an error reply and the connection simply
stays v1. ``DRYNX_WIRE=json`` is the kill-switch that pins everything to
v1. :class:`LinkModel` charges the real frame length either way, so the
wire formats are directly comparable byte-for-byte.

Failure contract: every transport failure raises a subclass of
:class:`TransportError`. The subclasses multiply-inherit the builtin
exception a pre-resilience caller would have caught (``ConnectionError``,
``TimeoutError``, ``RuntimeError``) so existing ``except`` clauses keep
working while new code can catch one hierarchy. A :class:`Conn` whose
frame exchange failed mid-flight is *broken*: the socket is in an
undefined state (a partial frame may be on the wire), so it is closed and
every later call raises immediately — recovery is a NEW connection,
decided by the caller's RetryPolicy (drynx_tpu/resilience/policy.py).
:class:`ConnPool` enforces the same contract across reuse: broken or
closed connections are never pooled, and a pooled socket with pending
bytes (a half-read reply from a timed-out call) is discarded on checkout.

Fault injection: when a :class:`~drynx_tpu.resilience.faults.FaultPlan`
is active (set_fault_plan), the client hooks (connect/request) and server
hooks (node/reply) consult it — see faults.py for the hook taxonomy. With
no plan active every hook is a no-op on the hot path.
"""
from __future__ import annotations

import base64
import json
import os
import socket
import socketserver
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..resilience import faults
from ..resilience import policy as rp

# DRYNX_PROTO_TRACE: record every Conn lifecycle event (checkout, use,
# break, put/discard/close) into the runtime protocol recorder
# (analysis/prototrace.py) so the chaos cross-check can assert the
# observed sequences against the conn-checkout-discipline automaton.
_PROTO_TRACE = os.environ.get("DRYNX_PROTO_TRACE", "0") == "1"


def _proto_record(conn: "Conn", event: str) -> None:
    inst = getattr(conn, "_proto_inst", None)
    if inst:
        from ..analysis import prototrace
        prototrace.record(inst, event)


# ---------------------------------------------------------------------------
# Typed failure hierarchy
# ---------------------------------------------------------------------------

class TransportError(Exception):
    """Base of every control-plane transport failure."""


class ConnectError(TransportError, ConnectionError):
    """TCP connect to a roster entry failed (refused / unreachable)."""


class ConnectionClosed(TransportError, ConnectionError):
    """The peer closed (or reset) the connection mid-exchange."""


class CallTimeout(TransportError, TimeoutError):
    """The socket timed out mid-frame; the connection is now broken."""


class FrameTooLarge(TransportError):
    """A frame header announced more bytes than the configured cap."""


class CorruptFrame(TransportError):
    """A frame's body did not decode under the connection's wire format."""


class RemoteError(TransportError, RuntimeError):
    """The peer's handler raised; its error reply carries the repr."""


class LinkModel:
    """Per-message link emulation + byte accounting.

    Mirrors the reference simulation's per-link network model
    (simul/runfiles/drynx.toml:6-7: Delay = 20 ms, Bandwidth = 100 Mbps;
    sensitivity study TIFS/networkTraffic.py). charge(n) sleeps
    delay + n*8/bandwidth before the bytes move, so TCP runs and the
    in-process simulation runner reproduce the reference's network rows
    with real wall-clock, not post-hoc arithmetic.

    Counters (bytes_total/msgs_total/by_peer) are mutated under a lock —
    fan_out workers charge concurrently — but the emulation sleep happens
    OUTSIDE the lock, so concurrent sends overlap their link time exactly
    like independent physical links would.

    ``rx_by_node`` is the receive-side ledger the tree plane needs: every
    frame is charged once at the SENDER (delay + bandwidth + totals), and
    counted once more — accounting only, no second sleep — against the
    node whose process RECEIVED it (count_rx). bytes-at-root, the number
    the tree topology exists to shrink, is rx_by_node[root] (relay-hop
    traffic lands on the relays instead). Empty until a tree/relay-aware
    caller labels receives, and omitted from stats() while empty so
    pre-tree consumers see the exact legacy shape.
    """

    def __init__(self, delay_ms: float = 0.0, bandwidth_mbps: float = 0.0):
        self.delay_s = float(delay_ms) / 1e3
        self.byte_s = (8.0 / (float(bandwidth_mbps) * 1e6)
                       if bandwidth_mbps else 0.0)
        self._lock = rp.named_lock("linkmodel_lock")
        self.bytes_total = 0
        self.msgs_total = 0
        self.by_peer: dict[str, int] = {}
        self.rx_by_node: dict[str, int] = {}

    @property
    def active(self) -> bool:
        return self.delay_s > 0 or self.byte_s > 0

    def charge(self, n_bytes: int, peer: str = "") -> None:
        with self._lock:
            self.bytes_total += n_bytes
            self.msgs_total += 1
            if peer:
                self.by_peer[peer] = self.by_peer.get(peer, 0) + n_bytes
        t = self.delay_s + n_bytes * self.byte_s
        if t > 0:
            time.sleep(t)

    def count_rx(self, n_bytes: int, node: str) -> None:
        """Attribute received bytes to the consuming node. Pure
        accounting: the frame already paid its link time at the sender."""
        if not node:
            return
        with self._lock:
            self.rx_by_node[node] = self.rx_by_node.get(node, 0) + n_bytes

    def stats(self) -> dict:
        with self._lock:
            out = {"bytes_total": self.bytes_total,
                   "msgs_total": self.msgs_total,
                   "by_peer": dict(self.by_peer)}
            if self.rx_by_node:
                out["rx_by_node"] = dict(self.rx_by_node)
            return out

    def reset_stats(self) -> None:
        with self._lock:
            self.bytes_total = 0
            self.msgs_total = 0
            self.by_peer = {}
            self.rx_by_node = {}

    @classmethod
    def from_env(cls) -> "LinkModel":
        """DRYNX_LINK_DELAY_MS / DRYNX_LINK_MBPS (0 = off, the default)."""
        return cls(float(os.environ.get("DRYNX_LINK_DELAY_MS", "0") or 0),
                   float(os.environ.get("DRYNX_LINK_MBPS", "0") or 0))


_LINK: Optional[LinkModel] = None

# Ambient per-thread node identity for receive-side accounting: a relay's
# OUTBOUND calls happen on handler/worker threads, far from any object
# that knows which node is talking. NodeServer.handle pins the serving
# node's name on its connection thread; fan_out / proof-delivery /
# poll threads must re-pin it on their workers (ThreadPoolExecutor
# threads inherit nothing). Unset means "client" — the querier process.
_CURRENT_NODE = threading.local()


def set_current_node(name: str) -> None:
    _CURRENT_NODE.name = name


def current_node() -> str:
    return getattr(_CURRENT_NODE, "name", "")


def link_model() -> LinkModel:
    global _LINK
    if _LINK is None:
        _LINK = LinkModel.from_env()
    return _LINK


def set_link_model(m: Optional[LinkModel]) -> None:
    global _LINK
    _LINK = m


# Frame-size cap: a corrupt or malicious 4-byte header must not drive an
# unbounded allocation (the old recv_msg would try to buffer up to 4 GiB).
# 64 MiB clears the largest legitimate payload by >100x (a 1024-value
# survey's ciphertext frame is ~500 KiB); DRYNX_MAX_FRAME_BYTES overrides
# for deployments shipping bigger tensors.
MAX_FRAME_BYTES = int(os.environ.get("DRYNX_MAX_FRAME_BYTES", str(1 << 26)))


def set_max_frame_bytes(n: int) -> None:
    global MAX_FRAME_BYTES
    MAX_FRAME_BYTES = int(n)


def b64(data: bytes) -> str:
    return base64.b64encode(data).decode()


def unb64(s) -> bytes:
    """Binary field decoder, wire-agnostic: v1 delivers base64 strings,
    v2 delivers raw bytes segments (possibly lazy narrowed ones).
    Handlers call this and never care."""
    if isinstance(s, LazySeg):
        return s.to_bytes()
    if isinstance(s, (bytes, bytearray, memoryview)):
        return bytes(s)
    return base64.b64decode(s.encode())


def pack_array(a) -> dict:
    """Tensor -> message field. ``data`` is raw bytes; the v1 encoder
    base64s it at frame time, the v2 encoder ships it as a segment."""
    a = np.asarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "data": a.tobytes()}


def unpack_array(d: dict) -> np.ndarray:
    return np.frombuffer(unb64(d["data"]),
                         dtype=np.dtype(d["dtype"])).reshape(d["shape"])


# ---------------------------------------------------------------------------
# Device-direct decode (wire -> device without the host widen)
# ---------------------------------------------------------------------------

def device_decode_on() -> bool:
    """``DRYNX_DEVICE_DECODE=off`` is the kill-switch back to the host
    decode path (narrowed segments widened via numpy before any handler
    sees them)."""
    return os.environ.get("DRYNX_DEVICE_DECODE",
                          "").strip().lower() not in ("off", "0", "no")


class LazySeg:
    """A narrowed v2 segment whose dtype widen has not happened yet.

    Host consumers (``unb64`` / ``unpack_array``) widen on demand and see
    bytes identical to the legacy decode; device consumers
    (``unpack_array_device``) skip the host widen entirely — the narrow
    view uploads as-is and a registered widen program restores the
    original dtype as the first on-device op."""

    __slots__ = ("raw", "wire_dt", "orig_dt", "_wide")

    def __init__(self, raw: bytes, wire_dt: str, orig_dt: str):
        self.raw = raw
        self.wire_dt = wire_dt
        self.orig_dt = orig_dt
        self._wide: Optional[bytes] = None

    def narrow_view(self) -> np.ndarray:
        """Zero-copy 1-D view of the wire bytes at the wire dtype."""
        return np.frombuffer(self.raw, dtype=np.dtype(self.wire_dt))

    def to_bytes(self) -> bytes:
        """Host-widened bytes — exactly what the legacy decoder produced."""
        if self._wide is None:
            self._wide = self.narrow_view() \
                .astype(np.dtype(self.orig_dt)).tobytes()
        return self._wide

    def __len__(self) -> int:
        return len(self.raw) // np.dtype(self.wire_dt).itemsize \
            * np.dtype(self.orig_dt).itemsize

    def __eq__(self, other) -> bool:
        # value-equal to the widened bytes, so decoded trees compare
        # equal to the original payload regardless of decode mode
        if isinstance(other, (bytes, bytearray)):
            return self.to_bytes() == bytes(other)
        if isinstance(other, LazySeg):
            return self.to_bytes() == other.to_bytes()
        return NotImplemented

    def __hash__(self) -> int:
        return hash(self.to_bytes())

    def __repr__(self) -> str:
        return (f"LazySeg({len(self.raw)}B {self.wire_dt}"
                f"->{self.orig_dt})")


def widen_pairs() -> list:
    """Every (narrow, wide) integer dtype pair the v2 encoder can ship —
    the set of on-device widen programs the compilecache registry
    certifies (registry._wire_specs)."""
    out = []
    for kind, cands in _NARROW.items():
        for size in (2, 4, 8):
            wide = np.dtype(f"{kind}{size}")
            for cand in cands:
                cdt = np.dtype(cand)
                if cdt.itemsize < wide.itemsize:
                    out.append((cdt.name, wide.name))
    return out


_WIDEN_JITS: dict = {}


def widen_program(wire_name: str, orig_name: str):
    """The registered on-device widen: a jitted astype per (narrow, wide)
    dtype pair. Integer astype zero-/sign-extends exactly like the numpy
    host widen, so the device path is byte-identical."""
    key = (wire_name, orig_name)
    fn = _WIDEN_JITS.get(key)
    if fn is None:
        import jax

        def _widen(a, _dt=orig_name):
            return a.astype(_dt)

        fn = jax.jit(_widen)
        _WIDEN_JITS[key] = fn
    return fn


_DEVICE_MIN_DEFAULT = 1 << 16


def device_decode_min_bytes() -> int:
    """Wire-byte floor below which a narrowed segment widens on the host
    even in device-decode mode: the on-device widen costs two extra op
    dispatches (upload + widen program), ~1 ms on the CPU backend —
    cheaper than the host astype only once the segment is large enough
    to amortize them (and, on a real accelerator, large enough that
    shipping half the bytes over PCIe matters).
    ``DRYNX_DEVICE_DECODE_MIN=0`` forces the device widen for every
    narrowed segment."""
    try:
        return int(os.environ.get("DRYNX_DEVICE_DECODE_MIN",
                                  _DEVICE_MIN_DEFAULT))
    except ValueError:
        return _DEVICE_MIN_DEFAULT


def unpack_array_device(d: dict):
    """Tensor field -> device array of the packed dtype/shape.

    The device-direct decode: a narrowed segment at or above
    ``device_decode_min_bytes()`` uploads its raw wire view (no
    intermediate host widen/copy) and widens on device through the
    registered program; anything else takes one ``jnp.asarray`` over
    the (cached) host widen. Values equal
    ``jnp.asarray(unpack_array(d))`` bit-for-bit either way."""
    import jax.numpy as jnp

    data = d["data"]
    t0 = time.perf_counter()
    if isinstance(data, LazySeg) and \
            len(data.raw) >= device_decode_min_bytes():
        dev = jnp.asarray(data.narrow_view())
        out = widen_program(data.wire_dt,
                            data.orig_dt)(dev).reshape(d["shape"])
    else:
        out = jnp.asarray(unpack_array(d))
    _record_glue("WireUpload", time.perf_counter() - t0)
    return out


def _record_glue(phase: str, dt: float) -> None:
    """Attribute a transport span to the shared host_glue/device_compute
    ledger (parallel.proof_plane.SHARD_TIMERS); never fails the wire."""
    try:
        from ..parallel import proof_plane as plane

        plane.SHARD_TIMERS.add_split(phase, "host_glue", dt)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Wire formats
# ---------------------------------------------------------------------------

def wire_default() -> int:
    """The wire version this process offers. ``DRYNX_WIRE=json`` (or v1/1)
    is the kill-switch pinning everything to the legacy JSON frames."""
    w = os.environ.get("DRYNX_WIRE", "").strip().lower()
    if w in ("json", "v1", "1"):
        return 1
    return 2


def _json_default(o):
    """v1 compatibility hook: bytes fields become base64 strings, exactly
    the shape the pre-v2 wire shipped."""
    if isinstance(o, LazySeg):
        return b64(o.to_bytes())
    if isinstance(o, (bytes, bytearray, memoryview)):
        return b64(bytes(o))
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


def jsonable(obj):
    """Deep-copy a message tree into pure-JSON types (bytes -> base64
    strings) for callers that persist or hash messages outside the wire
    (block storage, transcript digests)."""
    return json.loads(json.dumps(obj, default=_json_default))


_SEG_KEY = "__seg__"
_NARROW_KEY = "w"
# limb convention: the crypto layers carry 16-bit limbs in uint32 slots
# (and small int64 host values), so most tensor payloads narrow 2-8x
# losslessly on the wire — a bigger saving than dropping base64 alone
_NARROW = {"u": [np.uint8, np.uint16, np.uint32],
           "i": [np.int8, np.int16, np.int32]}


def _narrow_seg(dtype: str, data: bytes):
    """(wire_bytes, wire_dtype) for a packed-array payload, shipping the
    smallest integer dtype that holds every value exactly; (data, None)
    when narrowing doesn't apply. Lossless by construction: the decoder
    widens back to ``dtype`` before any handler sees the bytes."""
    try:
        dt = np.dtype(dtype)
        if dt.kind not in _NARROW or dt.itemsize <= 1 or not data:
            return data, None
        a = np.frombuffer(data, dtype=dt)
        lo, hi = int(a.min()), int(a.max())
        for cand in _NARROW[dt.kind]:
            cdt = np.dtype(cand)
            if cdt.itemsize >= dt.itemsize:
                break
            info = np.iinfo(cdt)
            if info.min <= lo and hi <= info.max:
                return a.astype(cdt).tobytes(), cdt.name
        return data, None
    except (ValueError, TypeError):
        return data, None


def _encode_v2(obj: dict) -> bytes:
    """Body of a v2 frame: [u32 header_len][header JSON][u32 nsegs]
    [u32 seg_len x nsegs][seg bytes...]. Integer tensor payloads are
    narrowed to their smallest lossless dtype (see _narrow_seg)."""
    segs: list[bytes] = []

    def ref(data: bytes, narrowed=None):
        segs.append(data)
        r = {_SEG_KEY: len(segs) - 1}
        if narrowed:
            r[_NARROW_KEY] = narrowed
        return r

    def strip(o):
        if isinstance(o, (bytes, bytearray, memoryview)):
            return ref(bytes(o))
        if isinstance(o, LazySeg):
            # relayed narrowed segment: forward the narrow wire bytes
            # untouched with the same widen marker — no host widen, and
            # byte-identical to re-narrowing the widened bytes
            return ref(o.raw, [o.wire_dt, o.orig_dt])
        if isinstance(o, dict):
            if isinstance(o.get("data"),
                          (bytes, bytearray, memoryview, LazySeg)) \
                    and isinstance(o.get("dtype"), str):
                if isinstance(o["data"], LazySeg):
                    wire_bytes = o["data"].raw
                    wdt = o["data"].wire_dt
                else:
                    wire_bytes, wdt = _narrow_seg(o["dtype"],
                                                  bytes(o["data"]))
                nw = [wdt, o["dtype"]] if wdt else None
                return {k: (ref(wire_bytes, nw) if k == "data"
                            else strip(v)) for k, v in o.items()}
            return {k: strip(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [strip(v) for v in o]
        return o

    header = json.dumps(strip(obj)).encode()
    parts = [len(header).to_bytes(4, "big"), header,
             len(segs).to_bytes(4, "big")]
    for s in segs:
        parts.append(len(s).to_bytes(4, "big"))
    parts.extend(segs)
    return b"".join(parts)


def _decode_v2(body: bytes) -> dict:
    try:
        if len(body) < 8:
            raise ValueError("truncated v2 body")
        hl = int.from_bytes(body[:4], "big")
        if 4 + hl + 4 > len(body):
            raise ValueError(f"header length {hl} exceeds body")
        header = json.loads(body[4:4 + hl].decode())
        off = 4 + hl
        nsegs = int.from_bytes(body[off:off + 4], "big")
        off += 4
        if off + 4 * nsegs > len(body):
            raise ValueError(f"segment table ({nsegs}) exceeds body")
        lens = []
        for _ in range(nsegs):
            lens.append(int.from_bytes(body[off:off + 4], "big"))
            off += 4
        segs: list[bytes] = []
        for n in lens:
            if off + n > len(body):
                raise ValueError("segment exceeds body")
            segs.append(body[off:off + n])
            off += n

        lazy = device_decode_on()

        def fill(o):
            if isinstance(o, dict):
                if _SEG_KEY in o and set(o) <= {_SEG_KEY, _NARROW_KEY}:
                    raw = segs[o[_SEG_KEY]]
                    nw = o.get(_NARROW_KEY)
                    if nw is None:
                        return raw
                    wire_dt, orig_dt = nw
                    if lazy:
                        # device-direct decode: defer the widen so device
                        # consumers can upload the narrow view as-is
                        return LazySeg(raw, wire_dt, orig_dt)
                    return np.frombuffer(raw, dtype=np.dtype(wire_dt)) \
                        .astype(np.dtype(orig_dt)).tobytes()
                return {k: fill(v) for k, v in o.items()}
            if isinstance(o, list):
                return [fill(v) for v in o]
            return o

        t0 = time.perf_counter()
        out = fill(header)
        _record_glue("WireDecode", time.perf_counter() - t0)
        return out
    except (UnicodeDecodeError, ValueError, KeyError,
            IndexError, TypeError) as e:
        raise CorruptFrame(f"undecodable {len(body)}-byte v2 frame: "
                           f"{e}") from e


def encode_frame(obj: dict, wire: int = 1) -> bytes:
    """Complete on-wire bytes (outer length prefix included)."""
    if wire >= 2:
        body = _encode_v2(obj)
    else:
        body = json.dumps(obj, default=_json_default).encode()
    return len(body).to_bytes(4, "big") + body


def decode_frame(body: bytes, wire: int = 1) -> dict:
    if wire >= 2:
        return _decode_v2(body)
    try:
        return json.loads(body.decode())
    except (UnicodeDecodeError, ValueError) as e:
        raise CorruptFrame(f"undecodable {len(body)}-byte frame: {e}") from e


def send_frame(sock: socket.socket, obj: dict, wire: int = 1,
               peer: str = "") -> None:
    frame = encode_frame(obj, wire)
    link_model().charge(len(frame), peer)
    sock.sendall(frame)


def recv_frame(sock: socket.socket, wire: int = 1,
               max_bytes: Optional[int] = None,
               rx_node: str = "") -> Optional[dict]:
    """One frame, or None on clean EOF. Raises :class:`FrameTooLarge`
    before allocating anything for an oversized header and
    :class:`CorruptFrame` when the body doesn't decode under ``wire``.
    ``rx_node`` attributes the received bytes to a node in the LinkModel's
    rx ledger (relay-hop accounting; "" skips it)."""
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    n = int.from_bytes(head, "big")
    cap = MAX_FRAME_BYTES if max_bytes is None else int(max_bytes)
    if n > cap:
        raise FrameTooLarge(
            f"frame header announces {n} bytes, cap is {cap} "
            f"(set_max_frame_bytes / DRYNX_MAX_FRAME_BYTES to raise)")
    body = _recv_exact(sock, n)
    if body is None:
        return None
    if rx_node:
        link_model().count_rx(4 + n, rx_node)
    return decode_frame(body, wire)


def send_msg(sock: socket.socket, obj: dict) -> None:
    """Legacy v1 send (raw-socket callers outside a negotiated Conn)."""
    send_frame(sock, obj, 1)


def recv_msg(sock: socket.socket,
             max_bytes: Optional[int] = None) -> Optional[dict]:
    """Legacy v1 receive."""
    return recv_frame(sock, 1, max_bytes)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _send_faulted_frame(sock: socket.socket, frame: bytes,
                        act: faults.FaultSpec) -> bool:
    """Emit (or suppress) one pre-encoded frame according to a
    request/reply fault. Returns False when the connection must be torn
    down afterwards. ``frame`` is the complete on-wire bytes; corrupting
    offset 4 (first body byte) breaks both wires deterministically: v1's
    first JSON byte becomes 0xFF (never valid UTF-8 JSON), v2's
    header-length field becomes >= 0xFF000000 (always exceeds the body)."""
    if act.kind == "drop":
        return True                      # frame vanishes on the wire
    if act.kind == "delay":
        time.sleep(act.delay_s)
        sock.sendall(frame)
        return True
    if act.kind == "corrupt":
        sock.sendall(frame[:4] + b"\xff" + frame[5:])
        return True
    if act.kind == "close_mid_frame":
        body = len(frame) - 4
        sock.sendall(frame[:4 + max(1, body // 2)])
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        sock.close()
        return False
    raise ValueError(f"unhandled fault kind {act.kind!r}")


Handler = Callable[[dict], dict]


class NodeServer:
    """One node process: a request/response dispatcher over TCP.

    The onet service-handler analogue: handlers are registered by message
    type (reference RegisterHandler via onet, service.go:149-170).
    ``node_name`` identifies this node to the fault plan's node/reply
    hooks (DrynxNode sets it; anonymous test servers stay exempt from
    name-targeted faults unless the plan targets "*").

    Each accepted connection starts in v1 and upgrades when the client's
    ``wire_hello`` arrives. The hello is transport-internal: it never
    reaches ``handlers``, never consults the fault plan's request/reply
    hooks, and so never perturbs a seeded chaos schedule.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 node_name: str = ""):
        self.handlers: dict[str, Handler] = {}
        self.node_name = node_name
        outer = self

        class _H(socketserver.BaseRequestHandler):
            def handle(self):
                wire = 1
                # handlers dial OTHER nodes from this thread (relay hops,
                # proof fan-out): pin the serving node's identity so their
                # received replies land on this node's rx ledger
                set_current_node(outer.node_name)
                while True:
                    plan = faults.fault_plan()
                    name = outer.node_name
                    if plan is not None and name and plan.killed(name):
                        return           # dead node: close without a word
                    try:
                        msg = recv_frame(self.request, wire, rx_node=name)
                    except TransportError:
                        # oversized/corrupt framing is unrecoverable on a
                        # stream transport: drop the connection, the peer
                        # sees ConnectionClosed and decides via its policy
                        return
                    if msg is None:
                        return
                    mtype = msg.get("type", "")
                    if mtype == "wire_hello":
                        agreed = min(int(msg.get("max", 1)), wire_default())
                        send_frame(self.request,
                                   {"type": "wire_hello_reply",
                                    "wire": agreed}, wire)
                        wire = agreed
                        continue
                    if plan is not None and name:
                        nf = plan.node_fault(name)
                        if nf is not None and nf.kind == "kill":
                            return
                        if nf is not None and nf.kind == "pause":
                            time.sleep(nf.delay_s)
                    fn = outer.handlers.get(mtype)
                    try:
                        if fn is None:
                            raise KeyError(f"no handler for {mtype!r}")
                        reply = fn(msg)
                        reply.setdefault("type", mtype + "_reply")
                    except Exception as e:  # fault is reported, not fatal
                        reply = {"type": "error", "error": repr(e)}
                    act = (plan.pick("reply", name, mtype)
                           if plan is not None and name else None)
                    if act is not None:
                        frame = encode_frame(reply, wire)
                        if not _send_faulted_frame(self.request, frame,
                                                   act):
                            return
                        continue
                    send_frame(self.request, reply, wire)

        class _Srv(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self.server = _Srv((host, port), _H)
        self.host, self.port = self.server.server_address
        self._thread: Optional[threading.Thread] = None

    def register(self, mtype: str, fn: Handler) -> None:
        self.handlers[mtype] = fn

    def start(self) -> None:
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()

    def serve_forever(self) -> None:
        self.server.serve_forever()

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()


class Conn:
    """Client connection with request/response semantics (SendProtobuf).

    ``peer`` names the destination node for fault-plan matching and error
    messages (call_entry passes the roster name; raw callers get
    "host:port"). After any mid-exchange failure the connection is
    ``broken``: closed, and every later call raises ConnectionClosed.
    ``sent`` reports whether the *last* call wrote any request bytes —
    the retry policy's idempotency gate reads it.

    Right after the TCP connect, the client negotiates the wire format
    (unless this process is pinned to v1 by ``DRYNX_WIRE=json``): one
    plain v1 ``wire_hello`` round-trip, invisible to fault hooks. A peer
    that errors the hello (an old server) leaves the connection on v1.
    """

    def __init__(self, host: str, port: int,
                 timeout: float = rp.CALL_TIMEOUT_S, peer: str = ""):
        self.peer = peer or f"{host}:{port}"
        self.host, self.port = host, int(port)
        self.broken = False
        self.closed = False
        self.sent = False
        self.wire = 1
        self._timeout = float(timeout)
        self._lock = rp.named_lock("conn_lock")
        plan = faults.fault_plan()
        if plan is not None:
            if plan.killed(self.peer):
                raise ConnectError(f"connect to {self.peer} refused "
                                   f"(fault plan: node killed)")
            src = current_node() or "client"
            if plan.partitioned(src, self.peer):
                raise ConnectError(
                    f"connect {src} -> {self.peer} refused "
                    f"(fault plan: partitioned)")
            act = plan.pick("connect", self.peer)
            if act is not None:
                if act.kind == "delay":
                    time.sleep(act.delay_s)
                elif act.kind == "refuse":
                    raise ConnectError(
                        f"connect to {self.peer} refused (fault plan)")
        try:
            self.sock = socket.create_connection((host, port),
                                                 timeout=timeout)
        except OSError as e:
            raise ConnectError(f"connect to {self.peer} failed: {e}") from e
        self._negotiate(wire_default())
        if _PROTO_TRACE:
            # minted only for fully constructed conns: a failed
            # negotiation raises before any caller holds a checkout
            from ..analysis import prototrace
            self._proto_inst = prototrace.new_instance("conn")
            prototrace.record(self._proto_inst, "checkout")

    def _negotiate(self, want: int) -> None:
        if want >= 2:
            try:
                send_frame(self.sock, {"type": "wire_hello", "max": want},
                           1, peer=self.peer)
                reply = recv_frame(self.sock, 1,
                                   rx_node=current_node() or "client")
                if (reply is not None and reply.get("type") != "error"
                        and int(reply.get("wire", 1)) >= 2):
                    self.wire = 2
            except (TransportError, OSError) as e:
                self._mark_broken()
                raise ConnectError(
                    f"wire negotiation with {self.peer} failed: {e}") from e
            if reply is None:
                self._mark_broken()
                raise ConnectError(
                    f"connection closed by {self.peer} during wire "
                    f"negotiation")

    # One request/response per connection AT A TIME is the wire contract:
    # the per-connection lock below deliberately covers send_frame +
    # recv_frame (a second thread interleaving frames on the same socket
    # would corrupt both conversations). Cross-peer parallelism comes
    # from the pool handing out one Conn per worker, never from sharing
    # a socket.
    def call(self, obj: dict) -> dict:  # drynx: noqa[blocking-call-under-lock]
        mtype = obj.get("type", "")
        if self.broken or self.closed:
            raise ConnectionClosed(
                f"connection to {self.peer} already broken")
        if _PROTO_TRACE:
            _proto_record(self, "use")
        with self._lock:
            self.sent = False
            try:
                plan = faults.fault_plan()
                if (plan is not None
                        and plan.partitioned(current_node() or "client",
                                             self.peer)):
                    # the link is cut under us: the frame never leaves,
                    # so sent stays False and the retry policy treats it
                    # as a connect-class failure (safe to re-dial later)
                    self._mark_broken()
                    raise ConnectionClosed(
                        f"link to {self.peer} cut before {mtype!r} "
                        f"(fault plan: partitioned)")
                act = (plan.pick("request", self.peer, mtype)
                       if plan is not None else None)
                if act is not None:
                    self.sent = True
                    frame = encode_frame(obj, self.wire)
                    if not _send_faulted_frame(self.sock, frame, act):
                        self._mark_broken()
                        raise ConnectionClosed(
                            f"connection to {self.peer} lost after partial "
                            f"write of {mtype!r} (fault plan)")
                else:
                    send_frame(self.sock, obj, self.wire, peer=self.peer)
                    self.sent = True
                reply = recv_frame(self.sock, self.wire,
                                   rx_node=current_node() or "client")
            except ConnectionClosed:
                raise
            except socket.timeout as e:
                self._mark_broken()
                raise CallTimeout(
                    f"timeout mid-call to {self.peer} ({mtype!r}); "
                    f"connection dropped") from e
            except TransportError:
                self._mark_broken()
                raise
            except OSError as e:
                self._mark_broken()
                raise ConnectionClosed(
                    f"connection to {self.peer} failed mid-call "
                    f"({mtype!r}): {e}") from e
        if reply is None:
            self._mark_broken()
            raise ConnectionClosed(
                f"connection closed by peer {self.peer}")
        if reply.get("type") == "error":
            raise RemoteError(f"remote error: {reply.get('error')}")
        return reply

    def _mark_broken(self) -> None:
        if _PROTO_TRACE and not self.broken:
            _proto_record(self, "timeout")
        self.broken = True
        try:
            self.sock.close()
        except OSError:
            pass

    def close(self) -> None:
        if _PROTO_TRACE and not self.closed:
            _proto_record(self, "close")
        self.closed = True
        self.sock.close()


class ConnPool:
    """Per-process connection reuse, keyed by (peer, host, port).

    Replaces the connect-per-RPC pattern: ``call_entry`` checks a
    connection out, runs one request/response, and returns it on success
    (RemoteError included — the conn is healthy, the handler raised).
    Anything that broke the frame exchange (CallTimeout, ConnectionClosed,
    CorruptFrame, OSError) leaves the conn ``broken`` and :meth:`put`
    refuses it, so a half-read reply can never desync a later caller.

    Checkout re-validates with a zero-timeout MSG_PEEK: EOF (the server
    restarted) or stray buffered bytes (a reply that arrived after its
    caller timed out) both disqualify the socket. Idle depth per key is
    bounded by ``max_idle`` (rp.CONN_POOL_MAX_IDLE); beyond it, returned
    connections are closed, keeping the fd footprint at
    len(roster) * max_idle.

    ``max_total`` bounds idle sockets across ALL keys: at a 256-DP
    roster the per-key bound alone still means hundreds of live fds in
    the root process. When a put would exceed it, the least-recently-
    used idle connection (whatever its peer) is closed first — warm
    peers keep their sockets, cold peers age out. rp.CONN_POOL_MAX
    defaults it generously; DRYNX_CONN_POOL_MAX overrides per process.

    The FaultPlan ``connect`` hook fires only on real (re)connects —
    reuse never consults it, which keeps seeded chaos schedules
    independent of pool hit rates (faults.py keys draws per node, not by
    global arrival order).
    """

    def __init__(self, max_idle: int = rp.CONN_POOL_MAX_IDLE,
                 max_total: Optional[int] = None):
        self.max_idle = int(max_idle)
        if max_total is None:
            env = os.environ.get("DRYNX_CONN_POOL_MAX", "").strip()
            max_total = int(env) if env else rp.CONN_POOL_MAX
        self.max_total = int(max_total)
        self._lock = rp.named_lock("connpool_lock")
        # stacks hold (stamp, Conn); LIFO per key keeps the warmest
        # socket on top, the monotonic stamp orders LRU eviction globally
        self._idle: dict[tuple, list[tuple[int, Conn]]] = {}
        # keys whose conns broke mid-exchange since their last fresh
        # dial: a dead-or-partitioned peer's idle sockets can pass the
        # MSG_PEEK health check (no FIN ever arrives through a cut
        # link), so each checkout would hand out another doomed socket
        # and burn a full call timeout. Once a FRESH dial to a suspect
        # peer succeeds (the peer is demonstrably back), the whole stale
        # idle stack for that key is purged instead.
        self._suspect: set[tuple] = set()
        self._stamp = 0
        self.connects = 0
        self.reuses = 0
        self.discards = 0
        self.evictions = 0
        self.purges = 0

    @staticmethod
    def _key(conn: Conn) -> tuple:
        return (conn.peer, conn.host, conn.port)

    def get(self, host: str, port: int,
            timeout: float = rp.CALL_TIMEOUT_S, peer: str = "") -> Conn:
        key = (peer or f"{host}:{port}", host, int(port))
        with self._lock:
            suspect = key in self._suspect
        # a suspect key bypasses its idle stack entirely: those sockets
        # pass MSG_PEEK (a cut link delivers no FIN) but each checkout
        # would burn a full call timeout on a doomed exchange. Dial
        # fresh instead — refusal fails fast and keeps the key suspect;
        # success proves the peer is back and purges the stale stack.
        while not suspect:
            with self._lock:
                stack = self._idle.get(key)
                conn = stack.pop()[1] if stack else None
            if conn is None:
                break
            if self._healthy(conn, timeout):
                with self._lock:
                    self.reuses += 1
                conn._timeout = float(timeout)
                if _PROTO_TRACE:
                    # a reuse starts a fresh checkout lifecycle: the
                    # previous token ended at its accepting "returned"
                    from ..analysis import prototrace
                    conn._proto_inst = prototrace.new_instance("conn")
                    prototrace.record(conn._proto_inst, "checkout")
                return conn
            self.discard(conn)
        conn = Conn(host, port, timeout=timeout, peer=peer)
        stale: list[Conn] = []
        with self._lock:
            self.connects += 1
            if key in self._suspect:
                self._suspect.discard(key)
                stale = [c for _stamp, c in self._idle.pop(key, [])]
                self.purges += len(stale)
        for s in stale:
            try:
                s.sock.close()
            except OSError:
                pass
            s.closed = True
        return conn

    @staticmethod
    def _healthy(conn: Conn, timeout: float) -> bool:
        if conn.broken or conn.closed:
            return False
        try:
            conn.sock.setblocking(False)
            try:
                conn.sock.recv(1, socket.MSG_PEEK)
            except (BlockingIOError, InterruptedError):
                return True          # nothing pending: idle and alive
            finally:
                conn.sock.settimeout(timeout)
            return False             # EOF (b"") or stray bytes: desynced
        except OSError:
            return False

    def put(self, conn: Optional[Conn]) -> None:
        if conn is None:
            return
        if conn.broken or conn.closed:
            self.discard(conn)
            return
        if _PROTO_TRACE:
            _proto_record(conn, "put")
        key = self._key(conn)
        evicted: list[Conn] = []
        pooled = False
        with self._lock:
            if len(self._idle.get(key, ())) < self.max_idle:
                while (sum(len(s) for s in self._idle.values())
                       >= self.max_total):
                    victim = self._pop_lru_locked()
                    if victim is None:
                        break
                    evicted.append(victim)
                    self.evictions += 1
                self._stamp += 1
                # (re)fetch after eviction: popping this key's last idle
                # conn deletes its stack, and appending to the orphaned
                # list would leak the socket out of the pool
                self._idle.setdefault(key, []).append((self._stamp, conn))
                pooled = True
        for v in evicted:
            try:
                v.sock.close()
            except OSError:
                pass
            v.closed = True
        if not pooled:
            # idle-depth overflow: the conn is healthy, just surplus —
            # closing it must not condemn the peer's pooled sockets
            self.discard(conn, suspect=False)

    def _pop_lru_locked(self) -> Optional[Conn]:
        """Remove and return the globally least-recently-pooled idle
        connection (caller holds the lock). Oldest stamp sits at each
        stack's base, so the scan is O(#keys)."""
        best_key, best_stamp = None, None
        for key, stack in self._idle.items():
            if stack and (best_stamp is None or stack[0][0] < best_stamp):
                best_key, best_stamp = key, stack[0][0]
        if best_key is None:
            return None
        conn = self._idle[best_key].pop(0)[1]
        if not self._idle[best_key]:
            del self._idle[best_key]
        return conn

    def discard(self, conn: Optional[Conn], *,
                suspect: bool = True) -> None:
        if conn is None:
            return
        if _PROTO_TRACE and not conn.closed:
            _proto_record(conn, "discard")
        with self._lock:
            self.discards += 1
            if suspect:
                self._suspect.add(self._key(conn))
        try:
            conn.sock.close()
        except OSError:
            pass
        conn.closed = True

    def close_all(self) -> None:
        with self._lock:
            idle, self._idle = self._idle, {}
        for stack in idle.values():
            for _stamp, conn in stack:
                try:
                    conn.sock.close()
                except OSError:
                    pass
                conn.closed = True

    def idle_count(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._idle.values())

    def stats(self) -> dict:
        with self._lock:
            return {"connects": self.connects, "reuses": self.reuses,
                    "discards": self.discards,
                    "evictions": self.evictions, "purges": self.purges,
                    "idle": sum(len(s) for s in self._idle.values())}


_POOL: Optional[ConnPool] = None
# Guards lazy creation/replacement of the process pool: two fan_out
# workers racing through conn_pool() must never build two pools (the
# loser's pool — and every socket it ever opens — would leak unpooled).
_POOL_LOCK = rp.named_lock("connpool_init_lock")


def pool_enabled() -> bool:
    """DRYNX_CONN_POOL=off is the kill-switch back to connect-per-RPC."""
    return os.environ.get("DRYNX_CONN_POOL",
                          "").strip().lower() not in ("off", "0", "no")


def conn_pool() -> Optional[ConnPool]:
    global _POOL
    if not pool_enabled():
        return None
    if _POOL is None:
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = ConnPool()
    return _POOL


def set_conn_pool(p: Optional[ConnPool]) -> None:
    global _POOL
    with _POOL_LOCK:
        old, _POOL = _POOL, p
    if old is not None and old is not p:
        old.close_all()


def local_call(peer: str, mtype: str, fn, *args, **kwargs):
    """Run an in-process node call under the same FaultPlan hooks a TCP
    exchange would hit (the open resilience next-step from ROBUSTNESS.md).

    LocalCluster never opens sockets, so before this helper the four
    transport hooks only fired on the TCP path — a soak test against the
    in-process scheduler could not kill/pause/delay nodes. ``local_call``
    replays the hook consultation order of Conn.__init__ + Conn.call +
    the NodeServer handler against the in-process callable:

      connect  — killed/kill-node -> ConnectError; refuse -> ConnectError;
                 delay -> sleep then proceed.
      request  — drop -> CallTimeout (the frame vanished; a socket caller
                 would block out its timeout — modeled immediately so the
                 soak stays fast); corrupt/close_mid_frame ->
                 ConnectionClosed; delay -> sleep then proceed.
      node     — pause -> sleep delay_s then proceed (kill handled above).
      reply    — same frame semantics as request, applied after fn ran
                 (the node did the work; only the answer is lost).

    With no plan active the overhead is one ``fault_plan()`` read.
    """
    plan = faults.fault_plan()
    if plan is None:
        return fn(*args, **kwargs)
    if plan.killed(peer):
        raise ConnectError(f"connect to {peer} refused "
                           f"(fault plan: node killed)")
    src = current_node() or "client"
    if plan.partitioned(src, peer):
        raise ConnectError(f"connect {src} -> {peer} refused "
                           f"(fault plan: partitioned)")
    act = plan.pick("connect", peer)
    if act is not None:
        if act.kind == "refuse":
            raise ConnectError(f"connect to {peer} refused (fault plan)")
        if act.kind == "delay":
            time.sleep(act.delay_s)
    act = plan.pick("request", peer, mtype)
    if act is not None:
        if act.kind == "drop":
            raise CallTimeout(
                f"timeout mid-call to {peer} ({mtype!r}); "
                f"request dropped (fault plan)")
        if act.kind in ("corrupt", "close_mid_frame"):
            raise ConnectionClosed(
                f"connection to {peer} lost mid-request of {mtype!r} "
                f"(fault plan: {act.kind})")
        if act.kind == "delay":
            time.sleep(act.delay_s)
    nf = plan.node_fault(peer)
    if nf is not None and nf.kind == "kill":
        raise ConnectError(f"connect to {peer} refused "
                           f"(fault plan: node killed)")
    if nf is not None and nf.kind == "pause":
        time.sleep(nf.delay_s)
    out = fn(*args, **kwargs)
    act = plan.pick("reply", peer, mtype)
    if act is not None:
        if act.kind in ("drop", "corrupt", "close_mid_frame"):
            raise ConnectionClosed(
                f"reply from {peer} lost for {mtype!r} "
                f"(fault plan: {act.kind})")
        if act.kind == "delay":
            time.sleep(act.delay_s)
    return out


__all__ = ["b64", "unb64", "pack_array", "unpack_array",
           "unpack_array_device", "device_decode_on",
           "device_decode_min_bytes", "LazySeg",
           "widen_pairs", "widen_program", "send_msg",
           "recv_msg", "send_frame", "recv_frame", "encode_frame",
           "decode_frame", "wire_default", "jsonable",
           "NodeServer", "Conn", "ConnPool", "conn_pool", "set_conn_pool",
           "pool_enabled", "LinkModel", "link_model",
           "set_link_model", "set_max_frame_bytes", "MAX_FRAME_BYTES",
           "local_call", "set_current_node", "current_node",
           "TransportError", "ConnectError", "ConnectionClosed",
           "CallTimeout", "FrameTooLarge", "CorruptFrame", "RemoteError"]

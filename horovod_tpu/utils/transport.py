"""Pluggable data-plane transports: TCP sockets and same-host shm rings.

The eager data plane historically spoke one language — framed TCP
(``socketutil.py``) — even between two ranks on the same host, where
every ring hop paid kernel copies and syscalls for bytes that never
leave the machine.  This module extracts the transport contract the
collectives actually use (:class:`Transport`: ticketed async send,
frame receive, segmented ``recv_exact_into``, teardown) and provides
two implementations:

* :class:`TcpTransport` — the existing socket path, byte-identical to
  before: sends ride the peer's persistent
  :class:`~horovod_tpu.utils.socketutil.PeerSender`, receives go through
  the same ``recv_frame`` / ``recv_frame_header`` / ``recv_exact_into``
  helpers, and the ``sock.stall`` chaos site fires exactly where the
  backend used to fire it.
* :class:`ShmRingTransport` — a per peer-pair
  ``multiprocessing.shared_memory`` segment holding two directed rings
  of seqlock'd slots (one per direction).  The writer thread packs
  frame bytes straight from fusion-buffer views into the mapped slots;
  the reader ``recv_into``s straight out of them.  Handoff is a
  sequence counter per slot — payload bytes and length are stored
  first, the slot's sequence word last, so a reader that observes
  ``seq == expected`` observes a complete slot (single writer + single
  reader per ring; CPython's byte-store ordering under the GIL provides
  the publication barrier).  Waits are adaptive: a short hot spin, then
  GIL-yielding ``sleep(0)``, then escalating micro-sleeps — and they
  honor the PR-6 collective deadline, raising the same
  ``TimeoutError("receive deadline exceeded")`` the socket path raises
  so ``HopTimeout(peer, phase)`` mapping is transport-agnostic.

Framing over shm is the same byte stream as the wire: each frame is the
5-byte ``socketutil.HEADER`` followed by the payload, chunked across
slots.  Receiver-local segmentation (``HVD_RING_SEGMENT_BYTES``) and
the dtype/op reduction order therefore work identically over both
transports, which is what keeps shm results bit-identical to TCP
(pinned by tests/test_dataplane.py).

Pairing protocol (:func:`build_transports`), leak-proof by construction:

1. every rank publishes a host record (hostname + boot id) to the KV
   rendezvous; ranks that cannot attach shm (native engine,
   ``HVD_SHM_DISABLE``) publish a rank-unique token so no peer ever
   selects shm against them;
2. for each same-host pair, the LOWER rank creates the segment and
   publishes its name; the higher rank attaches (the ``shm.attach``
   chaos site fires here) and acks;
3. on ack the creator **immediately unlinks** the ``/dev/shm`` entry —
   both mappings persist, but the name is gone, so a SIGKILL of either
   peer (or both) can never leak a segment;
4. any create/attach failure is acked as such and both sides
   deterministically fall back to TCP over the already-connected mesh
   socket.
"""

from __future__ import annotations

import collections
import os
import socket
import struct
import threading
import time
import uuid
from typing import Dict, Optional, Tuple

from horovod_tpu.common import fault_injection as _fi
from horovod_tpu.telemetry import registry as _tmx
from horovod_tpu.telemetry import trace as _trace
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils import socketutil as su


def _payload_nbytes(payload) -> int:
    n = getattr(payload, "nbytes", None)
    return n if n is not None else len(payload)


class Transport:
    """What a data-plane peer link must provide (see module docstring).

    ``send`` returns a ticket; ``wait(ticket)`` fences it (raising
    ``TimeoutError`` / ``ConnectionError`` with the same semantics as
    ``PeerSender.wait``).  ``deadline`` arguments are absolute
    ``time.monotonic()`` timestamps or ``None`` for block-forever."""

    kind = "none"
    peer = -1

    def send(self, payload, tag: int = su.TAG_DATA) -> int:
        raise NotImplementedError

    def wait(self, seq: int, timeout: Optional[float] = None) -> None:
        raise NotImplementedError

    def recv_frame(self,
                   deadline: Optional[float] = None) -> Tuple[int, bytes]:
        raise NotImplementedError

    def recv_frame_header(self,
                          deadline: Optional[float] = None
                          ) -> Tuple[int, int]:
        raise NotImplementedError

    def recv_exact_into(self, view: memoryview,
                        deadline: Optional[float] = None) -> None:
        raise NotImplementedError

    def close(self, timeout: float = 5.0) -> None:
        raise NotImplementedError

    def join(self, timeout: float = 2.0) -> None:
        """Join the sender thread after sockets/segments are torn down."""
        raise NotImplementedError


class TcpTransport(Transport):
    """The socket path behind the :class:`Transport` interface.

    Byte-identical to the pre-transport-layer code: same framing, same
    ``PeerSender`` ticket semantics, and the ``sock.stall`` chaos site
    fires once per received frame exactly where ``cpu_backend._recv`` /
    ``_recv_data_header`` used to fire it.  The socket stays owned by
    the engine (closed in engine shutdown, which is also what unblocks
    a sender thread wedged in the kernel)."""

    kind = "tcp"

    def __init__(self, sock: socket.socket, peer: int = -1,
                 sender: Optional[su.PeerSender] = None):
        self.sock = sock
        self.peer = peer
        self.sender = sender if sender is not None else su.PeerSender(
            sock, name=f"hvd-send-{peer}")

    def send(self, payload, tag: int = su.TAG_DATA) -> int:
        if _tmx.enabled():
            _tmx.inc_counter("hvd_transport_bytes_total",
                             float(_payload_nbytes(payload)), ("tcp",))
        return self.sender.send(payload, tag)

    def wait(self, seq: int, timeout: Optional[float] = None) -> None:
        self.sender.wait(seq, timeout)

    def recv_frame(self,
                   deadline: Optional[float] = None) -> Tuple[int, bytes]:
        _fi.fire("sock.stall")
        return su.recv_frame(self.sock, deadline)

    def recv_frame_header(self,
                          deadline: Optional[float] = None
                          ) -> Tuple[int, int]:
        _fi.fire("sock.stall")
        return su.recv_frame_header(self.sock, deadline)

    def recv_exact_into(self, view: memoryview,
                        deadline: Optional[float] = None) -> None:
        su.recv_exact_into(self.sock, view, deadline)

    def close(self, timeout: float = 5.0) -> None:
        self.sender.close(timeout)

    def join(self, timeout: float = 2.0) -> None:
        self.sender.thread.join(timeout)


# ---------------------------------------------------------------------------
# shared-memory ring segment
# ---------------------------------------------------------------------------

# Segment layout (all little-endian):
#   0    u32 magic, u32 version, u32 nslots, u32 slot_bytes
#   64   ring 0 write_seq (u64)   -- lower rank -> higher rank
#   128  ring 0 read_seq  (u64)
#   192  ring 1 write_seq (u64)   -- higher rank -> lower rank
#   256  ring 1 read_seq  (u64)
#   320  ring 0 slots, then ring 1 slots
# Slot: u64 seq, u32 nbytes, 4 pad bytes, payload; stride 64-aligned.
# The read_seq word is the writer's backpressure signal; the write_seq
# word is informational (attach validation / debugging) — readers use
# the per-slot seq, which is what makes the handoff a seqlock.
_MAGIC = 0x524D5348  # "HSMR"
_VERSION = 1
_HDR = struct.Struct("<IIII")
_CTRL = 64
_SLOTS_OFF = 320
_SLOT_HDR = 16

_SHM_PREFIX = "hvd-shm-"

# Wait-loop shape, env-tunable (HVD_SHM_SPIN / HVD_SHM_SLEEP_US;
# docs/performance.md "Transport selection").  Spinning is only
# profitable when the peer can make progress WHILE we spin — i.e. there
# is a spare core for it — so the spin default drops to 0 on a single
# core.  The escalating microsleep is capped at HVD_SHM_SLEEP_US on
# every host: the old single-core 1 ms ceiling meant ~0.5 ms average
# wake-up latency per slot while the TCP path got kernel-event wakeups,
# which is how shm once lost to TCP on one host.  On one core the
# yield phase is what hands the quantum to the producer; the sleep only
# exists so a yield storm cannot starve it.
_CPUS = os.cpu_count() or 1
_SPIN_HOT = env_util.shm_spin()
_SPIN_YIELD = _SPIN_HOT + (512 if _CPUS > 1 else 256)
_READ_SLEEP_CAP = env_util.shm_sleep_us() * 1e-6


def _slot_stride(slot_bytes: int) -> int:
    return (_SLOT_HDR + slot_bytes + 63) & ~63


_untracked: set = set()


def _untrack(shm) -> None:
    # Python 3.10's SharedMemory has no ``track=`` parameter: every
    # attach registers the segment with the resource tracker, which
    # unlinks it when ANY attaching process exits and prints "leaked
    # shared_memory" warnings besides.  Ownership here is explicit
    # (create -> attach ack -> immediate unlink), so opt out.  The
    # tracker's cache is per-process and dedups registrations, so
    # unregister at most once per name (an in-process create + attach
    # pair, as in tests, registers once but would unregister twice).
    if shm._name in _untracked:
        return
    _untracked.add(shm._name)
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass


class ShmSegment:
    """One mapped peer-pair segment: two directed seqlock'd rings."""

    def __init__(self, shm, nslots: int, slot_bytes: int, created: bool):
        self._shm = shm
        self.name = shm.name
        self.nslots = nslots
        self.slot_bytes = slot_bytes
        self.created = created
        self._unlinked = False

    @classmethod
    def create(cls, slot_bytes: Optional[int] = None,
               nslots: Optional[int] = None,
               name: Optional[str] = None) -> "ShmSegment":
        from multiprocessing import shared_memory

        slot_bytes = slot_bytes if slot_bytes is not None \
            else env_util.shm_slot_bytes()
        nslots = nslots if nslots is not None else env_util.shm_slots()
        stride = _slot_stride(slot_bytes)
        total = _SLOTS_OFF + 2 * nslots * stride
        name = name or f"{_SHM_PREFIX}{os.getpid()}-{uuid.uuid4().hex[:12]}"
        shm = shared_memory.SharedMemory(name=name, create=True,
                                         size=total)
        _untrack(shm)
        # Fresh tmpfs pages are zero-filled, so every seq word already
        # reads 0; only the header needs writing.
        _HDR.pack_into(shm.buf, 0, _MAGIC, _VERSION, nslots, slot_bytes)
        return cls(shm, nslots, slot_bytes, created=True)

    @classmethod
    def attach(cls, name: str) -> "ShmSegment":
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(name=name)
        _untrack(shm)
        magic, version, nslots, slot_bytes = _HDR.unpack_from(shm.buf, 0)
        if magic != _MAGIC or version != _VERSION or nslots < 1 \
                or slot_bytes < 1:
            shm.close()
            raise ValueError(
                f"shm segment {name!r} has an incompatible header "
                f"(magic={magic:#x} version={version})")
        return cls(shm, nslots, slot_bytes, created=False)

    @property
    def buf(self):
        return self._shm.buf

    def ring_offsets(self, ring: int) -> Tuple[int, int, int]:
        """(write_seq offset, read_seq offset, first slot offset)."""
        stride = _slot_stride(self.slot_bytes)
        return (_CTRL + ring * 128, _CTRL + ring * 128 + 64,
                _SLOTS_OFF + ring * self.nslots * stride)

    def unlink(self) -> None:
        """Remove the /dev/shm name; existing mappings stay valid.

        Raw ``shm_unlink`` rather than ``SharedMemory.unlink`` — the
        stdlib version also unregisters with the resource tracker, but
        :func:`_untrack` already did that at create/attach time, and a
        second unregister makes the tracker process print a KeyError
        traceback at exit.
        """
        if self._unlinked:
            return
        self._unlinked = True
        try:
            import _posixshmem

            _posixshmem.shm_unlink(self._shm._name)
        except (ImportError, FileNotFoundError, OSError):
            pass

    def close(self) -> None:
        try:
            self._shm.close()
        except (BufferError, OSError):
            pass


class _RingWriter:
    """Single-threaded frame writer for one directed ring."""

    def __init__(self, seg: ShmSegment, ring: int):
        self._buf = seg.buf
        self._nslots = seg.nslots
        self._slot_bytes = seg.slot_bytes
        self._stride = _slot_stride(seg.slot_bytes)
        self._w_off, self._r_off, self._slot0 = seg.ring_offsets(ring)
        self._wseq = struct.unpack_from("<Q", self._buf, self._w_off)[0]

    def _slot_base(self, seq: int) -> int:
        return self._slot0 + (seq % self._nslots) * self._stride

    def _acquire(self, stopped) -> int:
        """Next writable slot index; adaptive wait while the ring is
        full (reader behind).  ``stopped()`` breaks the wait so close()
        never hangs on a dead peer."""
        w = self._wseq
        n = 0
        while True:
            r = struct.unpack_from("<Q", self._buf, self._r_off)[0]
            if w - r < self._nslots:
                return w
            n += 1
            if n < _SPIN_HOT:
                continue
            if stopped():
                raise ConnectionError("shm transport closed")
            time.sleep(0 if n < _SPIN_YIELD else
                       min(_READ_SLEEP_CAP, 1e-6 * n))

    def _publish(self, w: int, nbytes: int) -> None:
        base = self._slot_base(w)
        struct.pack_into("<I", self._buf, base + 8, nbytes)
        # The seq store is the publication: everything above must be in
        # the slot before the reader can observe seq == w + 1.
        struct.pack_into("<Q", self._buf, base, w + 1)
        self._wseq = w + 1
        struct.pack_into("<Q", self._buf, self._w_off, self._wseq)

    def write_frame(self, tag: int, payload, stopped) -> None:
        view = su._as_byte_view(payload)
        total = len(view)
        header = su.HEADER.pack(tag, total)
        hb = len(header)
        w = self._acquire(stopped)
        base = self._slot_base(w)
        k = min(self._slot_bytes - hb, total)
        self._buf[base + _SLOT_HDR:base + _SLOT_HDR + hb] = header
        if k:
            self._buf[base + _SLOT_HDR + hb:
                      base + _SLOT_HDR + hb + k] = view[:k]
        self._publish(w, hb + k)
        off = k
        while off < total:
            w = self._acquire(stopped)
            base = self._slot_base(w)
            k = min(self._slot_bytes, total - off)
            self._buf[base + _SLOT_HDR:
                      base + _SLOT_HDR + k] = view[off:off + k]
            self._publish(w, k)
            off += k


class _RingReader:
    """Single-threaded byte-stream reader for one directed ring."""

    def __init__(self, seg: ShmSegment, ring: int):
        self._buf = seg.buf
        self._nslots = seg.nslots
        self._stride = _slot_stride(seg.slot_bytes)
        self._w_off, self._r_off, self._slot0 = seg.ring_offsets(ring)
        self._rseq = struct.unpack_from("<Q", self._buf, self._r_off)[0]
        self._avail = 0  # unread payload bytes left in the current slot
        self._pos = 0    # read cursor within the current slot

    def _slot_base(self, seq: int) -> int:
        return self._slot0 + (seq % self._nslots) * self._stride

    def _wait_slot(self, deadline: Optional[float], stopped) -> int:
        """Spin-then-sleep until slot ``_rseq`` is published; returns
        its base offset.  Raises the socket path's exact
        ``TimeoutError("receive deadline exceeded")`` past ``deadline``
        so HopTimeout mapping is shared."""
        base = self._slot_base(self._rseq)
        want = self._rseq + 1
        n = 0
        while True:
            if struct.unpack_from("<Q", self._buf, base)[0] == want:
                return base
            n += 1
            if n < _SPIN_HOT:
                continue
            if stopped():
                raise ConnectionError("shm transport closed")
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError("receive deadline exceeded")
            time.sleep(0 if n < _SPIN_YIELD else
                       min(_READ_SLEEP_CAP, 1e-6 * n))

    def recv_into(self, view: memoryview, deadline: Optional[float],
                  stopped) -> None:
        if view.format != "B":
            view = view.cast("B")
        need = len(view)
        got = 0
        while got < need:
            if self._avail == 0:
                base = self._wait_slot(deadline, stopped)
                self._avail = struct.unpack_from(
                    "<I", self._buf, base + 8)[0]
                self._pos = 0
            base = self._slot_base(self._rseq)
            k = min(self._avail, need - got)
            src = base + _SLOT_HDR + self._pos
            view[got:got + k] = self._buf[src:src + k]
            got += k
            self._pos += k
            self._avail -= k
            if self._avail == 0:
                # Slot fully drained: hand it back to the writer.
                self._rseq += 1
                struct.pack_into("<Q", self._buf, self._r_off,
                                 self._rseq)


class ShmRingTransport(Transport):
    """Same-host peer link over one mapped :class:`ShmSegment`.

    The send side mirrors ``PeerSender`` exactly — a named daemon
    thread (``hvd-send-shm-<peer>``) fed through a deque, tickets that
    ``wait`` fences, failures surfaced at ``wait`` — so the collectives
    and the sender-leak assertions treat both transports identically.
    The ``lower`` flag picks which directed ring this side writes
    (ring 0 belongs to the pair's lower rank)."""

    kind = "shm"

    def __init__(self, segment: ShmSegment, lower: bool, peer: int = -1,
                 name: Optional[str] = None):
        self._seg = segment
        self.peer = peer
        self._writer = _RingWriter(segment, 0 if lower else 1)
        self._reader = _RingReader(segment, 1 if lower else 0)
        self._hdr_buf = bytearray(su.HEADER.size)
        self._deque: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._enq_seq = 0
        self._done_seq = 0
        self._fail_seq: Optional[int] = None
        self._exc: Optional[BaseException] = None
        self._closing = False
        self._stop = False
        self.thread = threading.Thread(
            target=self._loop, name=name or f"hvd-send-shm-{peer}",
            daemon=True)
        self.thread.start()

    def _stopped(self) -> bool:
        return self._stop

    # -- send side (PeerSender-mirror) ----------------------------------

    def send(self, payload, tag: int = su.TAG_DATA) -> int:
        if _tmx.enabled():
            _tmx.inc_counter("hvd_transport_bytes_total",
                             float(_payload_nbytes(payload)), ("shm",))
        with self._cv:
            if self._closing:
                raise ConnectionError("sender is closed")
            if self._exc is not None:
                raise ConnectionError(
                    f"peer send failed: {self._exc!r}") from self._exc
            self._enq_seq += 1
            seq = self._enq_seq
            self._deque.append((seq, tag, payload))
            self._cv.notify_all()
        return seq

    def wait(self, seq: int, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._done_seq < seq and self._exc is None:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            "send did not complete in time")
                if not self._cv.wait(remaining):
                    raise TimeoutError("send did not complete in time")
            if self._exc is not None and self._fail_seq is not None \
                    and seq >= self._fail_seq:
                raise ConnectionError(
                    f"peer send failed: {self._exc!r}") from self._exc

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._deque and not self._closing:
                    self._cv.wait()
                if not self._deque and self._closing:
                    return
                seq, tag, payload = self._deque.popleft()
            try:
                if self._exc is None:
                    self._writer.write_frame(tag, payload, self._stopped)
            except BaseException as e:  # surface at wait()
                with self._cv:
                    self._exc = e
                    if self._fail_seq is None:
                        self._fail_seq = seq
                    self._cv.notify_all()
            with self._cv:
                self._done_seq = seq
                self._cv.notify_all()

    # -- receive side ----------------------------------------------------

    def recv_frame(self,
                   deadline: Optional[float] = None) -> Tuple[int, bytes]:
        tag, n = self.recv_frame_header(deadline)
        payload = bytearray(n)
        if n:
            self._reader.recv_into(memoryview(payload), deadline,
                                   self._stopped)
        return tag, bytes(payload)

    def recv_frame_header(self,
                          deadline: Optional[float] = None
                          ) -> Tuple[int, int]:
        # Same chaos role as the TCP path's sock.stall: wedge this
        # rank's next data-plane receive while the process stays alive.
        _fi.fire("shm.stall")
        self._reader.recv_into(memoryview(self._hdr_buf), deadline,
                               self._stopped)
        return su.HEADER.unpack(bytes(self._hdr_buf))

    def recv_exact_into(self, view: memoryview,
                        deadline: Optional[float] = None) -> None:
        self._reader.recv_into(view, deadline, self._stopped)

    # -- teardown --------------------------------------------------------

    def close(self, timeout: float = 5.0) -> None:
        """Drain-then-force: let already-enqueued frames finish, then
        break any writer blocked on a full ring (dead peer) via the
        stop flag, join the thread, and unmap the segment."""
        with self._cv:
            closing = self._closing
            self._closing = True
            self._cv.notify_all()
        if not closing:
            self.thread.join(timeout)
            if self.thread.is_alive():
                self._stop = True
                self.thread.join(timeout)
            self._stop = True  # unblock any reader still spinning
            self._seg.close()
        else:
            self.thread.join(timeout)

    def join(self, timeout: float = 2.0) -> None:
        self._stop = True
        self.thread.join(timeout)


# ---------------------------------------------------------------------------
# transport selection: KV host records + per-pair create/attach/ack
# ---------------------------------------------------------------------------


def shm_enabled() -> bool:
    return not env_util.shm_disabled()


def host_fingerprint() -> str:
    """Same-host equality token: hostname + kernel boot id (containers
    sharing a hostname but not an IPC namespace still differ by boot id
    only when the kernel differs — the mesh socket pairing below is the
    functional check: attach failure falls back to TCP)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            boot = f.read().strip()
    except OSError:
        boot = ""
    return f"{socket.gethostname()}|{boot}"


def host_record_value(rank: int, shm_capable: bool) -> str:
    """What a rank publishes under ``{prefix}hostid/{rank}``.  A
    non-capable rank (native engine, ``HVD_SHM_DISABLE``) publishes a
    rank-unique token, so both sides of every pair agree on TCP without
    any extra negotiation."""
    if shm_capable and shm_enabled():
        return host_fingerprint()
    return f"tcp-only-{rank}"


# KV value marking a failed create (wait_get cannot distinguish an empty
# value from an absent key, so the marker is a real string).
_CREATE_FAILED = "none"


def build_transports(rank: int, size: int, data: Dict[int, socket.socket],
                     kv, prefix: str,
                     timeout: Optional[float] = None,
                     tcp_factory=None, shm_factory=None
                     ) -> Dict[int, Transport]:
    """One :class:`Transport` per mesh peer, selected at mesh-build time.

    Same-host peers (matching KV host records) pair a shm segment via
    create/attach/ack with the lower rank owning creation; the name is
    unlinked the moment the ack lands, so no segment can outlive the
    gang.  Cross-host peers — and any pair whose shm pairing fails —
    get a :class:`TcpTransport` over the existing mesh socket.

    Peers are processed in ascending rank order on every rank; the
    globally smallest unfinished pair can always complete, so the
    ack waits cannot deadlock.

    ``tcp_factory(sock, peer)`` / ``shm_factory(sock, seg, lower, peer)``
    override what gets built on the selected medium without duplicating
    the pairing protocol — utils/ladder.py uses them to wrap every pair
    in a self-healing :class:`LadderLink` when ``HVD_WIRE_CRC=1``.
    """
    if timeout is None:
        timeout = env_util.get_float("HVD_START_TIMEOUT", 120.0)
    if tcp_factory is None:
        def tcp_factory(sock, peer):
            return TcpTransport(sock, peer=peer)
    if shm_factory is None:
        def shm_factory(sock, seg, lower, peer):
            return ShmRingTransport(seg, lower=lower, peer=peer)
    transports: Dict[int, Transport] = {}
    mine = host_record_value(rank, shm_capable=True)
    want_shm = shm_enabled() and "|" in mine
    for r in sorted(data):
        sock = data[r]
        peer_fp = kv.wait_get(f"{prefix}hostid/{r}",
                              timeout=timeout) if want_shm else None
        if isinstance(peer_fp, bytes):
            peer_fp = peer_fp.decode()
        if not want_shm or peer_fp != mine:
            transports[r] = tcp_factory(sock, r)
            continue
        a, b = (rank, r) if rank < r else (r, rank)
        name_key = f"{prefix}shm/{a}_{b}"
        ack_key = f"{prefix}shmack/{a}_{b}"
        if rank == a:
            seg = None
            try:
                seg = ShmSegment.create()
                kv.put(name_key, seg.name)
            except Exception:
                kv.put(name_key, _CREATE_FAILED)
            if seg is None:
                transports[r] = tcp_factory(sock, r)
                continue
            try:
                ack = kv.wait_get(ack_key, timeout=timeout)
            finally:
                # Unlink NOW, ack or not (even when the attacher died
                # mid-pairing and the wait raised): our mapping — and
                # the peer's, when it acked ok — persists; the /dev/shm
                # name must not survive a SIGKILL of either side.
                seg.unlink()
            if isinstance(ack, bytes):
                ack = ack.decode()
            if ack == "ok":
                transports[r] = shm_factory(sock, seg, True, r)
            else:
                seg.close()
                transports[r] = tcp_factory(sock, r)
        else:
            name = kv.wait_get(name_key, timeout=timeout)
            if isinstance(name, bytes):
                name = name.decode()
            seg = None
            if name and name != _CREATE_FAILED:
                try:
                    _fi.fire("shm.attach", name)
                    seg = ShmSegment.attach(name)
                except Exception:
                    seg = None
            if seg is None:
                kv.put(ack_key, "fail")
                transports[r] = tcp_factory(sock, r)
            else:
                kv.put(ack_key, "ok")
                transports[r] = shm_factory(sock, seg, False, r)
    if _trace.active():
        # Record the selected medium per peer so merged traces can
        # attribute hop latencies to the transport that carried them.
        for r, t in sorted(transports.items()):
            _trace.emit_instant("transport.map", peer=r, tp=t.kind)
    return transports


def make_transport_pair(slot_bytes: int = 4096, nslots: int = 4
                        ) -> Tuple[ShmRingTransport, ShmRingTransport]:
    """In-process shm transport pair for tests: create + attach + unlink
    immediately, exactly like the KV protocol, no rendezvous needed."""
    seg_a = ShmSegment.create(slot_bytes=slot_bytes, nslots=nslots)
    seg_b = ShmSegment.attach(seg_a.name)
    seg_a.unlink()
    return (ShmRingTransport(seg_a, lower=True, peer=1),
            ShmRingTransport(seg_b, lower=False, peer=0))

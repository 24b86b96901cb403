"""Cross-host KV-page transport: the wire under the disaggregated
handoff's documented multi-host branch (serve/disagg.py).

The same-host :class:`~.disagg.PageHandoff` moves refcounts and zero
bytes — both engines address one physical pool. Crossing hosts there is
no shared pool: the sequence's committed k/v payload must MOVE. This
module is that move, split into the honest CPU-testable pieces:

- ``gather_payload`` / ``scatter_payload``: device-to-host extraction of
  one sequence's pages (every pool leaf — an int8 pool ships its int8
  payload AND its fp32 scale rows; the scales are first-class pool state
  everywhere else and the wire is no exception) and the host-to-device
  re-allocation scatter at the receiver. Raw array bytes round-trip
  exactly, so the receiver's pool holds BITWISE the sender's bytes and
  the decode continuation is token-identical (pinned in
  tests/test_handoff.py).
- A length-prefixed CRC-checked frame (``encode_frame`` /
  ``decode_frame``) whose header carries the request + generation state,
  so the sequence's scheduling identity crosses the wire WITH its cache.
- A crash-safe delivery protocol (:class:`HandoffSender` +
  :class:`ReceiverThread`) whose only outcomes are "delivered exactly
  once" or "payload dropped" — never a torn page at the receiver, never
  a leaked page at the sender:

      sender                          receiver
      FRAME(id, header, payload, crc) ->
                                      (CRC ok)   <- ACK(id)
                                      (CRC bad)  <- NAK(id)   [drop]
      COMMIT(id) ->                   [decode + enqueue]
                                      <- FIN(id)
      -- or, on ACK timeout:  ABORT(id) ->       [drop]

  The receiver buffers a frame without touching any pool and commits it
  only on COMMIT; the sender declares delivery only on FIN, by which
  point the record is already in the receiver's inbox (no window where a
  delivered sequence is invisible to both sides). Any failure before
  COMMIT — torn frame (CRC), ack timeout, NAK — resolves to the drop
  outcome on both ends, and the disaggregated facade requeues the
  request at the prefill queue's head (recompute + bitwise replay). A
  receiver death between COMMIT and FIN is the two-generals residue this
  in-process transport cannot close (the sender would requeue a sequence
  the receiver committed); the per-transfer ``xfer_id`` dedup in
  ``disagg.CrossHostPageHandoff`` discards such a frame at the inbox.

Deterministic faults (``utils/faults.py``): ``handoff_fault(xfer_id)``
tears transfer N's payload on the wire (what a sender crash mid-write
leaves) or sits on it past the ack window — the chaos drills in
tests/test_chaos_serve.py drive both through this module's real code
path, not a mock.

``python -m distributed_training_guide_tpu.serve.transport --echo``
serves one connection as a receive-validate-commit echo endpoint over
real TCP and prints a payload digest — the cross-PROCESS leg of a
handoff.

The ICI/DCN path is the TPU rung of this seam; everything above it —
framing, the commit protocol, the requeue discipline — is
transport-agnostic by design.
"""
from __future__ import annotations

import hashlib
import json
import queue as queue_mod
import socket
import struct
import threading
import time
import zlib
from typing import Optional

import numpy as np

from ..models.llama import POOL_LEAVES
from ..train.precision import Quantized
from ..utils import faults

MAGIC = b"DTGH"
# frame prefix: magic, xfer_id, header_len, payload_len
_PRE = struct.Struct("<4sQIQ")
_CRC = struct.Struct("<I")
# control message: tag, xfer_id
_CTRL = struct.Struct("<4sQ")
ACK, NAK, CMT, ABT, FIN = b"ACK!", b"NAK!", b"CMT!", b"ABT!", b"FIN!"

_CRASH_TEAR_BYTES = 64


class TransportError(RuntimeError):
    """A wire-level failure (short read, bad magic, CRC mismatch)."""


# ---- payload <-> pool ------------------------------------------------------

def pool_leaf_names(pages: dict) -> list[str]:
    """Stable leaf order for the wire: k then v (then a family's per-page
    state, ``kv_pages.state_layout``), payload before scales for a
    quantized pool."""
    names = []
    for name in _pools(pages):
        if isinstance(pages[name], Quantized):
            names.extend([f"{name}.q", f"{name}.scale"])
        else:
            names.append(name)
    return names


def _pools(pages: dict) -> list[str]:
    return [name for name in POOL_LEAVES if name in pages]


def _leaf(pages: dict, name: str):
    base, _, part = name.partition(".")
    leaf = pages[base]
    return getattr(leaf, part) if part else leaf


def payload_nbytes(payload: dict) -> int:
    """Total host bytes of a gathered payload — the unit the host tier
    budgets in (serve/tiering.py) and the wire-cost row preflight prices."""
    return sum(int(np.asarray(v).nbytes) for v in payload.values())


def gather_payload(pages: dict, page_ids: list[int]) -> dict[str, np.ndarray]:
    """Device-to-host: one sequence's pages out of every pool leaf —
    ``{leaf_name: [L, n, page, kvh, hd(|1)]}`` host arrays in logical
    page order. The raw bytes are the pool's bytes (no dtype cast), so a
    scatter at the receiver reproduces them bitwise."""
    idx = np.asarray(page_ids, np.int32)
    return {name: np.asarray(_leaf(pages, name)[:, idx])
            for name in pool_leaf_names(pages)}


def scatter_payload(pages: dict, page_ids: list[int],
                    payload: dict[str, np.ndarray]) -> dict:
    """Host-to-device: write a received payload into freshly-allocated
    pages of the receiver's pool. Returns the updated pools dict (same
    keys; callers assign back into their shared handle)."""
    import jax.numpy as jnp

    idx = jnp.asarray(page_ids, jnp.int32)

    def upd(leaf, name):
        return leaf.at[:, idx].set(jnp.asarray(payload[name], leaf.dtype))

    out = {}
    for name in _pools(pages):
        leaf = pages[name]
        if isinstance(leaf, Quantized):
            out[name] = Quantized(q=upd(leaf.q, f"{name}.q"),
                                  scale=upd(leaf.scale, f"{name}.scale"))
        else:
            out[name] = upd(leaf, name)
    return out


# ---- frame -----------------------------------------------------------------

def encode_frame(xfer_id: int, header: dict,
                 payload: dict[str, np.ndarray]) -> bytes:
    """One transfer on the wire: prefix | header JSON | concatenated
    leaf bytes | CRC32(header+payload). The header's ``leaves`` entry
    records (name, shape, dtype) in payload order so the receiver can
    split the byte run without guessing."""
    header = dict(header)
    header["leaves"] = [{"name": k, "shape": list(v.shape),
                         "dtype": str(v.dtype)}
                        for k, v in payload.items()]
    blob = b"".join(np.ascontiguousarray(v).tobytes()
                    for v in payload.values())
    hdr = json.dumps(header).encode()
    crc = zlib.crc32(hdr)
    crc = zlib.crc32(blob, crc)
    return (_PRE.pack(MAGIC, xfer_id, len(hdr), len(blob))
            + hdr + blob + _CRC.pack(crc))


def split_payload(header: dict, blob: bytes) -> dict[str, np.ndarray]:
    """Rebuild the leaf arrays from a validated frame's payload bytes."""
    out, at = {}, 0
    for leaf in header["leaves"]:
        arr = np.zeros(leaf["shape"], np.dtype(leaf["dtype"]))
        n = arr.nbytes
        out[leaf["name"]] = np.frombuffer(
            blob[at:at + n], dtype=arr.dtype).reshape(leaf["shape"])
        at += n
    if at != len(blob):
        raise TransportError(f"payload length mismatch: leaves declare "
                             f"{at} B, frame carries {len(blob)} B")
    return out


def decode_frame(buf: bytes) -> tuple[int, dict, dict]:
    """(xfer_id, header, payload arrays) from one whole frame; raises
    :class:`TransportError` on any integrity failure."""
    if len(buf) < _PRE.size + _CRC.size:
        raise TransportError(f"short frame: {len(buf)} B")
    magic, xfer_id, hlen, plen = _PRE.unpack_from(buf)
    if magic != MAGIC:
        raise TransportError(f"bad magic {magic!r}")
    end = _PRE.size + hlen + plen
    if len(buf) != end + _CRC.size:
        raise TransportError("frame length mismatch")
    hdr_b, blob = buf[_PRE.size:_PRE.size + hlen], buf[_PRE.size + hlen:end]
    crc = zlib.crc32(hdr_b)
    crc = zlib.crc32(blob, crc)
    if crc != _CRC.unpack_from(buf, end)[0]:
        raise TransportError("CRC mismatch (torn or corrupted frame)")
    header = json.loads(hdr_b)
    return xfer_id, header, split_payload(header, blob)


# ---- sockets ---------------------------------------------------------------

def _read_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    chunks, got = [], 0
    while got < n:
        try:
            chunk = sock.recv(min(n - got, 1 << 20))
        except OSError:
            return None
        if not chunk:
            return None
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def _send_ctrl(sock: socket.socket, tag: bytes, xfer_id: int) -> None:
    try:
        sock.sendall(_CTRL.pack(tag, xfer_id))
    except OSError:
        pass                    # the peer is gone; outcomes don't change


def _read_ctrl(sock: socket.socket, want_id: int,
               timeout_s: float) -> Optional[bytes]:
    """Next control tag for ``want_id``, skipping stale messages from
    earlier (aborted/timed-out) transfers; None on timeout or close."""
    deadline = time.monotonic() + timeout_s
    while True:
        left = deadline - time.monotonic()
        if left <= 0:
            return None
        sock.settimeout(left)
        try:
            buf = _read_exact(sock, _CTRL.size)
        finally:
            try:
                sock.settimeout(None)
            except OSError:
                pass
        if buf is None:
            return None
        tag, got_id = _CTRL.unpack(buf)
        if got_id < want_id:
            continue            # a late ack for a transfer already resolved
        if got_id > want_id:
            return None         # protocol desync: treat as failure
        return tag


class HandoffSender:
    """The sending half of the delivery protocol, run inline on the
    engine thread: write the frame, wait for ACK, COMMIT, wait for FIN.
    ``send`` returns the outcome — "delivered" means the record is in
    the receiver's inbox ALREADY (FIN is sent after the enqueue), any
    other outcome means the receiver committed nothing and the caller
    must requeue."""

    def __init__(self, sock: socket.socket, *, ack_timeout_s: float = 2.0):
        self.sock = sock
        self.ack_timeout_s = ack_timeout_s

    def send(self, frame: bytes, xfer_id: int) -> str:
        fault = faults.handoff_fault(xfer_id)
        if fault == "crash":
            # a sender crash mid-write leaves a torn payload on the wire;
            # framing survives (the length prefix went out first) so the
            # receiver reads a full frame and the CRC rejects it
            pre, hlen, plen = _PRE.size, *_PRE.unpack_from(frame)[2:]
            tear = pre + hlen + plen // 2
            frame = (frame[:tear]
                     + bytes(b ^ 0xFF
                             for b in frame[tear:tear + _CRASH_TEAR_BYTES])
                     + frame[tear + _CRASH_TEAR_BYTES:])
        try:
            self.sock.sendall(frame)
        except OSError:
            return "dropped_link"
        tag = _read_ctrl(self.sock, xfer_id, self.ack_timeout_s)
        if tag != ACK:
            if tag is None:
                _send_ctrl(self.sock, ABT, xfer_id)
                return "dropped_timeout"
            return "dropped_nak"
        _send_ctrl(self.sock, CMT, xfer_id)
        if _read_ctrl(self.sock, xfer_id, self.ack_timeout_s) == FIN:
            return "delivered"
        # the two-generals residue: COMMIT sent, FIN lost — the receiver
        # MAY have committed; the inbox-side xfer_id dedup discards it
        return "dropped_timeout"


class ReceiverThread(threading.Thread):
    """The receiving half: reads frames off its socket end, runs the
    ACK/COMMIT exchange, and enqueues (header, payload) records on
    ``inbox`` — pure bytes work, no pool and no device; the receiver
    pool's allocation + scatter happen on the engine thread when the
    decode side takes the record. Exits on socket close."""

    def __init__(self, sock: socket.socket, *, ack_timeout_s: float = 2.0):
        super().__init__(daemon=True, name="handoff-recv")
        self.sock = sock
        self.ack_timeout_s = ack_timeout_s
        self.inbox: queue_mod.SimpleQueue = queue_mod.SimpleQueue()

    def run(self) -> None:
        try:
            self._run()
        except OSError:
            return      # socket closed under us mid-exchange (a per-pull
            #             channel torn down while the injected stall slept)

    def _run(self) -> None:
        while True:
            pre = _read_exact(self.sock, _PRE.size)
            if pre is None:
                return
            magic, xfer_id, hlen, plen = _PRE.unpack(pre)
            if magic != MAGIC:
                return          # framing lost: the link is unrecoverable
            body = _read_exact(self.sock, hlen + plen + _CRC.size)
            if body is None:
                return
            if faults.handoff_fault(xfer_id) == "timeout":
                # injected stall: sit on the frame past the sender's ack
                # window, then discard it unacked — the sender has long
                # since aborted and requeued. The sleep is 1.5x the ack
                # timeout so the RETRY (a fresh xfer_id, not re-faulted)
                # finds the receiver awake inside its own ack window —
                # one injected fault, exactly one drop. The sender's
                # ABORT for this id is already in our stream — absorb it
                # before the next frame read or framing desyncs.
                time.sleep(self.ack_timeout_s * 1.5)
                _read_ctrl(self.sock, xfer_id, self.ack_timeout_s)
                continue
            hdr_b, blob = body[:hlen], body[hlen:hlen + plen]
            crc = zlib.crc32(hdr_b)
            crc = zlib.crc32(blob, crc)
            if crc != _CRC.unpack(body[-_CRC.size:])[0]:
                _send_ctrl(self.sock, NAK, xfer_id)
                continue
            _send_ctrl(self.sock, ACK, xfer_id)
            tag = _read_ctrl(self.sock, xfer_id, self.ack_timeout_s)
            if tag != CMT:
                continue        # ABORT / timeout / desync: drop, no commit
            try:
                header = json.loads(hdr_b)
                payload = split_payload(header, blob)
            except (ValueError, TransportError):
                continue        # CRC passed but content is garbage: drop
            self.inbox.put((xfer_id, header, payload))
            _send_ctrl(self.sock, FIN, xfer_id)


def loopback_channel(*, ack_timeout_s: float = 2.0) \
        -> tuple[HandoffSender, ReceiverThread]:
    """A connected (sender, started receiver thread) pair over a real
    socketpair — the single-process stand-in for two hosts that still
    exercises every wire byte and protocol step."""
    a, b = socket.socketpair()
    sender = HandoffSender(a, ack_timeout_s=ack_timeout_s)
    receiver = ReceiverThread(b, ack_timeout_s=ack_timeout_s)
    receiver.start()
    return sender, receiver


# ---- cross-process echo (a handoff's cross-process leg) --------------------

def run_echo_server(port: int = 0, expect: Optional[int] = None,
                    out=None) -> dict:
    """Listen on 127.0.0.1:``port``, accept ONE connection, run the full
    receive-validate-commit protocol for ``expect`` frames (or until the
    peer closes), and return {frames, payload_bytes, sha256} — the
    digest the sending process compares against its own bytes, pinning
    that a real process boundary preserved the payload bitwise."""
    srv = socket.create_server(("127.0.0.1", port))
    if out is not None:
        print(json.dumps({"port": srv.getsockname()[1]}), file=out,
              flush=True)
    conn, _ = srv.accept()
    receiver = ReceiverThread(conn)
    receiver.start()
    digest = hashlib.sha256()
    frames = payload_bytes = 0
    while expect is None or frames < expect:
        try:
            _, header, payload = receiver.inbox.get(timeout=30.0)
        except queue_mod.Empty:
            break
        for name in (leaf["name"] for leaf in header["leaves"]):
            buf = np.ascontiguousarray(payload[name]).tobytes()
            digest.update(buf)
            payload_bytes += len(buf)
        frames += 1
    # the last frame's FIN may still be in the receiver thread's hands
    # (inbox.put precedes the FIN write); wait for the PEER to close —
    # the thread exits on its EOF — before tearing the socket down
    receiver.join(timeout=10.0)
    conn.close()
    srv.close()
    return {"frames": frames, "payload_bytes": payload_bytes,
            "sha256": digest.hexdigest()}


def main(argv=None) -> None:
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m distributed_training_guide_tpu.serve.transport",
        description="cross-process handoff echo endpoint")
    parser.add_argument("--echo", action="store_true", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--expect", type=int, default=None)
    args = parser.parse_args(argv)
    result = run_echo_server(args.port, args.expect, out=sys.stdout)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

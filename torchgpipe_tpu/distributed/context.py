"""Named mailboxes + pluggable transports for the multi-process pipeline.

Re-design of the reference's channel registry
(reference: torchgpipe/distributed/context.py:19-193): each worker owns a
:class:`Mailbox` of blocking channels keyed by ``(kind, index)`` — forward
activations, backward gradients, targets, and cross-rank skip tensors all
travel through the same mechanism.  Where the reference hard-codes
``torch.distributed.rpc`` one-way calls with CPU staging
(reference: torchgpipe/distributed/gpipe.py:86-96, 176-177), transport here
is pluggable:

* :class:`LocalTransport` — in-process delivery between rank objects living
  in one process (multi-device single-host runs, and the test harness; the
  reference tests mock RPC the same way,
  tests/distributed/test_distributed_gpipe.py:34-117).
* :class:`TcpTransport` — length-prefixed pickled numpy pytrees over TCP
  sockets between OS processes/hosts.  Host-staged, as the reference's RPC
  transport is.  For pod-scale TPU jobs the SPMD engine
  (:mod:`torchgpipe_tpu.spmd`) over ICI/DCN is the preferred path
  (SURVEY.md §2.3); this transport exists for capability parity with the
  reference's multi-process mode on commodity networks.

The reference's channel API (``put_forward``/``get_forward`` etc.,
distributed/context.py:96-193) maps to ``Mailbox.put/get`` with kinds
``"forward" | "backward" | "target" | ("skip", key) | ("skip_grad", key)``.
"""

from __future__ import annotations

import contextlib
import pickle
import queue
import random
import socket
import socketserver
import struct
import threading
import time
import zlib
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

Payload = Any
ChannelKey = Tuple[Any, int]

# Connect-retry backoff: exponential from BASE, CAPPED at CAP — the cap
# is the contract (a rank that has been retrying for a while still
# probes at least every RETRY_BACKOFF_CAP_S seconds, so a late-booting
# peer is picked up within one cap interval, never minutes).  Jitter
# (equal-jitter: half fixed, half uniform) keeps a fleet of ranks that
# all lost the same peer from re-connecting in lockstep and SYN-flooding
# its freshly restarted listener.
RETRY_BACKOFF_BASE_S = 0.5
RETRY_BACKOFF_CAP_S = 5.0


def _retry_sleep_s(attempt: int, rng: random.Random) -> float:
    """Sleep before connect retry ``attempt`` (1-based): equal-jitter
    exponential backoff, ``base * 2**(attempt-1)`` capped at
    :data:`RETRY_BACKOFF_CAP_S`, half of it jittered uniformly."""
    ceiling = min(
        RETRY_BACKOFF_CAP_S,
        RETRY_BACKOFF_BASE_S * (2.0 ** max(attempt - 1, 0)),
    )
    return ceiling / 2.0 + rng.random() * ceiling / 2.0


class PeerDiedError(TimeoutError):
    """A peer rank is confirmed dead (not merely slow).

    Refines the bare receive ``TimeoutError`` when the expected sender
    fails a liveness probe (``transport.is_alive``): unregistered from a
    :class:`LocalTransport`, or its :class:`TcpTransport` listener
    refusing connections.  Names the dead rank so the operator (or an
    external supervisor) knows WHICH worker to restart.  Subclasses
    ``TimeoutError`` so existing dead-peers-surface-as-named-timeouts
    handling keeps working — but :func:`torchgpipe_tpu.resilience.guard.
    classify_error` special-cases it FIRST as fatal (plain timeouts are
    transient): channels may hold stale messages and peers partial sends,
    so recovery is restart-and-resume from a checkpoint, not an
    in-process retry.
    """

    def __init__(self, rank: int, worker: str, detail: str = "") -> None:
        self.rank = rank
        self.worker = worker
        super().__init__(
            f"peer rank {rank} ({worker!r}) is dead"
            + (f": {detail}" if detail else "")
        )


class Mailbox:
    """Blocking channels keyed by ``(kind, micro-batch index)``.

    Reference: torchgpipe/distributed/context.py:19-26 (``TrainingContext``
    holds ``chunks`` forward + ``chunks`` backward queues + a target queue);
    here channels are created on demand, which also carries skip tensors.

    ``recorder`` (an :class:`~torchgpipe_tpu.obs.flightrec.
    FlightRecorder`, attached by the owning rank) turns every delivery
    into a ``mail_put`` flight event carrying the post-put channel depth
    — the RECEIVER-side arrival evidence the postmortem analyzer pairs
    against the sender's ``send`` event: a send with no matching arrival
    is a message lost (or hung) in transport.  ``put`` runs on sender /
    listener threads, which is why the recorder is thread-safe.
    """

    def __init__(self, name: str) -> None:
        self.name = name
        self.recorder: Optional[Any] = None
        self._channels: Dict[ChannelKey, queue.Queue] = {}
        self._lock = threading.Lock()

    def _channel(self, kind: Any, index: int) -> queue.Queue:
        key = (kind, index)
        with self._lock:
            ch = self._channels.get(key)
            if ch is None:
                ch = self._channels[key] = queue.Queue()
            return ch

    def depth(self, kind: Any, index: int) -> int:
        """Approximate queued-message count on one channel (``qsize`` —
        exact for the single-consumer engine loops)."""
        with self._lock:
            ch = self._channels.get((kind, index))
        return ch.qsize() if ch is not None else 0

    def put(self, kind: Any, index: int, payload: Payload) -> None:
        ch = self._channel(kind, index)
        ch.put(payload)
        rec = self.recorder
        if rec is not None:
            rec.record("mail_put", channel=(kind, index),
                       detail=f"depth={ch.qsize()}")

    def get(self, kind: Any, index: int, timeout: Optional[float] = None) -> Payload:
        try:
            return self._channel(kind, index).get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"worker {self.name!r}: no message on channel {(kind, index)!r} "
                f"within {timeout}s — is the peer rank alive?"
            ) from None


class LocalTransport:
    """In-process transport: a shared registry of mailboxes.

    Mirrors the reference's ``GlobalContext`` registry
    (reference: torchgpipe/distributed/context.py:28-38) without RPC.
    """

    def __init__(self) -> None:
        self._mailboxes: Dict[str, Mailbox] = {}

    def register(self, name: str) -> Mailbox:
        if name in self._mailboxes:
            raise ValueError(f"worker {name!r} already registered")
        box = Mailbox(name)
        self._mailboxes[name] = box
        return box

    def unregister(self, name: str) -> None:
        self._mailboxes.pop(name, None)

    def send(self, dst: str, kind: Any, index: int, payload: Payload) -> None:
        try:
            box = self._mailboxes[dst]
        except KeyError:
            raise KeyError(
                f"unknown worker {dst!r}; registered: {sorted(self._mailboxes)}"
            ) from None
        box.put(kind, index, payload)

    def is_alive(self, name: str) -> bool:
        """Liveness = still registered (a dead in-process rank unregisters
        via the :func:`worker` context manager's finally block)."""
        return name in self._mailboxes


def _to_host(tree: Payload) -> Payload:
    """Detach to host numpy (the reference stages through CPU the same way,
    torchgpipe/distributed/gpipe.py:176-177)."""
    return jax.tree_util.tree_map(np.asarray, tree)


class _MsgHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        data = b""
        hdr = self._recv_exact(8)
        if hdr is None:
            return
        (length,) = struct.unpack("!Q", hdr)
        data = self._recv_exact(length)
        if data is None:
            return
        kind, index, payload = pickle.loads(data)
        self.server.mailbox.put(kind, index, payload)  # type: ignore[attr-defined]

    def _recv_exact(self, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            chunk = self.request.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf


class TcpTransport:
    """Socket transport between OS processes; one listener per worker.

    ``addresses`` maps every worker name to ``(host, port)``; this worker
    binds its own address and receives into its :class:`Mailbox`.

    ``recorder`` (optional :class:`~torchgpipe_tpu.obs.flightrec.
    FlightRecorder`) is attached to the mailbox (arrival events) and
    records the transport's OWN failure anatomy: every connect-retry
    attempt, the final connect timeout, and a send-timeout — each
    recorded BEFORE its exception is raised, so a dump from a half-dead
    pipeline shows the retry history instead of ending mid-air.

    ``registry`` (optional :class:`~torchgpipe_tpu.obs.registry.
    MetricsRegistry`) adds a ``retries_total{rank}`` counter over the
    same connect-retry attempts, so an elastic supervisor's resize
    decisions and the transport flapping that caused them cross-
    reference one incident.  Retries back off exponentially with
    equal-jitter from :data:`RETRY_BACKOFF_BASE_S`, capped at
    :data:`RETRY_BACKOFF_CAP_S` (see :func:`_retry_sleep_s`).
    """

    def __init__(
        self,
        name: str,
        addresses: Dict[str, Tuple[str, int]],
        *,
        connect_timeout: float = 120.0,
        send_timeout: Optional[float] = None,
        recorder: Optional[Any] = None,
        registry: Optional[Any] = None,
    ) -> None:
        self.name = name
        self.addresses = dict(addresses)
        self.connect_timeout = connect_timeout
        self.send_timeout = send_timeout
        self.recorder = recorder
        # Deterministic per-rank jitter stream (crc32, not hash(): str
        # hashing is salted per process, and two runs of the same rank
        # should back off identically for reproducible traces).
        self._retry_rng = random.Random(zlib.crc32(name.encode("utf-8")))
        self._c_retries = (
            registry.counter(
                "retries_total",
                help="connect-retry attempts by the retrying rank",
                labels=("rank",),
            ) if registry is not None else None
        )
        self.mailbox = Mailbox(name)
        self.mailbox.recorder = recorder
        host, port = self.addresses[name]
        self._server = socketserver.ThreadingTCPServer(
            (host, port), _MsgHandler, bind_and_activate=False
        )
        self._server.allow_reuse_address = True
        self._server.server_bind()
        self._server.server_activate()
        self._server.mailbox = self.mailbox  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()

    def register(self, name: str) -> Mailbox:
        if name != self.name:
            raise ValueError(
                f"TcpTransport for {self.name!r} cannot register {name!r}; "
                "each process owns exactly one worker"
            )
        return self.mailbox

    def send(self, dst: str, kind: Any, index: int, payload: Payload) -> None:
        blob = pickle.dumps(
            (kind, index, _to_host(payload)), protocol=pickle.HIGHEST_PROTOCOL
        )
        host, port = self.addresses[dst]
        # Rendezvous tolerance: ranks are launched by hand in separate
        # shells (see tests/distributed/tcp_rank.py), so the peer's
        # listener may not be up yet — retry refused connections until
        # connect_timeout instead of crashing the first sender.
        deadline = time.monotonic() + self.connect_timeout
        attempt = 0
        while True:
            # Clamp each attempt to the REMAINING deadline budget: a bare
            # 30s per-attempt timeout could overshoot connect_timeout by up
            # to 30s when the last attempt starts just before the deadline
            # (SYNs silently dropped, not refused).
            remaining = deadline - time.monotonic()
            per_attempt = min(30.0, max(remaining, 0.01))
            try:
                sock = socket.create_connection(
                    (host, port), timeout=per_attempt
                )
                break
            except (ConnectionRefusedError, ConnectionResetError,
                    ConnectionAbortedError, socket.timeout) as err:
                # socket.timeout (== TimeoutError) covers peers whose SYNs
                # are dropped (host still booting, lossy link) rather than
                # refused — equally transient during rendezvous.
                # Only genuinely transient rendezvous failures are retried;
                # misconfiguration (bad hostname etc.) raises immediately.
                attempt += 1
                if self._c_retries is not None:
                    self._c_retries.inc(rank=self.name)
                if self.recorder is not None:
                    self.recorder.record(
                        "connect_retry", channel=(kind, index), peer=dst,
                        detail=f"attempt={attempt} {type(err).__name__}",
                    )
                if time.monotonic() >= deadline:
                    if self.recorder is not None:
                        # Final flight event BEFORE raising: the dump of
                        # a rank that died mid-rendezvous must show the
                        # whole retry history, not end mid-air.
                        self.recorder.record(
                            "connect_timeout", channel=(kind, index),
                            peer=dst,
                            detail=f"{attempt} attempts over "
                                   f"{self.connect_timeout}s",
                        )
                    raise TimeoutError(
                        f"worker {self.name!r} could not reach {dst!r} at "
                        f"{host}:{port} within {self.connect_timeout}s — is "
                        "that rank running?"
                    ) from err
                time.sleep(_retry_sleep_s(attempt, self._retry_rng))
        with sock:
            # The connect timeout must not govern the transfer itself
            # (large activation blobs to a busy peer legitimately take
            # longer).  send_timeout (opt-in, like recv_timeout) bounds the
            # TOTAL duration of the transfer — since Python 3.5 a socket
            # timeout on sendall() is the maximum total time to send all
            # data, not a per-write budget — so a wedged peer whose listener
            # stops READING (sendall blocked on a full TCP buffer, the one
            # hang recv_timeout cannot see) and a peer draining at a trickle
            # both trip it.  Size it for your largest blob over your
            # slowest link.
            sock.settimeout(self.send_timeout)
            try:
                sock.sendall(struct.pack("!Q", len(blob)) + blob)
            except socket.timeout:
                if self.recorder is not None:
                    self.recorder.record(
                        "send_timeout", channel=(kind, index), peer=dst,
                        detail=f"{len(blob)} bytes, "
                               f"send_timeout={self.send_timeout}s",
                    )
                raise TimeoutError(
                    f"worker {self.name!r}: send of {len(blob)} bytes to "
                    f"{dst!r} did not complete within {self.send_timeout}s "
                    "— is that rank still consuming?"
                ) from None

    def is_alive(self, name: str, *, probe_timeout: float = 2.0) -> bool:
        """Liveness probe: can ``name``'s listener accept a connection?

        Used by :class:`~torchgpipe_tpu.distributed.gpipe.DistributedGPipe`
        to turn a receive timeout into a :class:`PeerDiedError` naming the
        rank when the peer is confirmed gone (connection refused/ignored),
        rather than merely busy.  A connected-then-closed probe is
        harmless to the peer: its handler reads a length header, sees EOF,
        and returns (see ``_MsgHandler.handle``).
        """
        if name == self.name:
            return True
        host, port = self.addresses[name]
        try:
            with socket.create_connection((host, port), timeout=probe_timeout):
                return True
        except OSError:
            return False

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


@contextlib.contextmanager
def worker(transport: Any, name: str) -> Iterator[Mailbox]:
    """Register a worker mailbox for the duration of a training run.

    Reference: torchgpipe/distributed/context.py:41-64 (``worker`` context
    manager / ``@distributed`` decorator).
    """
    box = transport.register(name)
    try:
        yield box
    finally:
        unregister = getattr(transport, "unregister", None)
        if unregister is not None:
            unregister(name)

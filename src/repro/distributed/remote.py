"""Multi-process C²MPI: remote virtualization agents over a socket
transport (DESIGN.md §13).

Everything else in this repo is single-process multi-substrate; this module
extends the agent pool across OS processes while keeping the host program
unchanged.  Three pieces:

* :func:`spawn_worker` / :class:`WorkerRuntime` — launch a worker process
  (``python -m repro.launch.worker``) that builds its **own** runtime
  session (registry + agents + scheduler + TuningDB from the inherited
  ``HALO_*`` env) over ``N`` emulated host devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and serves
  requests over a length-prefixed frame protocol on a localhost socket.
* :class:`WorkerClient` — the host-side transport: a writer lock plus one
  reader thread that resolves per-request :class:`~repro.core.agents
  .HaloFuture`\\ s as result frames stream back (results arrive as
  done-callbacks, never by blocking the transport).
* :class:`RemoteAgent` — a :class:`~repro.core.agents.VirtualizationAgent`
  proxy for one substrate of one worker.  On :meth:`RemoteAgent.attach` it
  republishes the worker's kernel records under its remote platform id
  (``"xla@w0"``) via :func:`~repro.core.registry.clone_record`, so the
  *existing* selection, scheduling, collective-pinning, and failover
  machinery treats the worker as just another member substrate:
  ``MPIX_CommSplit(["xla", "xla@w0"])`` mixes in-process and remote members
  with no new verbs.

Failure semantics (DESIGN.md §11/§13): a dead worker process surfaces both
promptly (transport EOF -> ``handle_dead_agent``) and via the heartbeat
path (a busy RemoteAgent whose transport died reports an infinitely-stale
heartbeat, so a :class:`~repro.core.agents.HealthMonitor` sweep classifies
it DEAD), and flows into the normal mark-dead -> comm-repair -> replay
ladder.  The agent's cloned records are deregistered inside
:meth:`RemoteAgent.mark_dead`, so replayed work re-places onto survivors —
ending at the registry fail-safe — bit-identically to a single-process run.

What is NOT shipped across the wire: callables (records are mirrored by
alias/platform/priority/version, never by function), ``BufferHandle``
tables (stateful-CR state ships **by value** per request), jax tracers,
graph nodes (payloads are materialized before send), and scheduler/
TuningDB objects (workers build their own from the inherited env paths;
quarantine keys are the only scheduler state that crosses, see
:meth:`~repro.core.scheduler.CostModelScheduler.mark_failed_key`).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import socket
import struct
import subprocess
import sys
import threading
import time
import weakref
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.agents import HaloFuture, VirtualizationAgent
from ..core.config import halo_config
from ..core.registry import KernelRecord, clone_record

log = logging.getLogger("repro.halo.remote")

__all__ = [
    "RemoteAgent",
    "RemoteExecutionError",
    "RemoteWorker",
    "RemoteWorkerError",
    "WorkerClient",
    "WorkerRuntime",
    "decode_payload",
    "encode_payload",
    "recv_frame",
    "send_frame",
    "spawn_worker",
]


class RemoteWorkerError(RuntimeError):
    """Transport-layer failure: the worker process died or the socket
    closed with requests still pending."""


class RemoteExecutionError(RuntimeError):
    """A kernel execution failed inside the worker process.  Carries the
    worker-side exception type and message (the traceback object itself
    never crosses the wire)."""


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------
# A frame is ``[u64 total_len][u32 header_len][header JSON][buf 0][buf 1]…``
# (big-endian).  The header is the message pytree with every array leaf
# replaced by an ``{"__a__": index, "s": shape, "d": dtype}`` marker; the
# raw array bytes follow the header in marker order.  Arrays round-trip
# dtype-exactly — including bfloat16, whose dtype lives in ``ml_dtypes``
# rather than numpy proper.
#
# Host -> worker frames may additionally use the content-addressed buffer
# cache: a large *immutable* array (a ``jax.Array`` of at least
# ``HALO_WIRE_CACHE_MIN`` bytes) ships once as ``{"__a__": …, "put":
# digest}`` — the worker pins the decoded bytes under the digest — and
# every later occurrence travels as a bufferless ``{"__aref__": digest,
# "s": shape, "d": dtype}`` marker.  Misses are impossible by
# construction: the host stops promising new digests once
# ``HALO_WIRE_CACHE_MB`` worth are pinned (further arrays ship raw), and
# the worker never evicts a pinned buffer, so no miss/retry round trip
# exists in the protocol.  Mutable arrays (plain numpy) always ship raw —
# a digest memo keyed by object identity cannot see in-place writes.

_MAX_FRAME = 1 << 33            # 8 GiB sanity bound on a single frame


def _resolve_dtype(name: str) -> np.dtype:
    """``np.dtype`` by name, falling back to ``ml_dtypes`` for the extended
    float types (bfloat16, float8_*) jax uses."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes                     # ships with jax
        return np.dtype(getattr(ml_dtypes, name))


_digest_lock = threading.Lock()
#: id(array) -> (weakref, digest) — valid only while the weakref still
#: resolves to the *same* object (guards against id() reuse after gc, the
#: same discipline as ``fusion._callable_uid``)
_digest_memo: Dict[int, Tuple[Any, str]] = {}


def _digest_of(obj: Any, arr: np.ndarray) -> str:
    """Content digest of an immutable array, memoized by object identity
    so a matrix reused across thousands of dispatches is hashed once."""
    key = id(obj)
    with _digest_lock:
        ent = _digest_memo.get(key)
        if ent is not None and ent[0]() is obj:
            return ent[1]
    view = arr if arr.flags["C_CONTIGUOUS"] else np.ascontiguousarray(arr)
    digest = hashlib.blake2b(view, digest_size=16).hexdigest()
    with _digest_lock:
        if len(_digest_memo) > 4096:        # prune dead weakrefs, bounded
            for k in [k for k, e in _digest_memo.items() if e[0]() is None]:
                del _digest_memo[k]
        try:
            _digest_memo[key] = (weakref.ref(obj), digest)
        except TypeError:
            pass                            # not weakref-able: just re-hash
    return digest


class _WireCache:
    """Host-side ledger of buffers pinned inside one worker.

    Only *immutable* arrays (``jax.Array``) of at least ``min_bytes`` are
    eligible; the ledger stops promising new digests once ``cap_bytes``
    are pinned worker-side, so the worker's pin store is bounded by the
    same cap and can never miss.  ``offer`` runs under the client's write
    lock (one frame encodes at a time); ``commit``/``rollback`` settle a
    frame's new digests after the send succeeds or fails."""

    def __init__(self) -> None:
        hc = halo_config()
        self.enabled = hc.wire_cache
        self.min_bytes = hc.wire_cache_min
        self.cap_bytes = hc.wire_cache_mb * (1 << 20)
        self.known: set = set()
        self.pinned_bytes = 0
        self.bytes_sent = 0                 # every frame byte written
        self.bytes_saved = 0                # raw bytes elided by __aref__
        self._frame_new: List[Tuple[str, int]] = []

    def offer(self, obj: Any, arr: np.ndarray) -> Optional[Tuple[str, str]]:
        """('ref'|'put', digest) when the cache applies, else None."""
        if not self.enabled or arr.nbytes < self.min_bytes:
            return None
        import jax
        if not isinstance(obj, jax.Array):
            return None                     # mutable buffers ship raw
        digest = _digest_of(obj, arr)
        if digest in self.known:
            self.bytes_saved += arr.nbytes
            return "ref", digest
        new_bytes = self.pinned_bytes + sum(n for _, n in self._frame_new)
        if new_bytes + arr.nbytes > self.cap_bytes:
            return None                     # over cap: raw, never promised
        self._frame_new.append((digest, arr.nbytes))
        return "put", digest

    def commit(self) -> None:
        for digest, nbytes in self._frame_new:
            if digest not in self.known:
                self.known.add(digest)
                self.pinned_bytes += nbytes
        self._frame_new = []

    def rollback(self) -> None:
        self._frame_new = []

    def stats(self) -> Dict[str, int]:
        return {"bytes_sent": self.bytes_sent,
                "bytes_saved": self.bytes_saved,
                "pinned_buffers": len(self.known),
                "pinned_bytes": self.pinned_bytes}


def _enc(obj: Any, bufs: List[bytes],
         cache: Optional[_WireCache] = None) -> Any:
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, BaseException):
        return {"__e__": [type(obj).__name__, str(obj)]}
    if isinstance(obj, tuple):
        return {"__t__": [_enc(v, bufs, cache) for v in obj]}
    if isinstance(obj, list):
        return [_enc(v, bufs, cache) for v in obj]
    if isinstance(obj, dict):
        return {"__d__": [[_enc(k, bufs, cache), _enc(v, bufs, cache)]
                          for k, v in obj.items()]}
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):
        # note: tobytes() always emits C-order, and (unlike
        # ascontiguousarray) np.asarray keeps 0-d scalars 0-d
        arr = np.asarray(obj)
        offer = cache.offer(obj, arr) if cache is not None else None
        if offer is not None and offer[0] == "ref":
            return {"__aref__": offer[1], "s": list(arr.shape),
                    "d": str(arr.dtype)}
        idx = len(bufs)
        bufs.append(arr.tobytes())
        mark = {"__a__": idx, "s": list(arr.shape), "d": str(arr.dtype)}
        if offer is not None:               # ("put", digest)
            mark["put"] = offer[1]
        return mark
    raise TypeError(
        f"cannot serialize {type(obj).__name__!r} across the worker "
        f"transport (callables, handles and tracers never cross the wire)")


def _dec(obj: Any, bufs: Sequence[bytes],
         store: Optional[Dict[str, np.ndarray]] = None) -> Any:
    if isinstance(obj, list):
        return [_dec(v, bufs, store) for v in obj]
    if isinstance(obj, dict):
        if "__a__" in obj:
            dt = _resolve_dtype(obj["d"])
            arr = np.frombuffer(bufs[obj["__a__"]], dtype=dt)
            arr = arr.reshape(obj["s"]).copy()
            if store is not None and "put" in obj:
                arr.flags.writeable = False  # pinned: shared across requests
                store[obj["put"]] = arr
            return arr
        if "__aref__" in obj:
            if store is None or obj["__aref__"] not in store:
                raise RemoteWorkerError(
                    f"frame references unpinned buffer {obj['__aref__']}")
            return store[obj["__aref__"]]
        if "__t__" in obj:
            return tuple(_dec(v, bufs, store) for v in obj["__t__"])
        if "__d__" in obj:
            return {_dec(k, bufs, store): _dec(v, bufs, store)
                    for k, v in obj["__d__"]}
        if "__e__" in obj:
            return RemoteExecutionError(f"{obj['__e__'][0]}: {obj['__e__'][1]}")
    return obj


def encode_payload(obj: Any,
                   cache: Optional[_WireCache] = None) -> Tuple[Any, List[bytes]]:
    """Encode a message pytree into (JSON-safe header tree, array buffers).

    Supported leaves: None/bool/int/float/str, exceptions (by type name +
    message), and anything array-like (numpy/jax arrays, 0-d scalars) —
    shipped as raw bytes with shape/dtype preserved bit-exactly, bfloat16
    included.  Tuples and dicts survive as tuples and dicts.  With a
    ``cache``, eligible immutable arrays the peer already pins are elided
    into ``__aref__`` digest markers (see the wire-format notes above)."""
    bufs: List[bytes] = []
    return _enc(obj, bufs, cache), bufs


def decode_payload(header: Any, bufs: Sequence[bytes],
                   store: Optional[Dict[str, np.ndarray]] = None) -> Any:
    """Inverse of :func:`encode_payload`; arrays come back as numpy.
    ``store`` is the receiver's digest -> pinned-array dict serving
    ``put``/``__aref__`` markers (worker side only)."""
    return _dec(header, bufs, store)


def send_frame(sock: socket.socket, msg: Any,
               lock: Optional[threading.Lock] = None,
               cache: Optional[_WireCache] = None) -> None:
    """Serialize ``msg`` (a pytree, arrays allowed) and write one frame.
    With a ``cache``, encode + send + digest-commit run as one locked
    critical section so concurrent requests cannot interleave promises."""
    if lock is None:
        lock = threading.Lock()
    with lock:
        header, bufs = encode_payload(msg, cache)
        hdr = json.dumps({"m": header, "b": [len(b) for b in bufs]}).encode()
        total = 4 + len(hdr) + sum(len(b) for b in bufs)  # after the u64
        data = b"".join([struct.pack(">QI", total, len(hdr)), hdr, *bufs])
        try:
            sock.sendall(data)
        except BaseException:
            if cache is not None:
                cache.rollback()
            raise
        if cache is not None:
            cache.commit()
            cache.bytes_sent += len(data)


def _read_exact(rfile, n: int) -> bytes:
    data = rfile.read(n)
    if data is None or len(data) != n:
        raise EOFError("worker transport closed")
    return data


def recv_frame(rfile, store: Optional[Dict[str, np.ndarray]] = None) -> Any:
    """Read and decode one frame from a ``makefile('rb')`` stream.
    Raises :class:`EOFError` on a closed transport.  ``store`` is the
    receiver's pinned-buffer dict (see :func:`decode_payload`)."""
    total, hdr_len = struct.unpack(">QI", _read_exact(rfile, 12))
    if not 4 <= total <= _MAX_FRAME or hdr_len > total:
        raise RemoteWorkerError(f"corrupt frame (len={total})")
    hdr = json.loads(_read_exact(rfile, hdr_len))
    bufs = [_read_exact(rfile, n) for n in hdr["b"]]
    return decode_payload(hdr["m"], bufs, store)


# ---------------------------------------------------------------------------
# Host-side transport
# ---------------------------------------------------------------------------
class WorkerClient:
    """Request/response multiplexer over one worker socket.

    Writes are serialized by a lock; one reader thread matches reply frames
    to pending request futures by uid and resolves them — streamed results
    land as :class:`HaloFuture` done-callbacks, so N in-flight requests to
    one worker never block each other on the host side.

    On EOF (worker death) the death callbacks run **first** — so the
    session can mark the agent dead and hand its in-flight items to the
    replay ladder — and only then are pending transport futures failed
    (waking blocked worker threads into an already-dead agent, whose
    ``_fail_item`` discards the transport error instead of racing the
    replayed result)."""

    def __init__(self, sock: socket.socket, name: str = "worker"):
        self.name = name
        self._sock = sock
        self._rfile = sock.makefile("rb")
        self.cache = _WireCache()
        self._wlock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: Dict[int, Tuple[HaloFuture, Any]] = {}
        self._uid = 0
        self._dead = False
        self._dead_reason = ""
        self._closing = False
        self._death_callbacks: List[Callable[[str], None]] = []
        self._reader = threading.Thread(
            target=self._read_loop, name=f"{name}-reader", daemon=True)
        self._reader.start()

    # -- request side --------------------------------------------------------
    def request(self, op: str, owner: Any = None, **fields: Any) -> HaloFuture:
        """Send one op frame; returns the future its reply will resolve."""
        fut = HaloFuture(alias=op)
        with self._lock:
            if self._dead:
                raise RemoteWorkerError(
                    f"worker {self.name} is gone ({self._dead_reason})")
            self._uid += 1
            uid = self._uid
            self._pending[uid] = (fut, owner)
        try:
            send_frame(self._sock, dict(fields, op=op, uid=uid), self._wlock,
                       cache=self.cache)
        except OSError as exc:
            with self._lock:
                self._pending.pop(uid, None)
            self._on_eof(f"send failed: {exc}")
            raise RemoteWorkerError(str(exc)) from exc
        return fut

    def call(self, op: str, owner: Any = None,
             timeout: Optional[float] = None, **fields: Any) -> Dict[str, Any]:
        """Blocking request: returns the reply dict, raising the decoded
        worker-side exception for error replies."""
        reply = self.request(op, owner=owner, **fields).result(timeout=timeout)
        exc = reply.get("exc")
        if exc is not None:
            raise exc if isinstance(exc, BaseException) \
                else RemoteExecutionError(str(exc))
        return reply

    def pending_count(self) -> int:
        """Number of requests awaiting replies (test/diagnostic hook)."""
        with self._lock:
            return len(self._pending)

    def wire_stats(self) -> Dict[str, int]:
        """Transport counters: bytes written, raw bytes elided by the
        buffer cache, and what the worker currently pins."""
        return self.cache.stats()

    # -- reply side ----------------------------------------------------------
    def _read_loop(self) -> None:
        try:
            while True:
                msg = recv_frame(self._rfile)
                uid = msg.get("uid")
                with self._lock:
                    ent = self._pending.pop(uid, None)
                if ent is not None:
                    ent[0].set_result(msg)
                elif uid is not None:
                    log.debug("reply for unknown uid %s from %s (aborted "
                              "request?)", uid, self.name)
        except (EOFError, OSError, RemoteWorkerError, ValueError) as exc:
            self._on_eof(str(exc) or type(exc).__name__)

    def on_death(self, callback: Callable[[str], None]) -> None:
        """Register ``callback(reason)`` to run once when the transport
        dies unexpectedly (not on a graceful :meth:`close`)."""
        with self._lock:
            self._death_callbacks.append(callback)

    def _on_eof(self, reason: str) -> None:
        with self._lock:
            if self._dead:
                return
            self._dead = True
            self._dead_reason = reason
            callbacks = list(self._death_callbacks) \
                if not self._closing else []
        # death callbacks BEFORE failing pending futures: see class docstring
        for cb in callbacks:
            try:
                cb(reason)
            except Exception:
                log.exception("worker death callback raised")
        self._fail_pending(None, reason)

    def _fail_pending(self, owner: Any, reason: str) -> None:
        with self._lock:
            if owner is None:
                failed = list(self._pending.values())
                self._pending.clear()
            else:
                failed = [ent for ent in self._pending.values()
                          if ent[1] is owner]
                self._pending = {u: ent for u, ent in self._pending.items()
                                 if ent[1] is not owner}
        for fut, _owner in failed:
            fut.set_exception(RemoteWorkerError(
                f"worker {self.name} died with request in flight ({reason})"))

    def abort_for(self, owner: Any, reason: str = "agent shut down") -> None:
        """Fail this owner's pending requests (late replies are dropped by
        the reader) — unblocks an agent's worker thread at shutdown."""
        self._fail_pending(owner, reason)

    @property
    def dead(self) -> bool:
        return self._dead

    def close(self) -> None:
        """Graceful close: no death callbacks, pending requests fail."""
        with self._lock:
            self._closing = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        self._on_eof("closed")


# ---------------------------------------------------------------------------
# Remote agent proxy
# ---------------------------------------------------------------------------
class RemoteAgent(VirtualizationAgent):
    """Proxy one substrate of a worker process behind the standard agent
    interface.  Inherits the per-agent FIFO worker queue (submissions to
    one remote member serialize in order, members overlap) and the
    heartbeat contract; ``_device_execute`` ships (alias, args, kwargs)
    across the wire instead of calling ``record.fn``.

    The platform id is ``"<substrate>@<worker>"`` (e.g. ``"xla@w0"``):
    distinct from every local substrate, so device groups pin ranks to it,
    the scheduler keeps per-remote-member estimate tables (host-side EMAs
    include the wire cost — honest end-to-end latency), and quarantine is
    per-member."""

    def __init__(self, worker: "RemoteWorker", substrate: str = "xla"):
        self.platform = f"{substrate}@{worker.name}"
        super().__init__(name=f"remote-{substrate}-{worker.name}")
        self._worker_handle = worker
        self._substrate = substrate
        self._session = None
        self._clones: List[KernelRecord] = []
        self._applied_quarantine: set = set()
        self._timeout = halo_config().remote_timeout

    # -- session wiring ------------------------------------------------------
    def attach(self, session) -> "RemoteAgent":
        """Join a session: register as an agent and republish the worker's
        kernel records under this platform id (fresh uids, never failsafe —
        the jnp reference must stay the only failsafe so dead-member
        replays land on a local substrate)."""
        self._session = session
        for alias in list(session.registry.aliases()):
            for rec in session.registry.records(alias):
                if rec.platform != self._substrate:
                    continue
                clone = clone_record(rec, platform=self.platform,
                                     is_failsafe=False)
                session.registry.register(clone)
                self._clones.append(clone)
        session.attach_agent(self)
        return self

    def _deregister_clones(self) -> None:
        if self._session is None:
            return
        for rec in self._clones:
            try:
                self._session.registry.deregister(rec.alias, rec.platform)
            except Exception:
                log.exception("deregistering clone %s/%s failed",
                              rec.alias, rec.platform)
        self._clones = []

    # -- agent contract ------------------------------------------------------
    def available(self) -> bool:
        return not self._dead and not self._worker_handle.dead

    def heartbeat(self) -> Tuple[int, bool, float]:
        beats, busy, last = super().heartbeat()
        if busy and self._worker_handle.dead:
            # a busy member whose process died can never beat again: report
            # an infinitely stale heartbeat so the next monitor sweep
            # classifies DEAD regardless of the configured timeout
            return beats, True, float("-inf")
        return beats, busy, last

    def _fail_item(self, fut: HaloFuture, exc: BaseException) -> None:
        if self._dead and isinstance(exc, RemoteWorkerError):
            # mark_dead already handed this item to the replay ladder; the
            # transport error waking this thread must not outrace it
            log.debug("dropping transport error on dead agent %s: %s",
                      self.name, exc)
            return
        super()._fail_item(fut, exc)

    def mark_dead(self, reason: str = "declared dead") -> List[tuple]:
        """Dead-member teardown, ordered so the replay ladder sees a
        consistent registry: collect queue items (super), deregister the
        record clones (re-placement falls through to local records / the
        jnp fail-safe), then abort in-flight transport calls (their worker
        threads wake into ``_fail_item``'s discard path)."""
        items = super().mark_dead(reason)
        self._deregister_clones()
        self._worker_handle.client.abort_for(self, reason)
        return items

    def shutdown(self, cancel_pending: bool = True, wait: bool = True) -> None:
        self._worker_handle.client.abort_for(self, "agent shutdown")
        super().shutdown(cancel_pending=cancel_pending, wait=wait)

    # -- execution -----------------------------------------------------------
    def _device_execute(self, record: KernelRecord, args: Tuple, kwargs: Dict):
        reply = self._worker_handle.client.call(
            "exec", owner=self, timeout=self._timeout,
            alias=record.alias, platform=self._substrate,
            priority=record.priority, verid=record.attrs.sw_verid,
            args=list(args), kwargs=kwargs)
        self._apply_quarantine(reply.get("quarantined") or ())
        return reply.get("result")

    def _apply_quarantine(self, keys: Sequence[str]) -> None:
        """Propagate worker-side quarantine to the host scheduler: a worker
        key ``alias|<substrate>|prio:ver`` maps onto this member's clone key
        ``alias|<substrate>@<worker>|prio:ver`` — so host re-placement stops
        picking a record that only fails inside the worker (DESIGN.md §13)."""
        sess = self._session
        if sess is None or sess.scheduler is None:
            return
        for key in keys:
            if key in self._applied_quarantine:
                continue
            self._applied_quarantine.add(key)
            parts = key.split("|")
            if len(parts) == 3 and parts[1] == self._substrate:
                host_key = f"{parts[0]}|{self.platform}|{parts[2]}"
                log.warning("worker %s quarantined %s; quarantining %s "
                            "host-side", self._worker_handle.name, key,
                            host_key)
                sess.scheduler.mark_failed_key(host_key)


# ---------------------------------------------------------------------------
# Worker process handle
# ---------------------------------------------------------------------------
class RemoteWorker:
    """Host-side handle to one spawned worker process: owns the transport
    client and the process, and vends :class:`RemoteAgent` proxies (one per
    substrate — a single worker can back several remote members)."""

    def __init__(self, proc: Optional[subprocess.Popen],
                 client: WorkerClient, name: str,
                 platforms: Sequence[str], devices: int):
        self.proc = proc
        self.client = client
        self.name = name
        self.platforms = tuple(platforms)
        self.devices = devices
        self._agents: Dict[str, RemoteAgent] = {}
        client.on_death(self._on_death)

    @property
    def dead(self) -> bool:
        return self.client.dead

    def agent(self, substrate: str = "xla") -> RemoteAgent:
        """The :class:`RemoteAgent` proxy for one of this worker's
        substrates (cached — one proxy per substrate)."""
        if substrate not in self.platforms:
            raise ValueError(f"worker {self.name} does not serve "
                             f"{substrate!r} (has {self.platforms})")
        if substrate not in self._agents:
            self._agents[substrate] = RemoteAgent(self, substrate)
        return self._agents[substrate]

    def _on_death(self, reason: str) -> None:
        # prompt path (the heartbeat path also works, but needs a monitor
        # sweep): EOF on the transport declares every attached proxy dead
        # and replays its queue through the session ladder
        for agent in list(self._agents.values()):
            sess = agent._session
            if sess is None or agent.dead:
                continue
            if sess.agents.get(agent.platform) is not agent:
                continue
            try:
                sess.handle_dead_agent(
                    agent, reason=f"worker process died ({reason})")
            except Exception:
                log.exception("handle_dead_agent failed for %s", agent.name)

    def heartbeat(self) -> Dict[str, Any]:
        """Worker-side liveness snapshot (``ping`` round trip)."""
        return self.client.call("ping")

    def chaos(self, **plan: Any) -> None:
        """Install a serialized :class:`~repro.testing.faults.FaultPlan`
        inside the worker (test harness; fields: platform, mode, nth,
        times, delay_s, aliases)."""
        self.client.call("chaos", plan=plan)

    def release(self) -> None:
        """Release worker-side fault injection (unblocks hang modes)."""
        self.client.call("release")

    def kill(self) -> None:
        """Hard-kill the worker process (fault-injection path: the
        transport EOF fires the dead-agent ladder)."""
        if self.proc is not None:
            self.proc.kill()

    def shutdown(self, timeout: float = 10.0) -> None:
        """Graceful stop: ask the worker to finalize, close the transport
        (no death callbacks), reap the process."""
        try:
            self.client.call("shutdown", timeout=timeout)
        except (RemoteWorkerError, TimeoutError, OSError):
            pass
        self.client.close()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=timeout)


def _src_root() -> str:
    # repro is a namespace package (__file__ is None): resolve via __path__
    import repro
    return str(Path(list(repro.__path__)[0]).resolve().parent)


def spawn_worker(name: str = "w0", devices: Optional[int] = None,
                 platforms: Sequence[str] = ("xla", "jnp"),
                 timeout: Optional[float] = None,
                 env: Optional[Dict[str, str]] = None) -> RemoteWorker:
    """Launch ``python -m repro.launch.worker`` and connect it back.

    The child emulates ``devices`` host devices (SNIPPETS.md 2-3:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must be
    set before jax imports — hence a fresh process, not a fork) and serves
    the given substrates.  The parent's environment is inherited — so
    ``HALO_TUNING_DB`` / ``HALO_AUTOTUNE_CACHE`` give workers the same
    tuned-config and warm-start tables as the host — with transport
    details overridden by ``env``.  The child always runs JAX on the CPU
    (``JAX_PLATFORMS=cpu``, whatever the parent or ``env`` says): a parent
    that has touched JAX holds its accelerator, and a second process that
    asks for it fails or hangs.  Blocks until the worker's hello frame
    (default budget ``HALO_WORKER_TIMEOUT``, 120 s: the child pays a full
    jax import)."""
    devices = devices if devices is not None \
        else halo_config().worker_devices
    timeout = timeout if timeout is not None \
        else halo_config().worker_timeout
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    listener.settimeout(timeout)
    port = listener.getsockname()[1]
    child_env = dict(os.environ)
    xla_flags = child_env.get("XLA_FLAGS", "")
    child_env["XLA_FLAGS"] = (
        f"{xla_flags} --xla_force_host_platform_device_count={devices}"
        .strip())
    child_env["PYTHONPATH"] = os.pathsep.join(
        p for p in [_src_root(), child_env.get("PYTHONPATH", "")] if p)
    if env:
        child_env.update(env)
    child_env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.launch.worker",
           "--connect", f"127.0.0.1:{port}", "--name", name,
           "--platforms", ",".join(platforms), "--devices", str(devices)]
    proc = subprocess.Popen(cmd, env=child_env)
    try:
        conn, _addr = listener.accept()
    except socket.timeout:
        proc.kill()
        raise RemoteWorkerError(
            f"worker {name} did not connect within {timeout}s "
            f"(exit code {proc.poll()})") from None
    finally:
        listener.close()
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    client = WorkerClient(conn, name=name)
    hello = client.request("hello").result(timeout=timeout)
    return RemoteWorker(proc, client, name,
                        platforms=hello.get("platforms", platforms),
                        devices=hello.get("devices", devices))


# ---------------------------------------------------------------------------
# Worker-side runtime
# ---------------------------------------------------------------------------
class WorkerRuntime:
    """The serving loop inside a worker process: builds a private runtime
    session (``kernels.register_all()`` + a fresh
    :class:`~repro.core.agents.RuntimeAgent`, so scheduler/quarantine state
    is process-local by construction) and serves frames until EOF or a
    ``shutdown`` op.

    ``exec`` requests resolve the named record (alias + platform +
    priority + version — the host's clone mirrors these), then run through
    ``session._execute_record`` **asynchronously** on the substrate
    agent's own worker queue: the reader thread never blocks on a kernel,
    in-flight requests to one substrate serialize in order (matching the
    host proxy's FIFO), and the full quarantine -> re-place -> fail-safe
    ladder applies worker-side before an error ever crosses the wire.
    Every reply carries the scheduler's current quarantined record keys so
    the host can mirror them (DESIGN.md §13)."""

    def __init__(self, sock: socket.socket, name: str = "w0",
                 platforms: Sequence[str] = ("xla", "jnp")):
        import jax
        from .. import kernels
        from ..core.agents import RuntimeAgent
        kernels.register_all()
        self._sock = sock
        self._rfile = sock.makefile("rb")
        self._wlock = threading.Lock()
        self.name = name
        self.session = RuntimeAgent()
        self.platforms = tuple(p for p in platforms
                               if p in self.session.agents)
        self.devices = jax.local_device_count()
        self._crs: Dict[str, Any] = {}
        self._chaos: Dict[str, tuple] = {}   # platform -> (faulty, original)
        #: digest -> pinned read-only array serving ``__aref__`` markers;
        #: bounded by the host ledger's HALO_WIRE_CACHE_MB, never evicted
        self._pins: Dict[str, np.ndarray] = {}
        self._stop = False

    # -- serving -------------------------------------------------------------
    def serve(self) -> None:
        """Block serving frames until the host disconnects or asks for
        shutdown; finalizes the session on the way out."""
        log.info("worker %s serving %s over %d device(s)", self.name,
                 self.platforms, self.devices)
        try:
            while not self._stop:
                try:
                    msg = recv_frame(self._rfile, store=self._pins)
                except (EOFError, OSError):
                    break
                try:
                    self._handle(msg)
                except Exception as exc:  # noqa: BLE001 — reply, keep serving
                    log.exception("worker %s: %r failed", self.name,
                                  msg.get("op"))
                    self._reply(msg.get("uid"), exc=exc)
        finally:
            self._release_chaos()
            try:
                self.session.finalize()
            except Exception:
                log.exception("worker %s finalize failed", self.name)

    def _reply(self, uid: Optional[int], **fields: Any) -> None:
        if uid is None:
            return
        msg = dict(fields, uid=uid,
                   quarantined=self._quarantined_keys())
        try:
            send_frame(self._sock, msg, self._wlock)
        except (OSError, TypeError) as exc:
            if isinstance(exc, TypeError) and "result" in fields:
                # unserializable result: report instead of dying silently
                self._reply(uid, exc=exc)
            else:
                log.warning("worker %s could not reply to %s: %s",
                            self.name, uid, exc)

    def _quarantined_keys(self) -> List[str]:
        sched = self.session.scheduler
        return sched.failed_record_keys() if sched is not None else []

    # -- ops -----------------------------------------------------------------
    def _handle(self, msg: Dict[str, Any]) -> None:
        op, uid = msg.get("op"), msg.get("uid")
        if op == "exec":
            self._handle_exec(msg)
        elif op in ("hello", "ping"):
            busy = any(a.heartbeat()[1] for a in self.session.agents.values())
            self._reply(uid, name=self.name, platforms=list(self.platforms),
                        devices=self.devices, busy=busy,
                        pins=len(self._pins),
                        aliases=self.session.registry.aliases())
        elif op == "chaos":
            self._install_chaos(msg.get("plan") or {})
            self._reply(uid, ok=True)
        elif op == "release":
            self._release_chaos()
            self._reply(uid, ok=True)
        elif op == "shutdown":
            self._stop = True
            self._reply(uid, ok=True)
        else:
            self._reply(uid, exc=ValueError(f"unknown op {op!r}"))

    def _find_record(self, alias: str, platform: str, priority: Any,
                     verid: Any) -> Optional[KernelRecord]:
        for rec in self.session.registry.records(alias):
            if rec.platform == platform \
                    and (priority is None or rec.priority == priority) \
                    and (verid is None or rec.attrs.sw_verid == verid):
                return rec
        return None

    def _cr_for(self, alias: str, platform: str):
        key = f"{alias}|{platform}"
        cr = self._crs.get(key)
        if cr is None:
            cr = self.session.claim(alias, overrides={
                "allowed_platforms": [platform],
                "platform_preference": [platform]})
            self._crs[key] = cr
        return cr

    def _handle_exec(self, msg: Dict[str, Any]) -> None:
        uid = msg.get("uid")
        alias, platform = msg["alias"], msg.get("platform", "xla")
        args = tuple(msg.get("args") or ())
        kwargs = msg.get("kwargs") or {}
        agent = self.session.agents.get(platform)
        if agent is None:
            self._reply(uid, exc=ValueError(
                f"worker {self.name} has no {platform!r} agent"))
            return
        rec = self._find_record(alias, platform, msg.get("priority"),
                                msg.get("verid"))
        cr = self._cr_for(alias, platform)
        if rec is None:
            try:
                rec = self.session._select(alias, args, cr.overrides)
            except Exception as exc:  # noqa: BLE001 — report, keep serving
                self._reply(uid, exc=exc)
                return
        fut = HaloFuture(alias=alias)
        sess = self.session

        def _reply_done(f: HaloFuture, uid=uid) -> None:
            try:
                self._reply(uid, result=f.result())
            except BaseException as exc:  # noqa: BLE001 — ship error back
                self._reply(uid, exc=exc)

        fut.add_done_callback(_reply_done)
        try:
            agent.submit(lambda: sess._execute_record(rec, cr, args, kwargs),
                         future=fut)
        except Exception as exc:  # noqa: BLE001 — agent dead/shut down
            fut.set_exception(exc)

    # -- fault injection (test harness) --------------------------------------
    def _install_chaos(self, plan: Dict[str, Any]) -> None:
        from ..testing.faults import FaultPlan, FaultyAgent
        platform = plan.get("platform", "xla")
        self._release_chaos(platform)
        fp = FaultPlan(
            platform=platform, mode=plan.get("mode", "raise"),
            nth=plan.get("nth", 1), times=plan.get("times"),
            delay_s=plan.get("delay_s", 0.0),
            aliases=tuple(plan["aliases"]) if plan.get("aliases") else None)
        original = self.session.agents.get(platform)
        faulty = FaultyAgent(fp)
        self.session.attach_agent(faulty)
        self._chaos[platform] = (faulty, original)
        log.warning("worker %s: chaos installed on %s (%s)", self.name,
                    platform, fp.mode)

    def _release_chaos(self, platform: Optional[str] = None) -> None:
        targets = [platform] if platform else list(self._chaos)
        for p in targets:
            ent = self._chaos.pop(p, None)
            if ent is None:
                continue
            faulty, original = ent
            try:
                faulty.release()
            except Exception:
                log.exception("chaos release failed on %s", p)
            if original is not None:
                self.session.attach_agent(original)
        if self.session.scheduler is not None and targets:
            self.session.scheduler.clear_failures()


def connect_and_serve(address: str, name: str,
                      platforms: Sequence[str]) -> None:
    """Worker-process entry: dial the host and serve until disconnect
    (used by ``repro.launch.worker``)."""
    host, port = address.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    WorkerRuntime(sock, name=name, platforms=platforms).serve()


# make time importable-patchable for tests without a hard dependency here
_ = time

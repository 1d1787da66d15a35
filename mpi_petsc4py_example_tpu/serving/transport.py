"""Message-based RPC transport for the multi-host fleet tier.

The fleet router (serving/fleet.py) was built against an in-process
replica interface; this module is the wire underneath it, so a replica
can live in another process (or, in CI, behind a deterministic loopback
that still exercises every failure mode). The design carries the three
guarantees ROADMAP item 2(a) needs from a transport, each enforced here
rather than hoped for at call sites:

1. **Every call has a deadline.** :meth:`RpcClient.call` takes a
   ``deadline`` budget (defaulting to ``-rpc_deadline_s``) and divides
   it across send attempts; no code path blocks forever. tpslint rule
   TPS019 pins the discipline repo-wide: a transport call site without a
   deadline/timeout argument does not lint.
2. **Retries are idempotent.** Each logical call carries an idempotency
   key; the host keeps a result cache plus an in-flight table, so a
   retried ``submit`` whose first delivery actually ran joins the
   original execution (or is served the cached outcome) — it can never
   double-solve, and the client-side future it feeds can never resolve
   twice. The MPI reference gets exactly-once by construction (a
   communicator either delivers or the job dies); an RPC fleet has to
   EARN it, and this cache is where.
3. **Failure is typed and injected, not emergent.** The fault registry
   (resilience/faults.py, TPS012) gained ``rpc.send`` / ``rpc.recv``
   points with drop / delay / duplicate / reorder / partition kinds;
   both transports consume them through :func:`faults.triggered`, so
   ``chaos_smoke --transport`` drills drive real message loss through
   the real code path. ``rpc.send`` fires on the CLIENT before the
   request leaves (device= selects the destination host index);
   ``rpc.recv`` fires on the host path AFTER the handler ran but BEFORE
   the reply leaves — the canonical duplicate-generating failure, since
   the client saw a timeout for work that actually happened.

Two transports share the client/host classes:

- :class:`LoopbackTransport` — in-process, deterministic, used by CI
  and the chaos drills. ``kill()`` models abrupt host loss: in-flight
  handler work completes host-side but no reply escapes.
- :class:`SocketTransport` / :class:`SocketHostServer` — localhost TCP
  with length-prefixed pickled frames, for real two-process drills.

Telemetry: each client call runs under an ``rpc.call`` span (method,
host, attempts), re-sends count into ``rpc.retries``, collapsed
duplicate deliveries into ``rpc.duplicates``, and total call wall
(including backoff) into the ``rpc.call_seconds`` histogram.
"""

from __future__ import annotations

import pickle
import random
import socket
import struct
import threading
import time
from dataclasses import dataclass, field

from ..resilience import faults as _faults
from ..telemetry import metrics as _metrics
from ..telemetry import spans as _telemetry
from ..utils.options import global_options

__all__ = [
    "Message",
    "TransportError",
    "TransportUnreachableError",
    "RpcDeadlineError",
    "RpcHost",
    "RpcClient",
    "LoopbackTransport",
    "SocketTransport",
    "SocketHostServer",
]


class TransportError(RuntimeError):
    """Base for transport-layer failures (never a handler failure —
    handler exceptions marshal through the reply and re-raise as their
    own types)."""


class TransportUnreachableError(TransportError):
    """One send attempt could not reach the host (or its reply was
    lost). Retriable: the client re-sends the SAME idempotency key."""


class RpcDeadlineError(TransportError):
    """The call's deadline budget expired across all retry attempts.

    The transport twin of the serving tier's queue-side
    ``DeadlineExceededError``: carries ``method``, ``host``,
    ``attempts`` and the ``deadline`` that ran out, so failover logic
    can distinguish "host gone" from "handler slow".
    """

    def __init__(self, method: str, host: int, attempts: int,
                 deadline: float):
        self.method = str(method)
        self.host = int(host)
        self.attempts = int(attempts)
        self.deadline = float(deadline)
        super().__init__(
            f"RPC DEADLINE_EXCEEDED: {method!r} to host {host} spent its "
            f"{deadline:.3f}s budget over {attempts} attempt(s) — the "
            "host is unreachable or the handler overran the deadline")


@dataclass
class Message:
    """One wire frame. ``idem`` is the idempotency key (stable across
    retries of the same logical call); ``seq`` the per-client send
    counter (distinct per attempt — how hosts could observe reordering);
    ``error`` carries the marshalled handler exception on replies."""
    kind: str                   # "request" | "reply"
    method: str
    seq: int = 0
    idem: str = ""
    payload: object = None
    error: object = None
    host: int = -1


def _marshal_exc(exc: Exception):
    """An exception object safe to ship in a reply: the original when it
    pickles (both transports may cross a process boundary), else a
    RuntimeError carrying its type name and message."""
    try:
        pickle.loads(pickle.dumps(exc))
        return exc
    # tpslint: disable=TPS005 — any pickling failure (recursion,
    # sockets, locks in exception state) degrades to the string form;
    # nothing is swallowed, the error still reaches the client
    except Exception:  # noqa: BLE001
        return RuntimeError(f"{type(exc).__name__}: {exc}")


class RpcHost:
    """Host-side dispatcher: named handlers behind an idempotency cache.

    ``handlers`` maps method name -> callable(payload) -> result. The
    cache has two tiers: ``_done`` (idem key -> ("ok", result) |
    ("err", exc)) and ``_inflight`` (idem key -> Event). A duplicate
    delivery whose original is still RUNNING waits on the event and
    returns the original's outcome (bounded by ``join_timeout`` — a
    duplicate must not hang past its caller's deadline either); a
    duplicate arriving after completion is served from ``_done``.
    Either way the handler body runs exactly once per key, which is the
    whole exactly-once story: the solve executes once, the future
    resolves once, no matter how many deliveries the network produced.

    The cache is bounded (``cache_cap``, FIFO eviction) so a
    long-running host does not grow it without limit; retries of one
    logical call arrive within its deadline, far inside any realistic
    cap.
    """

    def __init__(self, handlers: dict, host_index: int = 0, *,
                 cache_cap: int = 4096, join_timeout: float = 60.0):
        self.handlers = dict(handlers)
        self.host_index = int(host_index)
        self.cache_cap = int(cache_cap)
        self.join_timeout = float(join_timeout)
        self._done = {}
        self._order = []            # FIFO of done keys for eviction
        self._inflight = {}
        self._lock = threading.Lock()
        self.stats = {"calls": 0, "duplicates": 0, "errors": 0}

    def dispatch(self, msg: Message) -> Message:
        """Run (or join, or replay) the request; always returns a reply
        Message — handler exceptions marshal into ``reply.error``."""
        outcome = self._execute(msg)
        reply = Message(kind="reply", method=msg.method, seq=msg.seq,
                        idem=msg.idem, host=self.host_index)
        if outcome[0] == "ok":
            reply.payload = outcome[1]
        else:
            reply.error = outcome[1]
        return reply

    # ---- exactly-once core -------------------------------------------------

    def _execute(self, msg: Message):
        key = msg.idem
        if key:
            with self._lock:
                if key in self._done:
                    self.stats["duplicates"] += 1
                    _metrics.registry.counter("rpc.duplicates").inc(
                        label=msg.method)
                    return self._done[key]
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                else:
                    self.stats["duplicates"] += 1
            if ev is not None:
                _metrics.registry.counter("rpc.duplicates").inc(
                    label=msg.method)
                ev.wait(timeout=self.join_timeout)
                with self._lock:
                    done = self._done.get(key)
                if done is not None:
                    return done
                return ("err", TransportUnreachableError(
                    f"duplicate of {msg.method!r} joined an execution "
                    f"that did not finish within {self.join_timeout}s"))
        outcome = self._run(msg)
        if key:
            with self._lock:
                self._done[key] = outcome
                self._order.append(key)
                ev = self._inflight.pop(key, None)
                while len(self._order) > self.cache_cap:
                    self._done.pop(self._order.pop(0), None)
            if ev is not None:
                ev.set()
        return outcome

    def _run(self, msg: Message):
        self.stats["calls"] += 1
        handler = self.handlers.get(msg.method)
        if handler is None:
            self.stats["errors"] += 1
            return ("err", KeyError(
                f"no RPC handler for method {msg.method!r} on host "
                f"{self.host_index}"))
        try:
            return ("ok", handler(msg.payload))
        # tpslint: disable=TPS005 — the RPC boundary: every handler
        # exception is marshalled into the reply and re-raised client
        # side, the opposite of swallowing
        except Exception as e:  # noqa: BLE001
            self.stats["errors"] += 1
            return ("err", _marshal_exc(e))


# ---- transports ------------------------------------------------------------


def _apply_send_fault(host_index: int):
    """Consume an ``rpc.send`` clause for destination ``host_index``.
    Returns the number of deliveries (1 normally, 2 for ``duplicate``);
    raises :class:`TransportUnreachableError` for drop/partition (the
    client observes a timeout); sleeps ``mean=`` for delay/reorder (an
    overtaking delay IS reordering on a per-call transport)."""
    fault = _faults.triggered("rpc.send", device=host_index)
    if fault is None:
        return 1
    if fault.kind in ("drop", "partition"):
        raise TransportUnreachableError(
            f"rpc.send {fault.kind}: request to host {host_index} lost")
    if fault.kind in ("delay", "reorder"):
        time.sleep(max(0.0, float(fault.mean)))
        return 1
    if fault.kind == "duplicate":
        return 2
    return 1


def _apply_recv_fault(host_index: int):
    """Consume an ``rpc.recv`` clause on host ``host_index``'s reply
    path (the handler has ALREADY run). Returns "redeliver" for
    duplicate (the request is dispatched again — the idempotency cache's
    moment), raises for drop/partition (reply lost after real work),
    sleeps for delay/reorder."""
    fault = _faults.triggered("rpc.recv", device=host_index)
    if fault is None:
        return None
    if fault.kind in ("drop", "partition"):
        raise TransportUnreachableError(
            f"rpc.recv {fault.kind}: reply from host {host_index} lost "
            "after the handler ran")
    if fault.kind in ("delay", "reorder"):
        time.sleep(max(0.0, float(fault.mean)))
        return None
    if fault.kind == "duplicate":
        return "redeliver"
    return None


class LoopbackTransport:
    """In-process transport to one :class:`RpcHost` — deterministic CI
    stand-in for a network hop that still takes every failure the fault
    registry can inject, plus abrupt host death via :meth:`kill`.

    The dead flag is checked at call entry AND again before the reply is
    returned: killing a host mid-call means the handler's work happened
    (a solve really ran) but the client never hears — precisely the
    ambiguity failover logic must handle, reproduced on demand."""

    def __init__(self, host: RpcHost):
        self._host = host
        self.host_index = host.host_index
        self._dead = False

    def kill(self):
        """Abrupt host loss: every future call (and any reply not yet
        returned) fails with :class:`TransportUnreachableError`."""
        self._dead = True

    def revive(self):
        self._dead = False

    @property
    def dead(self) -> bool:
        return self._dead

    def call_once(self, msg: Message, timeout: float) -> Message:
        """One delivery attempt under ``timeout`` (loopback dispatch is
        synchronous, so the budget only bounds injected delays)."""
        if self._dead:
            raise TransportUnreachableError(
                f"host {self.host_index} is dead")
        deliveries = _apply_send_fault(self.host_index)
        reply = None
        for _ in range(deliveries):
            reply = self._host.dispatch(msg)
        if _apply_recv_fault(self.host_index) == "redeliver":
            reply = self._host.dispatch(msg)
        if self._dead:
            raise TransportUnreachableError(
                f"host {self.host_index} died before replying")
        return reply

    def close(self):
        self.kill()


def _send_frame(sock, obj, timeout: float):
    sock.settimeout(timeout)
    blob = pickle.dumps(obj)
    sock.sendall(struct.pack(">I", len(blob)) + blob)


def _recv_frame(sock, timeout: float):
    sock.settimeout(timeout)
    need = struct.unpack(">I", _recv_exact(sock, 4))[0]
    return pickle.loads(_recv_exact(sock, need))


def _recv_exact(sock, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise TransportUnreachableError("peer closed mid-frame")
        buf += chunk
    return buf


class SocketHostServer:
    """Host side of :class:`SocketTransport`: a localhost TCP listener
    feeding an :class:`RpcHost`, one thread per accepted connection
    (clients connect per call — the framing is 4-byte big-endian length
    + pickled :class:`Message`, one request/one reply per connection).
    """

    def __init__(self, host: RpcHost, *, port: int = 0,
                 frame_timeout: float = 30.0):
        self._host = host
        self.frame_timeout = float(frame_timeout)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", int(port)))
        self._sock.listen(32)
        self.address = self._sock.getsockname()
        self._closed = False
        self._thread = threading.Thread(
            target=self._accept_loop, name="rpc-host-accept", daemon=True)
        self._thread.start()

    def _accept_loop(self):
        while not self._closed:
            try:
                conn, _addr = self._sock.accept()
            except OSError:
                return          # listener closed
            threading.Thread(target=self._serve_one, args=(conn,),
                             daemon=True).start()

    def _serve_one(self, conn):
        try:
            with conn:
                msg = _recv_frame(conn, self.frame_timeout)
                if self._closed:
                    return      # killed mid-call: work done, reply lost
                reply = self._host.dispatch(msg)
                if _apply_recv_fault(self._host.host_index) == "redeliver":
                    reply = self._host.dispatch(msg)
                if self._closed:
                    return
                _send_frame(conn, reply, self.frame_timeout)
        # tpslint: disable=TPS005 — a per-connection serving thread: any
        # framing/socket error just drops this connection (the client's
        # retry machinery is the recovery path, not this thread)
        except Exception:  # noqa: BLE001
            return

    def close(self):
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass

    kill = close


class SocketTransport:
    """Client side of the localhost TCP transport: per-call connect to
    ``address`` with ``timeout``, one framed request, one framed reply.
    ``rpc.send`` faults apply client-side exactly like loopback (the
    recv-side faults live in :class:`SocketHostServer`)."""

    def __init__(self, address, host_index: int = 0):
        self.address = (str(address[0]), int(address[1]))
        self.host_index = int(host_index)
        self._dead = False

    def kill(self):
        self._dead = True

    @property
    def dead(self) -> bool:
        return self._dead

    def call_once(self, msg: Message, timeout: float) -> Message:
        if self._dead:
            raise TransportUnreachableError(
                f"host {self.host_index} is dead")
        deliveries = _apply_send_fault(self.host_index)
        reply = None
        budget = max(0.01, float(timeout))
        for _ in range(deliveries):
            try:
                with socket.create_connection(
                        self.address, timeout=budget) as sock:
                    _send_frame(sock, msg, budget)
                    reply = _recv_frame(sock, budget)
            except (OSError, EOFError, pickle.UnpicklingError) as e:
                raise TransportUnreachableError(
                    f"socket call to host {self.host_index} at "
                    f"{self.address} failed: {e}") from e
        return reply

    def close(self):
        self.kill()


# ---- client ----------------------------------------------------------------


@dataclass
class RetrySchedule:
    """Capped exponential backoff with deterministic jitter. ``base``
    doubles per attempt up to ``cap``; jitter draws uniformly from
    [0.5, 1.0]× the raw delay off a seeded PRNG so two clients that
    lost the same host do not re-send in lockstep, yet every drill
    replays identically."""
    base: float = 0.02
    cap: float = 0.5
    seed: int = 0
    _rng: random.Random = field(default=None, repr=False)

    def __post_init__(self):
        self._rng = random.Random(self.seed)

    def delay(self, attempt: int) -> float:
        raw = min(self.cap, self.base * (2.0 ** max(0, attempt - 1)))
        return raw * (0.5 + 0.5 * self._rng.random())


class RpcClient:
    """Deadline-bounded, idempotent-retry client over one transport.

    Defaults come from the options database: ``-rpc_deadline_s`` (per
    call budget), ``-rpc_retry_max`` (send attempts per call),
    ``-rpc_backoff_base_s`` / ``-rpc_backoff_cap_s`` (the backoff
    curve). ``sleep`` is injectable so drills retry instantly.
    """

    def __init__(self, transport, *, deadline: float | None = None,
                 retry_max: int | None = None, seed: int = 0,
                 sleep=time.sleep):
        opt = global_options()
        self.transport = transport
        self.deadline = float(
            opt.get_real("rpc_deadline_s", 30.0)
            if deadline is None else deadline)
        self.retry_max = int(
            opt.get_int("rpc_retry_max", 4)
            if retry_max is None else retry_max)
        self.schedule = RetrySchedule(
            base=opt.get_real("rpc_backoff_base_s", 0.02),
            cap=opt.get_real("rpc_backoff_cap_s", 0.5),
            seed=seed)
        self._sleep = sleep
        self._seq = 0
        self._lock = threading.Lock()
        self.host_index = int(getattr(transport, "host_index", -1))

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _idem(self, method: str, seq: int) -> str:
        return f"c{id(self):x}.{method}.{seq}"

    def call(self, method: str, payload=None, *,
             deadline: float | None = None,
             idem_key: str | None = None):
        """One logical call: up to ``retry_max`` send attempts of the
        SAME idempotency key under one ``deadline`` budget. Raises
        :class:`RpcDeadlineError` when the budget runs out,
        :class:`TransportUnreachableError` when attempts are exhausted
        with budget left (the host is gone, not slow), or the
        marshalled handler exception itself."""
        budget = self.deadline if deadline is None else float(deadline)
        seq0 = self._next_seq()
        idem = idem_key if idem_key else self._idem(method, seq0)
        t0 = time.perf_counter()
        attempts = 0
        last_exc = None
        with _telemetry.span("rpc.call", method=method,
                             host=self.host_index) as sp:
            while attempts < self.retry_max:
                remaining = budget - (time.perf_counter() - t0)
                if remaining <= 0.0:
                    break
                attempts += 1
                if attempts > 1:
                    _metrics.registry.counter("rpc.retries").inc(
                        label=method)
                msg = Message(kind="request", method=method,
                              seq=self._next_seq(), idem=idem,
                              payload=payload, host=self.host_index)
                try:
                    reply = self.transport.call_once(msg, timeout=remaining)
                except TransportUnreachableError as e:
                    last_exc = e
                    remaining = budget - (time.perf_counter() - t0)
                    if attempts < self.retry_max and remaining > 0.0:
                        self._sleep(min(self.schedule.delay(attempts),
                                        max(0.0, remaining)))
                    continue
                sp.set_attrs(attempts=attempts)
                _metrics.registry.histogram("rpc.call_seconds").observe(
                    time.perf_counter() - t0)
                if reply.error is not None:
                    raise reply.error
                return reply.payload
            sp.set_attrs(attempts=attempts, failed=True)
        _metrics.registry.histogram("rpc.call_seconds").observe(
            time.perf_counter() - t0)
        if time.perf_counter() - t0 >= budget:
            raise RpcDeadlineError(method, self.host_index, attempts,
                                   budget) from last_exc
        raise TransportUnreachableError(
            f"RPC {method!r} to host {self.host_index}: "
            f"{self.retry_max} attempt(s) exhausted "
            f"({last_exc})") from last_exc

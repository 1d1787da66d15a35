"""Flight recorder — a bounded ring of recent span trees + fault events.

The post-mortem answer to "what was the solver doing when it died":
a ``collections.deque(maxlen=...)`` of the most recent completed root
span trees, fired fault-injection/runtime-fault events, and recovery-
ladder events — so a chaos-run autopsy needs NO re-execution. The ring
length is ``-telemetry_flight_len`` (default 256 entries); the ring is
only fed while telemetry is enabled (the disabled path never touches
it).

Dumps:

* :meth:`FlightRecorder.dump` — on demand, JSON to a path (default
  ``<tmpdir>/tpu_solve_flight_<pid>.json``);
* :func:`auto_dump` — called by the resilience wrappers when an error
  escapes UNRECOVERED (exhausted retries, non-retriable class, failed
  shrink) and by the serving dispatcher when a dispatch fails its
  waiting futures: the ring is written out at the moment the failure
  becomes someone else's problem.

Fault events arrive through :func:`record_fault`, which
``resilience/faults.py`` calls (lazily — this module is stdlib-only, so
the import keeps faults.py framework-free) for every fired clause at
every registered fault point; ``telemetry/names.FLIGHT_FAULT_POINTS``
declares that coverage and tpslint TPS014 enforces it against
``faults.FAULT_POINTS``. The ``fault.count`` counter increments even
when telemetry is disabled (counters are always-on, like every other
registry metric).
"""

from __future__ import annotations

import collections
import json
import os
import tempfile
import threading
import time

DEFAULT_FLIGHT_LEN = 256


class FlightRecorder:
    def __init__(self, maxlen: int = DEFAULT_FLIGHT_LEN):
        self._lock = threading.Lock()
        self._entries = collections.deque(maxlen=int(maxlen))
        self.last_dump_path = None

    @property
    def maxlen(self) -> int:
        return self._entries.maxlen

    def set_maxlen(self, n: int):
        """Resize the ring, keeping the newest entries."""
        with self._lock:
            self._entries = collections.deque(self._entries,
                                              maxlen=max(1, int(n)))

    # ---- feeding ------------------------------------------------------------
    def record_span(self, tree: dict, group: dict | None = None):
        """Append a root span tree. With ``group`` (an empty span tree),
        the tree goes in as a child of a group root instead: of the
        newest entry when that is a group of the same name and thread,
        else of ``group`` as a new entry. A burst of small roots (the
        one-op programs JAX compiles outside any solve) then takes one
        ring slot, and solve trees and fault events stay in the ring."""
        with self._lock:
            if group is not None:
                last = self._entries[-1] if self._entries else None
                if (last is not None and last["type"] == "span"
                        and last["span"]["name"] == group["name"]
                        and last["span"]["thread"] == group["thread"]):
                    self._entries.pop()
                    group = last["span"]
                else:
                    group = dict(group, wall=tree["wall"], t0=tree["t0"],
                                 t1=tree["t1"])
                # a new dict: a tree handed out by spans() never changes
                tree = dict(group, t0=min(group["t0"], tree["t0"]),
                            t1=max(group["t1"], tree["t1"]),
                            children=[*group["children"], tree])
            self._entries.append({"type": "span", "wall": time.time(),
                                  "span": tree})

    def record_event(self, kind: str, **data):
        with self._lock:
            self._entries.append({"type": "event", "kind": str(kind),
                                  "wall": time.time(), "data": data})

    # ---- views --------------------------------------------------------------
    def entries(self) -> list:
        with self._lock:
            return list(self._entries)

    def spans(self) -> list:
        """The recorded root span trees, oldest first."""
        return [e["span"] for e in self.entries() if e["type"] == "span"]

    def events(self, kind: str | None = None) -> list:
        return [e for e in self.entries()
                if e["type"] == "event"
                and (kind is None or e["kind"] == kind)]

    def clear(self):
        with self._lock:
            self._entries.clear()
        self.last_dump_path = None

    # ---- dumping ------------------------------------------------------------
    def dump(self, path: str | None = None, reason: str = "on demand"):
        """Write the ring as JSON; returns the path written."""
        path = path or os.path.join(
            tempfile.gettempdir(), f"tpu_solve_flight_{os.getpid()}.json")
        payload = {"reason": reason, "dumped_at": time.time(),
                   "flight_len": self.maxlen, "pid": os.getpid(),
                   "entries": self.entries()}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)         # atomic, like utils/checkpoint
        self.last_dump_path = path
        return path


recorder = FlightRecorder()


def record_fault(point: str, kind: str, **data):
    """One fired fault (injected or classified-real) at a registered
    fault point. Counter always; ring entry only while telemetry is
    armed. Never raises — a telemetry failure must not mask the fault
    being recorded."""
    from .metrics import registry
    try:
        registry.counter("fault.count").inc(label=point)
        from .spans import enabled
        if enabled():
            recorder.record_event("fault", point=point, fault_kind=kind,
                                  **data)
    # tpslint: disable=TPS005 — last-resort guard: the fault path is
    # already unwinding a failure; recording it must never replace the
    # real error with a telemetry one
    except Exception:  # noqa: BLE001
        pass


def auto_dump(reason: str):
    """Dump the ring when an error escapes unrecovered (resilience
    wrappers / serving dispatcher). No-op while telemetry is disabled;
    returns the dump path or None."""
    from .spans import enabled
    if not enabled():
        return None
    try:
        return recorder.dump(reason=reason)
    except OSError:
        return None

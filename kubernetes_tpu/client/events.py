"""Event recording.

Analog of client-go `tools/record`: EventRecorder.Eventf producing v1 Events
with series counting (repeated events aggregate into count bumps, the
EventCorrelator's role), and EventBroadcaster, the queue and sink thread in
front of it: a caller on a hot path records an Event without waiting for
the apiserver.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

from kubernetes_tpu.machinery import errors, meta


class EventRecorder:
    def __init__(self, client, component: str = "kubernetes-tpu"):
        self.client = client
        self.component = component
        self._mu = threading.Lock()
        # (ns, involved-uid, reason, message) -> event name
        self._seen: Dict[Tuple[str, str, str, str], str] = {}

    @staticmethod
    def dedup_key(involved: dict, reason: str,
                  message: str) -> Tuple[str, str, str, str]:
        return (meta.namespace(involved) or "default",
                meta.uid(involved) or meta.name(involved), reason, message)

    def event(self, involved: dict, event_type: str, reason: str,
              message: str, count: int = 1,
              timestamp: Optional[str] = None) -> Optional[dict]:
        """record.Eventf. event_type ∈ {Normal, Warning}. `count`
        occurrences as of `timestamp` (now, when None) in one write."""
        dedup = self.dedup_key(involved, reason, message)
        ns = dedup[0]
        timestamp = timestamp or meta.now_rfc3339()
        with self._mu:
            existing_name = self._seen.get(dedup)
        try:
            if existing_name:
                bumped = self._bump(existing_name, ns, count, timestamp)
                if bumped is not None:
                    return bumped
                # the Event was deleted server-side (namespace sweep, GC):
                # forget the stale name and record a fresh one
                with self._mu:
                    if self._seen.get(dedup) == existing_name:
                        del self._seen[dedup]
            name = f"{meta.name(involved)}.{meta.new_uid()[:13]}"
            ev = self.client.events.create({
                "apiVersion": "v1", "kind": "Event",
                "metadata": {"name": name, "namespace": ns},
                "involvedObject": {
                    "kind": involved.get("kind", ""),
                    "namespace": ns,
                    "name": meta.name(involved),
                    "uid": meta.uid(involved),
                },
                "reason": reason, "message": message, "type": event_type,
                "source": {"component": self.component},
                "firstTimestamp": timestamp,
                "lastTimestamp": timestamp,
                "count": count,
            }, ns)
            with self._mu:
                self._seen[dedup] = name
            return ev
        except errors.StatusError:
            return None

    def _bump(self, name: str, ns: str, count: int,
              timestamp: str) -> Optional[dict]:
        try:
            cur = self.client.events.get(name, ns)
            cur["count"] = int(cur.get("count", 1)) + count
            cur["lastTimestamp"] = timestamp
            return self.client.events.update(cur, ns)
        except errors.StatusError:
            return None


class EventBroadcaster:
    """The broadcaster half of `tools/record` (event.go: a queue and a sink
    goroutine): `event()` never calls the apiserver. It queues the Event —
    or, while one with the same dedup key is still queued, adds to that
    one's count — and ONE daemon thread writes the queue through an
    EventRecorder, oldest first. The thread is started by the first Event
    and sleeps on a condition while the queue is empty: an idle
    broadcaster wakes nothing.

    `observe(outcome, n)` counts dispositions: `queued`, `coalesced`,
    `dropped` (refused at QUEUE_BOUND, or still queued when `stop()` ran
    out of time) on the caller's thread; `emitted`, `error` on the sink's,
    one per write."""

    #: one entry per distinct failing pod: three times the 4,352 that the
    #: flagship drain fails in one wave
    QUEUE_BOUND = 16384

    def __init__(self, client, component: str = "kubernetes-tpu",
                 observe: Callable[[str, int], None] = lambda outcome, n: None):
        self._recorder = EventRecorder(client, component)
        self._observe = observe
        self._mu = threading.Lock()
        self._work = threading.Condition(self._mu)      # the sink waits
        self._drained = threading.Condition(self._mu)   # flush() waits
        # dedup key -> [involved, event_type, count, timestamp]
        self._queue: "OrderedDict[Tuple[str, str, str, str], list]" = \
            OrderedDict()
        self._in_flight = 0   # 1 while the sink writes the entry it took
        self._closing = False
        self._thread: Optional[threading.Thread] = None

    def event(self, involved: dict, event_type: str, reason: str,
              message: str) -> None:
        self.events((involved,), event_type, reason, message)

    def events(self, involved_objects, event_type: str, reason: str,
               message: str) -> None:
        """One Event per object, all queued under one hold of the lock: the
        sink wakes once, when the last is in, and does not fight the caller
        for the interpreter while it queues the rest."""
        outcomes = {"queued": 0, "coalesced": 0, "dropped": 0}
        at = meta.now_rfc3339()
        with self._mu:
            for involved in involved_objects:
                key = EventRecorder.dedup_key(involved, reason, message)
                entry = self._queue.get(key)
                if entry is not None:
                    entry[2] += 1
                    outcomes["coalesced"] += 1
                elif len(self._queue) >= self.QUEUE_BOUND:
                    outcomes["dropped"] += 1
                else:
                    self._queue[key] = [involved, event_type, 1, at]
                    outcomes["queued"] += 1
            if outcomes["queued"]:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._sink, daemon=True, name="event-sink")
                    self._thread.start()
                self._work.notify()
        for outcome, n in outcomes.items():
            if n:
                self._observe(outcome, n)

    def pending(self) -> int:
        """Events queued and not yet written (the one being written too)."""
        with self._mu:
            return len(self._queue) + self._in_flight

    def flush(self, timeout: float) -> bool:
        """Wait until everything queued so far is written; False when
        `timeout` seconds were not enough."""
        with self._mu:
            return self._drained.wait_for(
                lambda: not self._queue and not self._in_flight, timeout)

    def stop(self, timeout: float) -> None:
        """Flush with a deadline, count what is left as `dropped`, and end
        the sink thread (a later Event starts a new one)."""
        self.flush(timeout)
        left = self._close()
        if left:
            self._observe("dropped", left)

    def abandon(self) -> None:
        """A killed process writes nothing: forget the queue, uncounted."""
        self._close()

    def _close(self) -> int:
        with self._mu:
            left = len(self._queue)
            self._queue.clear()
            thread = self._thread
            if thread is not None:
                self._closing = True
                self._work.notify()
        if thread is not None:
            thread.join(timeout=2)
        return left

    def _sink(self) -> None:
        while True:
            with self._mu:
                self._in_flight = 0
                if not self._queue:
                    self._drained.notify_all()
                while not self._queue and not self._closing:
                    self._work.wait()   # no timeout: asleep until an Event
                if not self._queue:
                    self._closing = False
                    self._thread = None
                    return
                (_, _, reason, message), (involved, event_type, count, at) \
                    = self._queue.popitem(last=False)
                self._in_flight = 1
            try:
                written = self._recorder.event(involved, event_type, reason,
                                               message, count, at)
            except Exception:  # noqa: BLE001 - the sink outlives a write
                # that failed below the StatusErrors the recorder absorbs
                # (a transport down): the Event is lost, the next is tried
                logging.getLogger("ktpu.client.events").exception(
                    "event sink: write failed")
                written = None
            self._observe("emitted" if written is not None else "error", 1)

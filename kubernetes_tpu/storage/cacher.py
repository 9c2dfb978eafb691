"""Watch cache: the Cacher tier between the KV store and watchers.

Analog of the apiserver's Cacher
(/root/reference/staging/src/k8s.io/apiserver/pkg/storage/cacher/cacher.go:309):
the reference interposes a reflector-fed ring buffer (watchCache, :369-374)
between etcd and the N registered watchers so that

  * each event is decoded at most ONCE on the broadcast side, not once per
    watcher, and
  * a new watcher resuming from a recent resourceVersion replays its catch-up
    window from memory — storage reads stay independent of watcher count
    (`WatchCache.events_since`); only a resume older than the ring's horizon
    falls through to the backing store (counted in `storage_fallbacks`).

The ring holds events `(rev, type, key, value)` in revision order, the value
as the bytes the store keeps. `horizon` is the revision BEFORE the oldest
retained event: a resume from `since >= horizon` is served fully from memory.

Who owns a decoded object (ISSUE 28): nobody shares a mutable one. The ring
and every stream's buffer hold the SAME `CachedEvent`, which is never
changed; `CachedEvent.obj` is decoded at most once and only ever READ (a
watcher's predicate); what a stream's consumer receives is `event()`, an
object of its own, decoded from the bytes on the consumer's thread at the
moment it takes the event — a consumer that is kept from reading costs the
broadcast nothing until it reads.
"""

from __future__ import annotations

import itertools
import json
import threading
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from kubernetes_tpu.machinery import meta
from kubernetes_tpu.machinery import watch as mwatch

DEFAULT_CAPACITY = 8192  # ring slots (cacher.go watchCache capacity analog)


def decode(data: bytes, rev: int) -> Dict[str, Any]:
    """A stored value as a fresh object, private to the caller, with the
    record's revision as its resourceVersion (the value holds none)."""
    obj = json.loads(data)
    meta.set_resource_version(obj, str(rev))
    return obj


class CachedEvent:
    """One event of the stream: what the ring retains and what a watcher's
    buffer holds until its consumer takes it. Immutable once built."""

    __slots__ = ("rev", "type", "key", "value", "_obj")

    def __init__(self, rev: int, type: str, key: str, value: bytes):
        self.rev = rev
        self.type = type    # machinery.watch ADDED/MODIFIED/DELETED
        self.key = key
        self.value = value  # the stored bytes (for DELETED: the last ones)
        self._obj: Optional[Dict[str, Any]] = None

    @property
    def obj(self) -> Dict[str, Any]:
        """Decoded once, for readers that do not change it (predicates).
        Never handed to a consumer."""
        if self._obj is None:
            self._obj = decode(self.value, self.rev)
        return self._obj

    def event(self) -> mwatch.Event:
        """The event as ONE consumer receives it: its own object."""
        return mwatch.Event(self.type, decode(self.value, self.rev))


class WatchCache:
    """Decoded-event ring buffer with a revision horizon."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, horizon: int = 0):
        self._mu = threading.Lock()
        self._ring: Deque[CachedEvent] = deque()
        self._capacity = capacity
        self._horizon = horizon   # rev before the oldest retained event
        self.hits = 0             # catch-ups served from memory
        self.storage_fallbacks = 0  # catch-ups that had to read the store

    @property
    def horizon(self) -> int:
        with self._mu:
            return self._horizon

    def extend(self, evs: List[CachedEvent]) -> None:
        """One turn of the pump's events, in revision order."""
        with self._mu:
            ring = self._ring
            ring.extend(evs)
            for _ in range(len(ring) - self._capacity):
                self._horizon = ring.popleft().rev

    def compact(self, at_rev: int) -> None:
        """Drop every retained event at or below `at_rev` and raise the
        horizon to it — what a sustained storm does to the ring organically
        (old revisions churn out). Resumes below the new horizon fall back
        to storage, where a compacted revision earns its 410."""
        with self._mu:
            while self._ring and self._ring[0].rev <= at_rev:
                self._ring.popleft()
            self._horizon = max(self._horizon, at_rev)

    def events_since(self, since: int, prefix: str, limit: int = 0,
                     count: bool = True) -> Optional[List[CachedEvent]]:
        """Events with rev > since under prefix, oldest first and at most
        `limit` of them (0 = all), from memory — or None when `since`
        predates the ring's horizon (caller falls back to storage).
        `count=False` leaves `hits` / `storage_fallbacks` alone: a catch-up
        brought in several refills is one catch-up."""
        with self._mu:
            if since < self._horizon:
                self.storage_fallbacks += count
                return None
            self.hits += count
            found = (e for e in self._ring
                     if e.rev > since and e.key.startswith(prefix))
            return list(itertools.islice(found, limit or None))

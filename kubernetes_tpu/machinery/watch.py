"""Watch event types and channels.

Analog of apimachinery `pkg/watch/watch.go`: an Interface delivering a stream
of {type, object} events. Here a watch is a closeable blocking queue; the
storage layer and clients share this shape.

The channel is the per-watcher BOUNDED delivery buffer of the cacher
contract (cacher.go forgetWatcher): a producer that finds it full terminates
THIS watcher instead of blocking the broadcast loop, and `terminate()` lets
it leave a terminal Status event (e.g. 410 "too old resource version") that
the consumer receives after draining whatever it had buffered — so even a
slow-but-alive client learns WHY its stream died instead of seeing a bare
socket EOF.

A producer may buffer, in place of an `Event`, anything with an `event()`
method that builds one (the store's `CachedEvent`): the consumer's side
calls it as it takes the item, on the consumer's own thread, so what each
stream receives is built once per reader and only when it reads.

Every buffered item carries the instant (`time.monotonic()`) it went into
the buffer; the consumer finds that of the event it took last in
`buffered_at`: how long the event waited for its reader is the reader's to
tell (a consumer that delivers in turn, as an informer does, holds every
event behind the one whose handler waits).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterator, Optional

ADDED = "ADDED"
MODIFIED = "MODIFIED"
DELETED = "DELETED"
BOOKMARK = "BOOKMARK"
ERROR = "ERROR"


@dataclass(frozen=True)
class Event:
    type: str
    object: Dict[str, Any]


class Watch:
    """watch.Interface: ResultChan() + Stop(). Iteration ends on Stop or when
    the producer closes the stream; a terminal event set via `terminate()`
    is delivered exactly once, after the buffered events drain."""

    _SENTINEL = object()

    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=capacity)
        self._stopped = threading.Event()
        self._term_mu = threading.Lock()
        self._terminal: Optional[Event] = None
        # when the event the consumer took last went into the buffer
        self.buffered_at = 0.0

    def send(self, event: Event, timeout: Optional[float] = 5.0) -> bool:
        """Producer side. Returns False if the watcher is gone/slow: the
        reference terminates slow watchers (cacher.go forgetWatcher) rather
        than blocking the event path."""
        if self._stopped.is_set():
            return False
        try:
            if timeout is not None and timeout <= 0:
                self._q.put_nowait((event, time.monotonic()))
            else:
                self._q.put((event, time.monotonic()), timeout=timeout)
            return True
        except queue.Full:
            self.stop()
            return False

    def offer(self, event: Any) -> bool:
        """Producer side, for a producer that keeps its own place in the
        stream (the store's pump): put the event (or what builds it, see
        the module's note) if there is room and say whether it went in. A
        full buffer costs the consumer nothing — the producer comes back
        with the same event later."""
        if self._stopped.is_set():
            return False
        try:
            self._q.put_nowait((event, time.monotonic()))
            return True
        except queue.Full:
            return False

    def terminate(self, event: Event) -> None:
        """Stop the stream with a terminal event the consumer still gets
        AFTER draining the buffer — works even when the buffer is full (the
        deaf-watcher case, where the failed send() already stopped the
        stream and a plain send() could never land the WHY)."""
        with self._term_mu:
            if self._terminal is None:
                self._terminal = event
        self.stop()

    def _take_terminal(self) -> Optional[Event]:
        with self._term_mu:
            t, self._terminal = self._terminal, None
            return t

    def stop(self) -> None:
        if not self._stopped.is_set():
            self._stopped.set()
            try:
                self._q.put_nowait(self._SENTINEL)
            except queue.Full:
                pass

    @property
    def stopped(self) -> bool:
        return self._stopped.is_set()

    def depth(self) -> int:
        """Buffered (undelivered) events — the backpressure signal the
        dispatcher exports as `watch_buffer_depth`."""
        return self._q.qsize()

    def _taken(self, item: Any) -> Event:
        event, self.buffered_at = item
        return event if event.__class__ is Event else event.event()

    def __iter__(self) -> Iterator[Event]:
        while True:
            item = self._q.get()
            if item is self._SENTINEL:
                t = self._take_terminal()
                if t is not None:
                    yield t
                return
            yield self._taken(item)
            if self._stopped.is_set() and self._q.empty():
                t = self._take_terminal()
                if t is not None:
                    yield t
                return

    def next(self, timeout: Optional[float] = None) -> Optional[Event]:
        """Blocking pop; the terminal event (if any) after drain; None on
        stop/timeout."""
        if self._stopped.is_set() and self._q.empty():
            return self._take_terminal()
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            return None
        if item is self._SENTINEL:
            return self._take_terminal()
        return self._taken(item)

"""Operation tracing: spans with steps + slow-op logging.

Analog of `vendor/k8s.io/utils/trace/trace.go` (utiltrace) as used by the
scheduler (`core/generic_scheduler.go:188-217` Step/LogIfLong): a Trace
collects timed steps; if the whole operation exceeds a threshold, the steps
are emitted so slow cycles are explainable.

Child accounting (ISSUE 24): below its steps a Trace keeps a tree of
AGGREGATES — `[count, total_s, max_s]` per path, never one record per call,
so a wave of 13,600 Bindings costs a dict lookup and three updates per span.
A path's segments are its parents: `bind-commit/bind-call/apiserver.bind`.
The step that closes a phase names the top segment (children recorded while
the phase ran are filed under the step's message); `begin`/`end` nest below
it; `child` adds a leaf. A layer's self time is its total less its
children's — readers compute it, the program does not.

`current()` is the trace of the operation running on THIS thread (a
`contextvars` slot): a callee several layers down — the binder, the
in-process apiserver, the store — adds its time to the span that caused it
without a new argument through every signature between. Off that thread,
after the operation, or with tracing off, it is `None`: one check.
"""

from __future__ import annotations

import contextvars
import logging
import time
from typing import Callable, Dict, List, Optional, Tuple

logger = logging.getLogger("kubernetes_tpu.trace")


#: default LogIfLong threshold (the reference's 100ms scheduler trace bound)
DEFAULT_THRESHOLD = 0.1


class _Node:
    """One path's aggregate and the paths below it."""

    __slots__ = ("count", "total", "max", "kids")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.kids: Dict[str, "_Node"] = {}

    def kid(self, name: str) -> "_Node":
        node = self.kids.get(name)
        if node is None:
            node = self.kids[name] = _Node()
        return node

    def at(self, path: str) -> "_Node":
        """The node `path` names below this one, a `/` between parents."""
        node = self
        for name in path.split("/"):
            node = node.kid(name)
        return node

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total += seconds
        if seconds > self.max:
            self.max = seconds

    def add_many(self, count: int, total: float, longest: float) -> None:
        """`count` calls that took `total` seconds, the longest `longest`."""
        self.count += count
        self.total += total
        if longest > self.max:
            self.max = longest

    def merge(self, other: "_Node") -> None:
        self.add_many(other.count, other.total, other.max)
        for name, node in other.kids.items():
            self.kid(name).merge(node)

    def flatten(self, prefix: str, out: Dict[str, List[float]]) -> None:
        for name, node in self.kids.items():
            path = prefix + name
            if node.count:
                out[path] = [node.count, node.total, node.max]
            node.flatten(path + "/", out)


class Trace:
    def __init__(self, name: str, clock: Callable[[], float] = time.monotonic,
                 threshold: float = DEFAULT_THRESHOLD, **fields):
        self.name = name
        self.fields = fields
        self.clock = clock
        self.threshold = threshold
        self.start = clock()
        self.steps: List[Tuple[float, str]] = []
        self._ended: Optional[float] = None
        # child accounting: `_open` collects what runs before the next
        # step names it; `_cur` is the span new children nest under
        self._root = _Node()
        self._open = _Node()
        self._cur = self._open

    def step(self, msg: str, at: Optional[float] = None) -> None:
        """Close the phase that just ran. `at` is the instant it ended, on
        this trace's clock, where the caller read it earlier (a request's
        arrival, read before the operation it belongs to was known)."""
        self.steps.append((self.clock() if at is None else at, msg))
        if self._open.kids:
            self._root.kid(msg).merge(self._open)
            self._open = _Node()
        self._cur = self._open

    def begin(self, name: str) -> _Node:
        """Open span `name` below the current one; children recorded until
        `end` nest under it. Returns the token `end` takes."""
        parent = self._cur
        self._cur = parent.kid(name)
        return parent

    def end(self, token: _Node, seconds: float) -> None:
        """Close the span `begin` opened, adding one call of `seconds`."""
        self._cur.add(seconds)
        self._cur = token

    def child(self, path: str, seconds: float) -> None:
        """Add one call of `seconds` under `path`, below the current span.
        A `/` in the path names parents: `child("a/b", s)` files `b`
        below `a` without counting a call of `a`."""
        cur = self._cur
        (cur.at(path) if "/" in path else cur.kid(path)).add(seconds)

    def graft(self, path: str, children: Dict[str, List[float]]) -> None:
        """File another trace's `children()` below `path` under the current
        span: what a stage did on a thread of its own (an informer's
        list+replace round), merged by the thread that waited for it, once
        it has ended."""
        under = self._cur.at(path)
        for sub, (count, total, longest) in children.items():
            under.at(sub).add_many(count, total, longest)

    def children(self) -> Dict[str, List[float]]:
        """`{path: [count, total_s, max_s]}` of every span that was called,
        in the order first seen. Children of a phase no step has closed
        yet are listed without a phase segment."""
        out: Dict[str, List[float]] = {}
        self._root.flatten("", out)
        self._open.flatten("", out)
        return out

    def record(self) -> Dict[str, List[float]]:
        """`children()` as a record carries it: seconds to the microsecond."""
        return {path: [count, round(total, 6), round(longest, 6)]
                for path, (count, total, longest) in self.children().items()}

    def duration(self) -> float:
        return (self._ended or self.clock()) - self.start

    def log_if_long(self, threshold: float,
                    sink: Optional[Callable[[str], None]] = None) -> bool:
        """utiltrace.LogIfLong: emit the step timeline when total > threshold.
        Returns True if it logged."""
        self._ended = self.clock()
        total = self.duration()
        if total < threshold:
            return False
        emit = sink or (lambda s: logger.warning("%s", s))
        fs = ",".join(f"{k}={v}" for k, v in self.fields.items())
        lines = [f'Trace "{self.name}" ({fs}) took {total * 1000:.1f}ms '
                 f"(threshold {threshold * 1000:.0f}ms):"]
        prev = self.start
        for ts, msg in self.steps:
            lines.append(f"  +{(ts - prev) * 1000:.1f}ms {msg}")
            prev = ts
        emit("\n".join(lines))
        return True

    def __enter__(self) -> "Trace":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # exiting on an exception: the operation's failure path already
        # reports (and the timeline would blame the step that happened to
        # be open when the raise unwound) — only log clean slow exits
        if exc_type is None:
            self.log_if_long(self.threshold)


_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "kubernetes_tpu_trace", default=None)

#: the Trace of the operation running on this thread, or None
current = _CURRENT.get


def activate(trace: Trace):
    """Make `trace` this thread's `current()`; returns the token
    `deactivate` takes. A thread started meanwhile sees None."""
    return _CURRENT.set(trace)


def deactivate(token) -> None:
    _CURRENT.reset(token)

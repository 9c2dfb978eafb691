"""What a long-lived serving process sets once, at its start: where compiled
XLA programs are kept between process starts, the heap's policy, and where
the interpreter's cyclic collector stands while the start lists the cluster.

What the freeze at the end of `listing_heap()` costs a process that lives
on: the objects alive at that instant (the listed nodes and pods, the
mirror built from them, the compiled programs' host side) are never again
examined for reference cycles. Reference counts free them as before, so a
pod that a later relist or delete drops is freed where it is dropped; what
stays for good is whatever of them is garbage ONLY by a cycle, at the freeze
or later (a server that is stopped and dropped while its process lives on:
its handlers point back at it). `gc.unfreeze()` hands them all back to the
collector; the tests do that after each test (tests/conftest.py)."""

from __future__ import annotations

import contextlib
import ctypes
import gc
import os
import threading
import time
from typing import Dict, Iterator, Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache so a process restart does
    not re-pay XLA compile time for shapes it has already seen (the 5k×50k
    lattice compiles for minutes cold). One rule for the directory: where
    JAX_COMPILATION_CACHE_DIR is set, jax already reads it and nothing is set
    here; otherwise the fixed `<checkout>/.cache/xla` — the path is part of
    the cache key, so it never moves. Call before the first compile of the
    process (every entry point that builds a scheduler does); idempotent.
    Returns the directory in effect.

    Also where the process's XLA account goes in (sched/telemetry.py
    `xla_account`: one `jax.monitoring` listener pair a process, none with
    `KTPU_TELEMETRY=0`), so that the first compile is already on it."""
    import jax

    from ..sched.telemetry import xla_account

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        d = os.path.join(_CHECKOUT, ".cache", "xla")
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every compile that takes noticeable time, not just >1s ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    xla_account()
    return jax.config.jax_compilation_cache_dir


#: glibc's <malloc.h> parameter numbers, and the value each is given
_HEAP_POLICY = (("trim_threshold", -1, 256 << 20),
                ("top_pad", -2, 64 << 20),
                ("mmap_threshold", -3, 32 << 20))
_heap_mu = threading.Lock()
_heap_set: Optional[Dict[str, int]] = None
_widened = threading.local()   # .done: this thread's arena has its room


def _libc():
    """The process's libc with `mallopt`, `malloc` and `free` declared, or
    None where it has no `mallopt` (musl, macOS)."""
    try:
        libc = ctypes.CDLL(None)
        libc.mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    except (OSError, AttributeError):
        return None
    libc.mallopt.restype = ctypes.c_int
    libc.malloc.argtypes, libc.malloc.restype = (ctypes.c_size_t,), \
        ctypes.c_void_p
    libc.free.argtypes, libc.free.restype = (ctypes.c_void_p,), None
    return libc


def steady_heap() -> Dict[str, int]:
    """Give the process, and the calling thread, a heap that does not go to
    the kernel while a wave commits.

    For the process, once: glibc's `mallopt` with M_TRIM_THRESHOLD 256 MiB,
    M_TOP_PAD 64 MiB and M_MMAP_THRESHOLD 32 MiB. Setting any of them also
    stops glibc moving its thresholds with what the process freed before,
    which made two starts of one binary serve at different speeds. The pad
    carries the gain, and it has to be a whole arena heap (64 MiB): glibc
    sizes a NEW heap of a thread's arena by the pad but grows an existing
    one by the least it needs, one `mprotect` for a page or two. Measured
    (PR 33; `density-1k.backlog` on the chip's host, a gVisor sandbox where
    such a call costs ~0.3 ms): the thread that commits made 19,851 of them
    for 30,000 Bindings, 0.19 of a Binding's 0.40 ms. Drains in pods/s:
    plain 1,863-1,925; trim alone 1,752; mmap alone 1,948; pad alone 2,888;
    all three 2,677-2,890; 64 / 16 / 4 MiB 2,032 (a quarter of each heap).

    For the calling thread, once: where the runtime has started a hundred
    threads before the first server is built (the TPU client does), glibc
    has made all the arenas it will (8 a core), so a new thread is handed
    an old one, whose heap keeps growing page by page until it is full:
    the policy alone read 2,186 against 1,959. Two blocks just under the
    mmap threshold, taken and given back, make the next 62 MiB of THIS
    thread's arena writable in two calls (untouched, so not resident):
    1,921 -> 2,842 (6 pairs; `gang-5k.backlog` 1,615 -> 2,443). So a thread
    that will allocate through a wave calls this at its start, as the
    scheduler's loop does.

    The price is memory kept when the process goes quiet: at most the trim
    threshold plus the pad (320 MiB) above the live heap of the main arena,
    and a thread's arena at its peak (its heaps are 64 MiB, under the trim
    threshold, so they no longer shrink).
    Idempotent and thread-safe. Returns what the process was given, by name
    in bytes; `{}` where the libc has no `mallopt` (glibc only): nothing is
    set and no thread's arena touched there."""
    global _heap_set
    with _heap_mu:
        if _heap_set is None:
            libc = _libc()
            _heap_set = {} if libc is None else {
                name: value for name, param, value in _HEAP_POLICY
                if libc.mallopt(param, value) == 1}
        policy = dict(_heap_set)
    if len(policy) == len(_HEAP_POLICY) \
            and not getattr(_widened, "done", False):
        _widened.done = True
        libc = _libc()
        # two blocks, so that one which does not fit the arena's current
        # heap opens a new one (writable whole, by the pad) for both
        size = policy["mmap_threshold"] - (1 << 20)
        blocks = [libc.malloc(size), libc.malloc(size)]
        for block in reversed(blocks):
            libc.free(block)
    return policy


_listing_mu = threading.Lock()
_listing_depth = 0          # the `listing_heap()` contexts now open
_collector_was_on = False   # as the first of them found it


@contextlib.contextmanager
def listing_heap() -> Iterator[Dict[str, float]]:
    """The interpreter's cyclic collector stands aside while a server makes
    its INITIAL lists, and what they listed leaves its walk for good.

    Everything an informer's first list decodes survives (the lister's
    store, the scheduler's cache or the extender's mirror keep it), and none
    of it is a cycle (`json.loads`, `pod_from_v1`). The collector cannot
    know: each time the survivors number a quarter more than at its last
    full collection it walks all of them again, and finds nothing. Measured
    (ledger, PR 49): 5-8 full collections a drain's window, 6-7 of them
    inside `start/pods-sync`; 0.7-1.4 s of a drain's 8-11 s, 3.99 s of the
    extender cell's 20.6 s (50,000 pods), and every pause stops the loop,
    the informers, the store's pump and the client's watch together.

    Enter: the collector is turned off (`gc.disable()`). Exit, also by an
    exception: everything the process holds is moved to the collector's
    permanent generation (`gc.freeze()`: one pass that links lists, no
    walk), then the collector is put back as it was found: one found off
    is left off. No `gc.collect()` before the freeze: that is the walk over
    the listed population this takes out. The module's docstring says what
    the freeze costs a process that lives on.

    Process-wide, because the handlers that a sync waits for run on the
    informers' threads: contexts may nest and overlap across threads (a
    warm-up server and the measured one, two servers of a test); the first
    in turns the collector off, the LAST out freezes and restores. A relist
    in a running server is not a start: the collector stays on there.

    Yields a dict that is filled at the exit: `frozen_objects`, how many
    container objects the young generation held when this context froze it
    (what was made since the collector last ran and is still alive: with
    the collector off, the start's own population; 0 from a context that
    was not the last out), and `collector_off_s`, how long this context
    stood open. The count is the allocator's own (`gc.get_count()[0]`:
    allocations less deallocations since the last collection), one number
    read once. Until PR 51 it was `gc.get_freeze_count()` at either end,
    each a walk of the whole permanent generation inside the start's timed
    stages (~12 ns an object: 0.06 s over 5 million; ledger, PR 50: the
    extender cell's `start_nodes_sync_s` 0.125 -> 0.237 s)."""
    global _listing_depth, _collector_was_on
    took: Dict[str, float] = {}
    with _listing_mu:
        _listing_depth += 1
        if _listing_depth == 1:
            _collector_was_on = gc.isenabled()
            gc.disable()
    t0, froze = time.perf_counter(), 0
    try:
        yield took
    finally:
        with _listing_mu:
            _listing_depth -= 1
            if _listing_depth == 0:
                froze = gc.get_count()[0]
                gc.freeze()
                if _collector_was_on:
                    gc.enable()
        took["frozen_objects"] = froze
        took["collector_off_s"] = time.perf_counter() - t0

"""The reduction from a profiler trace to metrics: device busy and idle
seconds, the operations that took most device time, and the longest idle
gaps attributed to what the scheduler thread was doing in them.

Works on a neutral form, {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}, which `load_xplane` makes from the
profiler's .xplane.pb and which the recorded fixture beside the tests keeps
as JSON. Times inside a trace are nanoseconds from the profiler session's
start; `marks` finds the harness's own annotations, whose host-clock instants
are known, and so ties the trace to time.perf_counter.
"""

from __future__ import annotations

import glob
import os

MARK_OPEN, MARK_CLOSE = "bench-window-open", "bench-window-close"
#: device-plane lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)
#: CPU rehearsal only: the PjRt CPU client's threads stand in for a device
_CPU_LINE, _CPU_SKIP = "tf_XLAPjRtCpuClient", ("ThreadpoolListener", "end: ",
                                               "ThunkExecutor")


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO text
    (`%fusion.64 = s32[2949696]{...} fusion(...)`): keep the instruction's
    name and its result type."""
    if " = " not in name:
        return name[:80]
    head, rest = name.split(" = ", 1)
    result = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {result}"[:80]


def load_xplane(trace_dir: str) -> tuple:
    """The newest .xplane.pb under `trace_dir`, in the neutral form, cut to
    what the reducer reads (the device planes' operation lines and the
    harness's marks), and the inventory of everything it held."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes, held, names = [], [], {}
    for pl in data.planes:
        device = pl.name.startswith("/device:")
        lines = []
        for ln in pl.lines:
            events = []
            n = 0
            for e in ln.events:
                n += 1
                if device and ln.name in OP_LINES:
                    nm = names.get(e.name)
                    if nm is None:
                        nm = names[e.name] = short_name(e.name)
                    events.append((nm, e.start_ns, e.duration_ns))
                elif not device and (e.name in (MARK_OPEN, MARK_CLOSE)
                                     or ln.name.startswith(_CPU_LINE)):
                    events.append((e.name, e.start_ns, e.duration_ns))
            held.append([pl.name, ln.name, n])
            if events:
                lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}, held


def marks(trace: dict) -> dict:
    """{annotation name: start_ns} for the harness's own marks."""
    out = {}
    for pl in trace["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for name, start, _dur in ln["events"]:
                if name in (MARK_OPEN, MARK_CLOSE):
                    out.setdefault(name, start)
    return out


def device_ops(trace: dict, rehearse: bool = False) -> dict:
    """{device plane: [[name, start_ns, duration_ns], ...]} — one entry per
    chip. Without a device plane, and only in a rehearsal, the CPU client's
    executor threads together stand in for one device."""
    out = {}
    for pl in trace["planes"]:
        if not pl["name"].startswith("/device:"):
            continue
        evs = [e for ln in pl["lines"] if ln["name"] in OP_LINES
               for e in ln["events"] if e[2] > 0]
        if evs:
            out[pl["name"]] = evs
    if not out and rehearse:
        evs = [e for pl in trace["planes"] if pl["name"].startswith("/host:")
               for ln in pl["lines"] if ln["name"].startswith(_CPU_LINE)
               for e in ln["events"]
               if e[2] > 0 and not e[0].startswith(_CPU_SKIP)]
        if evs:
            out["/host:CPU (rehearsal)"] = evs
    return out


def busy_intervals(events: list, w0: float, w1: float) -> list:
    """The union of the events' intervals, clipped to [w0, w1], sorted.
    Operations nest (a while loop holds its body's fusions): a union counts
    each instant once."""
    import numpy as np

    if not events:
        return []
    start = np.array([e[1] for e in events], dtype=np.float64)
    end = start + np.array([e[2] for e in events], dtype=np.float64)
    keep = (start < w1) & (end > w0)
    start, end = np.maximum(start[keep], w0), np.minimum(end[keep], w1)
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    # a new interval opens where an event starts past everything before it
    opens = np.ones(len(start), dtype=bool)
    opens[1:] = start[1:] > reach[:-1]
    first = np.flatnonzero(opens)
    last = np.append(first[1:] - 1, len(start) - 1)
    return [[float(a), float(b)] for a, b in zip(start[first], reach[last])]


def idle_gaps(busy: list, w0: float, w1: float) -> list:
    gaps, at = [], w0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if w1 > at:
        gaps.append((at, w1))
    return gaps


def top_ops(events: list, w0: float, w1: float, k: int = 10) -> list:
    """The k operations with most device time, by name, over LEAF events
    only: an operation inside which another starts (a while loop, a call) is
    its children's time, not its own."""
    import numpy as np

    if not events:
        return []
    start = np.array([e[1] for e in events], dtype=np.float64)
    dur = np.array([e[2] for e in events], dtype=np.float64)
    order = np.lexsort((-dur, start))       # by start, the longer first
    start, dur = start[order], dur[order]
    end = start + dur
    leaf = np.ones(len(start), dtype=bool)
    leaf[:-1] = start[1:] >= end[:-1]
    clipped = np.minimum(end, w1) - np.maximum(start, w0)
    total: dict = {}
    for i in np.flatnonzero(leaf & (clipped > 0)):
        name = events[order[i]][0]
        total[name] = total.get(name, 0.0) + float(clipped[i]) / 1e9
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]


def attribute(gaps: list, phases: list, k: int = 10) -> list:
    """Idle seconds by what the host was doing. `phases`: (name, start_ns,
    end_ns) on the trace's clock, sorted, not overlapping; time outside
    every phase is `between-waves`."""
    total: dict = {}
    for g0, g1 in gaps:
        covered = 0.0
        for name, p0, p1 in phases:
            if p1 <= g0:
                continue
            if p0 >= g1:
                break
            lap = min(g1, p1) - max(g0, p0)
            if lap > 0:
                total[name] = total.get(name, 0.0) + lap / 1e9
                covered += lap
        rest = (g1 - g0) - covered
        if rest > 0:
            total["between-waves"] = total.get("between-waves", 0.0) \
                + rest / 1e9
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]


def wave_phases(waves: list, perf_to_ns) -> list:
    """The flight recorder's per-wave phases as (name, start_ns, end_ns) on
    the trace's clock; `perf_to_ns` maps a time.perf_counter instant there."""
    out = []
    for w in waves:
        at = w["t_start"]
        for name, dt in w["phases"]:
            out.append((name, perf_to_ns(at), perf_to_ns(at + dt)))
            at += dt
    return sorted(out, key=lambda p: p[1])


def reduce_trace(trace: dict, t_open: float, t_close: float, waves: list,
                 rehearse: bool = False) -> dict:
    """busy_s (averaged over the chips that ran anything), window_s, the
    breakdown, and the total device-operation seconds. `t_open`/`t_close`
    are the time.perf_counter instants at which the two marks were set."""
    mk = marks(trace)
    if MARK_OPEN not in mk or MARK_CLOSE not in mk:
        raise ValueError(f"trace lacks the window marks: {sorted(mk)}")
    w0, w1 = float(mk[MARK_OPEN]), float(mk[MARK_CLOSE])
    # one linear map through both marks absorbs clock drift between them
    scale = (w1 - w0) / max(t_close - t_open, 1e-9)

    def perf_to_ns(t: float) -> float:
        return w0 + (t - t_open) * scale

    devices = device_ops(trace, rehearse)
    if not devices:
        raise ValueError("trace holds no device operations; planes: "
                         f"{[pl['name'] for pl in trace['planes']]}")
    busy_s, all_events, gaps = [], [], []
    for _plane, evs in sorted(devices.items()):
        b = busy_intervals(evs, w0, w1)
        busy_s.append(sum(y - x for x, y in b) / 1e9)
        all_events += evs
        if not gaps:   # gaps of the first chip: with one chip, the chip
            gaps = idle_gaps(b, w0, w1)
    return {
        "busy_s": sum(busy_s) / len(busy_s),
        "window_s": (w1 - w0) / 1e9,
        "chips": len(busy_s),
        "device_ops": top_ops(all_events, w0, w1),
        "idle_gaps": attribute(gaps, wave_phases(waves, perf_to_ns)),
    }

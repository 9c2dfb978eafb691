"""A number reduced from the profiler trace of the window: `idle_pct` (the
share of the traced window in which no operation ran on the device) or
`roofline_pct` (the least time the cycles' bytes need at the chip's peak
bandwidth, over the seconds in which an operation ran on the device)."""

from benchmarks.harness import roofline


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr:
        return None
    if spec["select"] == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if spec["select"] == "roofline_pct":
        if obs["rehearse"]:
            return None   # a CPU has no place in the table of peaks
        cycles = sum(1 for w in obs["waves"] if w.get("device_split"))
        if not cycles or not tr["busy_s"]:
            return None
        return roofline.roofline_pct(obs["dims"], cycles, tr["busy_s"],
                                     obs["device"]["kind"])
    raise ValueError(f"unknown trace selector {spec['select']!r}")

"""The extender's share of its roofline, from the profiler trace and the
per-pod records: each device dispatch of a verb (`filter`'s mask, its reasons,
`prioritize`'s scores) rebuilds the cycle's lattice over the whole mirror, so
it has to move a cycle's bytes (roofline.cycle_bytes, from the cell's
capacities alone) whatever it answers. The least time those bytes need at the
chip's peak bandwidth, over the seconds in which an operation ran on the
device. The count of dispatches is the records' `dispatches`, summed over the
window's pods; a program that records none gives nothing."""

from benchmarks.harness import roofline


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    dispatches = sum(w.get("dispatches", 0) for w in obs["waves"])
    if not dispatches:
        return None
    return roofline.roofline_pct(obs["dims"], dispatches, tr["busy_s"],
                                 obs["device"]["kind"])

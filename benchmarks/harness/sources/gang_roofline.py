"""The gang loop's share of its roofline, from the profiler trace and the wave
records: ops/gang.py restarts the wave fixpoint once per rejection round, so a
gang-bearing dispatch is the waves kernel run `gang_rounds` times and has to
move a cycle's bytes (roofline.cycle_bytes, from the cell's capacities alone)
that many times, whatever implements the loop. The least time those bytes
need at the chip's peak bandwidth, over the seconds in which an operation ran
on the device. The count of fixpoints is the record's `gang_rounds`, summed
over the window's waves; a program that records none gives nothing."""

from benchmarks.harness import roofline


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    fixpoints = sum(w.get("gang_rounds", 0) for w in obs["waves"])
    if not fixpoints:
        return None
    return roofline.roofline_pct(obs["dims"], fixpoints, tr["busy_s"],
                                 obs["device"]["kind"])

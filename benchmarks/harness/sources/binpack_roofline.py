"""The waves engine's share of its roofline on a cell whose classes FILL
(claim as many pods as fit on the best node, then on the next: a packing
score over an extended resource), from the profiler trace and the wave
records: a cycle has to move `roofline.cycle_bytes` at the cell's capacities,
the resource axis R with its extended slots included (a node's allocatable
and requested rows are R words each), AND what the fill claim takes, whatever
implements it. That, 4 bytes a word:
  classes  SC x R request rows read (what one pod of the class asks)
  counts   SC x N "how many pods of the class fit here", produced once and
           read back once along the class's score order
  claims   SC x N "how many the class claims here", produced once
Times the window's cycles in which a class filled (`fill_classes` on the
wave's record, waves that dispatched), at the chip's peak bandwidth, over the
seconds in which an operation ran on the device. The bytes side binds (a
division per class, node and resource at most, no dense arithmetic), and like
every cycle here the waves read latency-bound, far below it. A program that
records no `fill_classes` gives nothing."""

from benchmarks.harness import roofline


def fill_bytes(dims: dict) -> int:
    return 4 * dims["SC"] * dims["R"] + 3 * 4 * dims["SC"] * dims["N"]


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    cycles = sum(1 for w in obs["waves"]
                 if w.get("device_split") and w.get("fill_classes"))
    if not cycles:
        return None
    least = cycles * (roofline.cycle_bytes(obs["dims"])
                      + fill_bytes(obs["dims"])) \
        / roofline.peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / tr["busy_s"]

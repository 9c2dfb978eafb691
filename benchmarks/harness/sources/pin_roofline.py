"""The waves engine's share of its roofline on a cell whose pods carry a pin
(the one node a pod's required node affinity names), from the profiler trace
and the wave records: a cycle has to move `roofline.cycle_bytes` (from the
cell's capacities alone) AND what deciding the pins takes, whatever implements
it. That, 4 bytes a word:
  pods     P pins read (the node each pod names, or none)
  nodes    N names read (which node bears the name a pin gives)
  classes  SC x N bits of "a pod of the class still waits for this node",
           1 byte each, produced and read back once
Times the window's cycles that held a pinned pod (`pinned` on the wave's
record, waves that dispatched), at the chip's peak bandwidth, over the
seconds in which an operation ran on the device. The bytes side binds (a
compare per pod and node at most, no dense arithmetic), and like every cycle
here the waves read latency-bound, far below it. A program that records no
`pinned` gives nothing."""

from benchmarks.harness import roofline


def pin_bytes(dims: dict) -> int:
    return 4 * (dims["P"] + dims["N"]) + 2 * dims["SC"] * dims["N"]


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    cycles = sum(1 for w in obs["waves"]
                 if w.get("device_split") and w.get("pinned"))
    if not cycles:
        return None
    least = cycles * (roofline.cycle_bytes(obs["dims"])
                      + pin_bytes(obs["dims"])) \
        / roofline.peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / tr["busy_s"]

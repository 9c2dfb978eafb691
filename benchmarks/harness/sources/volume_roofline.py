"""The waves engine's share of its roofline on a cell whose pods mount
volumes, from the profiler trace and the wave records: a cycle has to move
`roofline.cycle_bytes` (from the cell's capacities alone) AND the volume
state the volume predicates are decided on, whatever implements the plane.
That state, 4 bytes a word:
  nodes    N x DR attach limits read; N x DR counts of volumes one pod names
           and N x VW words each of attached / attached read-write shared
           volumes, read and written back (a wave's commits land in them)
  classes  SC x DR counts a pod of the class brings
`DR` (volume drivers) and `VW` (words of 32 SHARED volumes) are not among the
capacities the harness hands a reader, so the metric's file states the
values the cell is provisioned with: no capacity of this cell follows the
number of volumes that one pod alone names. Times the window's cycles (waves
that dispatched), at the chip's peak bandwidth, over the seconds in which an
operation ran on the device. The bytes side binds (no dense arithmetic), and
like every cycle here the waves read latency-bound, far below it. Only waves
that popped a pod with a volume count (`volume_pods` on the record): a
program that records none gives nothing."""

from benchmarks.harness import roofline


def volume_bytes(dims: dict, dr: int, vw: int) -> int:
    n = dims["N"]
    return 4 * (n * dr + 2 * n * dr + 2 * 2 * n * vw + dims["SC"] * dr)


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    cycles = sum(1 for w in obs["waves"]
                 if w.get("device_split") and w.get("volume_pods"))
    if not cycles:
        return None
    least = cycles * (roofline.cycle_bytes(obs["dims"])
                      + volume_bytes(obs["dims"], spec["DR"], spec["VW"])) \
        / roofline.peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / tr["busy_s"]

"""The preemption what-if's share of its roofline, from the profiler trace and
the wave records: the bytes one what-if dispatch has to move, reckoned here
from the cell's capacities and the lane count alone (never from the program's
own arrays), times the window's `preempt_dispatches`, at the chip's peak
bandwidth, over the seconds in which an operation ran on the device inside
the waves' `requeue` phase (the phase's seconds less the idle seconds the
trace's reduction attributes to it; the pass's fresh snapshot uploads inside
the phase too, so the share reads a little low).

A dispatch evaluates `lanes` preemptor templates at once (the program pads to
that many). Per lane, 4 bytes a word:
  existing  E x (node, class, priority, creation) read, E x 1 victim mask
            written
  counts    the survivors' [SC, N] class-by-node histogram written and read,
            and its two [S, N] products (term counts, anti-affinity holders)
  nodes     N x (allocatable R + used R) read; the five [N] keys of
            pickOneNodeForPreemption and the [N] order written
`S` (interned selector terms) is not among the capacities the harness hands
a reader, so the metric's file states the value the cell is provisioned
with, beside `lanes`. Like every cycle here the what-if is compare-and-select
over tables and a sequential scan over E: it reads latency-bound, far below
its roofline. A program that records no `preempt_dispatches` gives nothing."""

from benchmarks.harness import roofline


def whatif_bytes(dims: dict, lanes: int, s_terms: int) -> int:
    n, e = dims["N"], dims["E"]
    existing = e * 4 * 4 + e
    counts = 2 * dims["SC"] * n * 4 + 2 * s_terms * n * 4
    nodes = n * 2 * dims["R"] * 4 + 6 * n * 4
    return lanes * (existing + counts + nodes)


def read(obs: dict, spec: dict):
    tr = obs.get("trace")
    if not tr or obs["rehearse"]:
        return None   # no trace; a CPU has no place in the table of peaks
    dispatches = sum(w.get("preempt_dispatches", 0) for w in obs["waves"])
    if not dispatches:
        return None
    in_phase = sum(d for w in obs["waves"] for name, d in w["phases"]
                   if name == "requeue")
    idle = sum(s for name, s in tr["idle_gaps"] if name == "requeue")
    busy = in_phase - idle
    if busy <= 0:
        return None
    least = dispatches * whatif_bytes(obs["dims"], spec["lanes"], spec["S"]) \
        / roofline.peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / busy

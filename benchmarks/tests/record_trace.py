#!/usr/bin/env python3
"""Makes recorded_trace.json, for the chip: a `--trace 1` run of a cell, its
profiler trace cut after the device's first `--keep-events` operations (the
close mark is moved there on the same clock map) and to the device's
operation lines and the harness's marks, with what the reducer gives for it.
Run once; the file is kept beside test_trace.py.

    python3 benchmarks/tests/record_trace.py --workload flagship-5k.backlog \
        --seed 5 --seconds 40 --keep-events 3000 \
        --out chiprun_out/recorded_trace.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-events", type=int, default=3000)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import cell, trace

    kept = {}
    real = trace.reduce_trace

    def spy(neutral, t_open, t_close, waves, rehearse=False):
        kept.update(neutral=neutral, t_open=t_open, t_close=t_close,
                    waves=waves, rehearse=rehearse)
        return real(neutral, t_open, t_close, waves, rehearse)

    trace.reduce_trace = spy
    _code, res = cell.run_cell(args.workload, args.seed, args.seconds, True,
                               rehearse=args.rehearse)
    print(json.dumps(res), flush=True)

    mk = trace.marks(kept["neutral"])
    w0, w1 = mk[trace.MARK_OPEN], mk[trace.MARK_CLOSE]
    starts = sorted(e[1] + e[2] for evs in trace.device_ops(
        kept["neutral"], kept["rehearse"]).values() for e in evs
        if e[1] >= w0)
    cut = min(starts[min(args.keep_events, len(starts)) - 1] + 1000, w1)
    share = (cut - w0) / (w1 - w0)
    t_cut = kept["t_open"] + share * (kept["t_close"] - kept["t_open"])
    planes = [{"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.MARK_OPEN, w0, 1], [trace.MARK_CLOSE, cut, 1]]}]}]
    for plane, evs in trace.device_ops(kept["neutral"],
                                       kept["rehearse"]).items():
        planes.append({"name": plane if plane.startswith("/device:")
                       else "/device:REHEARSAL:0", "lines": [
            {"name": trace.OP_LINES[0],
             "events": [e for e in evs if e[1] < cut]}]})
    small = {"planes": planes}
    waves = [{"t_start": w["t_start"], "duration_s": w["duration_s"],
              "phases": w["phases"]} for w in kept["waves"]
             if w["t_start"] < t_cut]
    red = real(small, kept["t_open"], t_cut, waves)
    doc = {"what": f"{args.workload} seed {args.seed}, --trace 1, "
                   f"device {res['device']['kind']}; the window up to the "
                   f"device's first {args.keep_events} operations, "
                   "operation lines and marks only",
           "t_open": kept["t_open"], "t_close": t_cut, "waves": waves,
           "expect": {"window_s": red["window_s"], "busy_s": red["busy_s"],
                      "top_ops": [n for n, _ in red["device_ops"]],
                      "longest_gap": red["idle_gaps"][0][0]},
           "trace": small}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
        f.write("\n")
    print(f"wrote {args.out}: "
          f"{sum(len(l['events']) for p in planes for l in p['lines'])} "
          f"events, {os.path.getsize(args.out)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

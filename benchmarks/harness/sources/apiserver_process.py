"""The apiserver's own account, where it is a process of its own: its
`/metrics` text as the wiring read it when the measured scheduler started
(`open`) and after the window (`close`), handed over through `note`. One
number from the two readings:

    {"select": "sum", "metric": M, "labels": {...}}
        the growth of histogram M's `_sum` for those labels, in seconds
        (`apiserver_request_duration_seconds` of `create pods/binding`: the
        server's own time in the window's Bindings; reduce `per_bound_pod`)
    {"select": "rate", "metric": M}
        the growth of counter M over the seconds between the two readings
        (`process_cpu_seconds_total`: the share of one core the apiserver's
        process computed for; above 1 with more threads than one)

The second reading is the wiring's first act after the window (its
`counters()`), a fraction of a second after the last Binding. A wiring whose
apiserver is in the benchmark's own process hands nothing, and a program
whose `/metrics` lacks the series (the parent's) gives nothing: the metric
is left out."""

from __future__ import annotations

#: "open" / "close" -> (perf_counter instant, the /metrics text)
READINGS: dict = {}


def note(which: str, at: float, text: str) -> None:
    if which == "open":
        READINGS.clear()
    READINGS[which] = (at, text)


def value(text: str, series: str, labels: dict):
    """The value of `series{labels...}` in a Prometheus text exposition;
    None where no line has the name and every label."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    for line in text.splitlines():
        if not line.startswith(series) or line[len(series):len(series) + 1] \
                not in ("{", " "):
            continue
        head, _, number = line.rpartition(" ")
        if all(w in head for w in want):
            return float(number)
    return None


def read(obs: dict, spec: dict):
    if "open" not in READINGS or "close" not in READINGS:
        return None
    (t0, before), (t1, after) = READINGS["open"], READINGS["close"]
    if t1 <= t0:
        return None
    series = spec["metric"] + ("_sum" if spec["select"] == "sum" else "")
    labels = spec.get("labels", {})
    end = value(after, series, labels)
    if end is None:
        return None
    grown = end - (value(before, series, labels) or 0.0)
    return grown / (t1 - t0) if spec["select"] == "rate" else grown

"""What a pod waited for, from a wave's record. `wait` names an entry of
`waits` (`queue`: pop instant less first-seen stamp over the popped batch;
`confirm`: the informer's confirmation less the Binding's completion, over
the confirmations since the previous wave), each `[count, sum_s, max_s]`:
the result is ONE number, the mean over the window's waves weighted by
count (reduce it with `first`). `field` instead names a plain number on
the record (`assumed_outstanding`), one sample per wave. A program that
records neither gives nothing."""


def read(obs: dict, spec: dict):
    if "field" in spec:
        return [w[spec["field"]] for w in obs["waves"]
                if spec["field"] in w] or None
    entries = [w["waits"][spec["wait"]] for w in obs["waves"]
               if (w.get("waits") or {}).get(spec["wait"])]
    count = sum(e[0] for e in entries)
    return sum(e[1] for e in entries) / count if count else None

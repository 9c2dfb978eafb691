"""What ran inside a flight-recorder phase, from the `children` field of a
wave's record: `{path: [count, total_s, max_s]}`, a path's segments being
its parents (`bind-commit/bind-call/apiserver.bind/store.txn`). One sample
per wave: the total seconds under `path`; or, with `phase` and `less`, the
phase's seconds less the total under the path `less` (the phase's own
time). Waves without the path give no sample; a program that records no
`children` gives nothing."""


def read(obs: dict, spec: dict):
    out = []
    for w in obs["waves"]:
        ch = w.get("children")
        if not ch:
            continue
        if "less" in spec:
            inner = ch.get(spec["less"])
            phase = [d for name, d in w["phases"] if name == spec["phase"]]
            if inner and phase:
                out.append(sum(phase) - inner[1])
        elif spec["path"] in ch:
            out.append(ch[spec["path"]][1])
    return out or None

"""What the server loop did between two waves, from the `loop` field the
later wave's record carries: seconds in one of its phases (`phase`:
`post-wave` `lock-wait` `batch-wait` `idle-wait`, and `start` `recover`
`standby`), or one field of the informer handlers' account (`handlers`:
`calls`, `wait_s` on the server's lock, `held_s`). One sample per wave.

A wave's `loop` covers the gap since the previous wave, so the first wave
of a window may reach back before the window opened. `obs` holds the
window's length, not its start; the start is no earlier than the last
wave's start less `window_s`, and a `loop` that began before that instant
is left out. A program that records no `loop` gives nothing."""


def read(obs: dict, spec: dict):
    waves = [w for w in obs["waves"] if w.get("loop")]
    if not waves:
        return None
    opened_after = obs["waves"][-1]["t_start"] - obs["window_s"]
    out = []
    for w in waves:
        loop = w["loop"]
        if loop["t_start"] < opened_after:
            continue
        if "handlers" in spec:
            out.append(loop["handlers"][spec["handlers"]])
        else:
            out.append(sum(s for name, s in loop["phases"]
                           if name == spec["phase"]))
    return out

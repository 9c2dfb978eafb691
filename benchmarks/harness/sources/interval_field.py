"""A plain number on a wave's record that covers the INTERVAL since the
record before it ended (`field`: `gc_pause_s`, `gc_full_collections`: what
the interpreter's collector did in that interval), one sample per wave.

Whether an interval began inside the window is a question of the window's
FIRST record alone: every later one began where a wave of the window ended.
`obs["waves"]` holds every wave that started inside the window, so the wave
before the first one started before the window opened, and the first
record's interval reaches back to where that wave ended: into set-up (the
harness's own `gc.collect()` among its pauses). It is left out, unless
there was no such wave: the server's life began in the interval (its `loop`
holds the `start` lap, and the collector was marked at `loop.t_start`), and
by `loop.py`'s rule not before the window can have opened (no earlier than
the last wave's start less `window_s`). A program that records no such
field gives nothing."""


def read(obs: dict, spec: dict):
    field = spec["field"]
    waves = [w for w in obs["waves"] if field in w]
    if not waves:
        return None
    first = obs["waves"][0]
    if first is waves[0]:
        loop = first.get("loop") or {}
        opened_after = obs["waves"][-1]["t_start"] - obs["window_s"]
        if not (any(name == "start" for name, _s in loop.get("phases", ()))
                and loop["t_start"] >= opened_after):
            waves = waves[1:]
    return [w[field] for w in waves]

"""A field of the flight recorder's wave record, one sample per wave; `gap`
is the idle time between one wave's end and the next wave's start."""


def read(obs: dict, spec: dict):
    waves = obs["waves"]
    if spec["field"] == "gap":
        return [max(b["t_start"] - (a["t_start"] + a["duration_s"]), 0.0)
                for a, b in zip(waves, waves[1:])]
    return [w[spec["field"]] for w in waves]

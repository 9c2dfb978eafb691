"""A flight-recorder phase: seconds spent in `spec["phase"]`, one sample per
wave of the window (a wave that never entered the phase gives none)."""


def read(obs: dict, spec: dict):
    out = []
    for w in obs["waves"]:
        dt = [d for name, d in w["phases"] if name == spec["phase"]]
        if dt:
            out.append(sum(dt))
    return out

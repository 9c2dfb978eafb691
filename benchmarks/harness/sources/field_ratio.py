"""The ratio of two plain numbers on the wave records, each summed over the
window's waves (`over` / `under`): seconds an event, bytes a request. ONE
number (reduce it with `first`). Records that lack either field are left
out; none left, or nothing counted under the line, gives nothing."""


def read(obs: dict, spec: dict):
    waves = [w for w in obs["waves"]
             if spec["over"] in w and spec["under"] in w]
    under = sum(w[spec["under"]] for w in waves)
    return sum(w[spec["over"]] for w in waves) / under if under else None

"""device.memory_stats() of the fullest chip, read after the window."""


def read(obs: dict, spec: dict):
    return obs["memory"].get(spec["field"])

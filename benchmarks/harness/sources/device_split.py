"""The dispatch worker's split of one wave's device call
(`launch_s`, `execute_s`, `readback_s`), one sample per wave."""


def read(obs: dict, spec: dict):
    return [w["device_split"][spec["field"]] for w in obs["waves"]
            if w.get("device_split")]

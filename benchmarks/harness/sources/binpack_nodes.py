"""What the wiring `local_policy` counted over the apiserver's listing when
the run was over, handed over through `note` (its `counters()`, the
harness's first read after the window): `opened`, the accelerator nodes that
hold a pod of the backlog, and `reference`, what the plain sequential
reference (checks/accelerators.py `sequential`) opens for the same queue on
the same cluster. `select` picks one of the two, or `over_reference`: their
ratio, 1.0 where the system under test packs as the reference's loop does,
above it where it opens more nodes. A wiring that notes nothing gives
nothing, and the metric is left out."""

from __future__ import annotations

NOTED: dict = {}


def note(opened: int, reference: int) -> None:
    NOTED.clear()
    NOTED.update(opened=opened, reference=reference)


def read(obs: dict, spec: dict):
    if not NOTED:
        return None
    if spec["select"] == "over_reference":
        return NOTED["opened"] / NOTED["reference"] \
            if NOTED["reference"] else None
    return NOTED[spec["select"]]

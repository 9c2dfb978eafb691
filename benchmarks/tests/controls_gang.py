"""The gang configuration's control, for `run_cell(sabotage=)` and the control
run on the chip (`chip_control_gang.py`): it breaks the guarantee `gang-5k`
adds, no job partly bound, and the run must come out `correct: false`."""

from __future__ import annotations

import dataclasses


def ignore_gangs(cluster, server) -> None:
    """The scheduler never sees a pod's group: the two
    `pod-group.scheduling.sigs.k8s.io` annotations are dropped where a pod is
    read, so every member is placed for itself. The members of an incomplete
    job, which all fit a node, get bound: caught by `gangs_partly_bound`, one
    count a job."""
    import kubernetes_tpu.sched.server as srv

    real = srv.pod_from_v1
    if getattr(real, "_bench_control", False):
        return

    def pod_from_v1(obj):
        return dataclasses.replace(real(obj), pod_group="", min_member=0)

    pod_from_v1._bench_control = True
    srv.pod_from_v1 = pod_from_v1


CONTROLS = {"ignore_gangs": ignore_gangs}

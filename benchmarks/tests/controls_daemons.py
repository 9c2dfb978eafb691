"""The DaemonSet configuration's controls, for `run_cell(sabotage=)` and the
control runs on the chip (`chip_control_daemons.py`): each breaks one
guarantee `daemonset-5k` adds, where the measured scheduler decodes what its
informers hand it, and the run must come out `correct: false` by the count
named. The engines, the representation and the checks are left alone."""

from __future__ import annotations


def ignore_pins(cluster, server) -> None:
    """The measured scheduler's pods lose their required node affinity at
    decode (the controller's matchFields term is all a daemon pod has
    there). It schedules them as plain pods with tolerations: every one
    binds, the 200 named for full nodes among them, by score and not by
    name: `pinned_elsewhere` on nearly every pod."""
    from dataclasses import replace

    real = server._to_pod

    def to_pod(obj):
        pod = real(obj)
        if pod.affinity.node_required is not None:
            pod.affinity = replace(pod.affinity, node_required=None)
        return pod

    server._to_pod = to_pod


def drop_daemon_tolerations(cluster, server) -> None:
    """The measured scheduler's pods lose what their tolerations tolerate at
    decode: each is exchanged for one that names a taint no node carries (as
    many as before, so the program's capacities, `Dims.TL` among them, stay
    the warm-up's; emptied out, the measured scheduler compiled a program of
    its own inside its window at the published size: my chip run, PR 49).
    The 400 daemon pods named for cordoned nodes are refused there
    (`node.kubernetes.io/unschedulable:NoSchedule` and spec.unschedulable)
    and, being pinned, go nowhere: `pods_never_bound` and `daemon_missing`
    400 each (8 at the rehearsal size)."""
    from dataclasses import replace

    real = server._to_pod

    def to_pod(obj):
        pod = real(obj)
        pod.tolerations = tuple(
            replace(t, key=f"benchmarks.invalid/tolerates-nothing-{i}")
            for i, t in enumerate(pod.tolerations))
        return pod

    server._to_pod = to_pod


CONTROLS = {"ignore_pins": ignore_pins,
            "drop_daemon_tolerations": drop_daemon_tolerations}

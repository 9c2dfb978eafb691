"""The volume configuration's controls, for `run_cell(sabotage=)` and the
control runs on the chip (`chip_control_volumes.py`): each breaks one
guarantee `csi-pvs-5k` adds, where the measured scheduler decodes what its
informers hand it, and the run must come out `correct: false` by the count
named. The device plane, the binder and the checks are left alone."""

from __future__ import annotations


def ignore_volumes(cluster, server) -> None:
    """The measured scheduler's pods lose their volumes at decode: claims and
    direct mounts alike, pending and bound. It schedules them as plain pods.
    At the published size every one binds and the limit of 39 refuses
    nothing, so only the scheduler's own state tells: `node_volume_state_wrong`
    on every node that took a volume pod. At the rehearsal size, where limits
    of 1 and 3 alternate, an even spread puts two on nodes that may hold one:
    `nodes_over_volume_limit` and `volume_bindings_refused_at_their_turn`
    too."""
    real = server._to_pod

    def to_pod(obj):
        pod = real(obj)
        pod.volumes, pod.claims = (), ()
        return pod

    server._to_pod = to_pod


def ignore_volume_limits(cluster, server) -> None:
    """Every node's attach limits read as unlimited at decode (allocatable
    and CSINode alike); volumes are still resolved and counted, so the
    scheduler's state adds up. Caught at the rehearsal size by
    `nodes_over_volume_limit`; at the published size nothing refuses and the
    run is sound, which is why the limits are held where they bind."""
    real = server._to_node

    def to_node(obj):
        node = real(obj)
        node.volume_limits = {}
        return node

    server._to_node = to_node


CONTROLS = {"ignore_volumes": ignore_volumes,
            "ignore_volume_limits": ignore_volume_limits}

"""Ways to break the timed path underneath a run, for the tests here and for
the control runs on the chip (chip_control.py). Each is a `sabotage(cluster,
server)` for harness.cell.run_cell; each breaks one guarantee the
configurations state, and the run must come out `correct: false`."""

from __future__ import annotations

import dataclasses
import zlib


def drop_bindings(cluster, server) -> None:
    """The binder acknowledges one Binding in 53 without writing it: an
    acknowledged placement that is not at the apiserver (a part of the batch
    left out). Caught by `pods_never_bound`."""
    binder = server.scheduler.binder
    real = binder.bind

    def bind(pod, node_name):
        if zlib.crc32(pod.name.encode()) % 53 == 0:
            return True
        return real(pod, node_name)

    binder.bind = bind


def misdirect_bindings(cluster, server) -> None:
    """One Binding in 53 is written to the next node instead of the one the
    engine chose (an answer altered where it is produced). The apiserver and
    the watch agree with each other, so only the predicates can tell: caught
    where the configuration's constraints bind (host anti-affinity)."""
    binder = server.scheduler.binder
    real = binder.bind

    def bind(pod, node_name):
        if zlib.crc32(pod.name.encode()) % 53 == 0:
            i = int(node_name.rsplit("-", 1)[1])
            node_name = f"node-{(i + 1) % cluster.cfg['nodes']}"
        return real(pod, node_name)

    binder.bind = bind


def ignore_required_affinity(cluster, server) -> None:
    """The scheduler never sees a pod's required pod affinity, so it places
    affinity groups before their partners exist. Caught by
    `bindings_infeasible_at_their_turn` (the reference's replay)."""
    import kubernetes_tpu.sched.server as srv

    real = srv.pod_from_v1
    if getattr(real, "_bench_control", False):
        return

    def pod_from_v1(obj):
        pod = real(obj)
        pod.affinity = dataclasses.replace(pod.affinity, pod_required=())
        return pod

    pod_from_v1._bench_control = True
    srv.pod_from_v1 = pod_from_v1


def lower_commit_precision(cluster, server) -> None:
    """The wave commit's f32 matmul (ops/waves.py WSYM) at the chip's default
    precision instead of HIGHEST — the step PR 21 repaired. It moves scores,
    never feasibility; what it does to a run is recorded in PERF.md."""
    import kubernetes_tpu.ops.waves as waves

    class _Default:
        HIGHEST = None   # precision=None is the backend's default

    class _Lax:
        Precision = _Default

        def __getattr__(self, name):
            import jax.lax

            return getattr(jax.lax, name)

    waves.lax = _Lax()


CONTROLS = {f.__name__: f for f in (drop_bindings, misdirect_bindings,
                                    ignore_required_affinity,
                                    lower_commit_precision)}

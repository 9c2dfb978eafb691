"""The extender configuration's controls, for `run_cell(sabotage=)` and the
control runs on the chip (`chip_control_extender.py`): each breaks one
guarantee `extender-5k` states underneath the served extender, and the run
must come out `correct: false`."""

from __future__ import annotations

import dataclasses


def ignore_required_affinity(cluster, server) -> None:
    """The extender never sees a pod's required pod (anti-)affinity: the
    terms are dropped where a pod is read, off the wire (`backend.py`) and
    off the watch (`served.py`; an existing pod's anti-affinity refuses a
    newcomer as its own does, so the mirror's pods lose theirs too). A pod
    of a host anti-affinity group is then passed the ~1,000 hosts that hold
    its group: caught by `filter_answers_wrong` (the reference refuses them)
    and, where the stand-in's draw lands on one, by
    `bindings_infeasible_at_their_turn`. (Every zone holds every affinity
    partner here, so the affinity half alone would change no answer.) It
    changes the shapes the verbs compile for (no term is interned), so the
    chip script puts it in place before the warm-up."""
    import kubernetes_tpu.extender.backend as backend
    import kubernetes_tpu.extender.served as served

    real = backend.pod_from_v1
    if getattr(real, "_bench_control", False):
        return

    def pod_from_v1(obj):
        pod = real(obj)
        pod.affinity = dataclasses.replace(pod.affinity, pod_required=(),
                                           anti_required=())
        return pod

    pod_from_v1._bench_control = True
    backend.pod_from_v1 = served.pod_from_v1 = pod_from_v1


def no_assume_on_bind(cluster, server) -> None:
    """`bind` writes the Binding but does not assume the pod, and the pod
    informer's echo of the run's Bindings is held back: the mirror never
    learns of a placement of the window, and every later `filter` is
    answered from a stale lattice. The second pod of a host anti-affinity
    group is passed the host the first one took: caught by
    `filter_answers_wrong` (the reference, rebuilt from the client's watch,
    refuses it) and, where the draw lands there, by
    `bindings_infeasible_at_their_turn` and `invariant_violations`."""
    served = server.served
    served.backend.cache.assume_pod = lambda pod, node_name: None
    real = served._on_pod

    def on_pod(obj):
        if not obj["metadata"]["name"].startswith("job-"):
            real(obj)   # the pre-bound population and the warm-up's pods

    served._on_pod = on_pod


CONTROLS = {"ignore_required_affinity": ignore_required_affinity,
            "no_assume_on_bind": no_assume_on_bind}

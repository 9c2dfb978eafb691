"""kubernetes_tpu — a TPU-native scheduling framework with the capability
surface of the Kubernetes control plane's scheduler.

Instead of the reference's per-pod, per-node Go loops (pkg/scheduler), the
Filter and Score phases are boolean constraint masks and score tensors over a
(pod-class × node) lattice, evaluated in one XLA dispatch per scheduling cycle;
assignment is a lax.scan that preserves sequential assume semantics.

Layers:
  api/       — object model + executable reference semantics (the oracle)
  state/     — vocab interning, class tables, device arrays, cache
  ops/       — the tensor kernels (Filter masks, Score tensors, assignment)
  sched/     — cycle driver, queue, framework plugin surface
  parallel/  — Mesh/pjit sharding of the lattice across chips
  extender/  — HTTP Scheduler-Extender boundary to stock clusters
  models/    — end-to-end scheduling profiles (flagship entry points)
"""

__version__ = "0.1.0"

from .api.types import (  # noqa: F401
    Affinity,
    HostPort,
    LabelSelector,
    Node,
    NodeSelector,
    NodeSelectorTerm,
    Op,
    Pod,
    PodAffinityTerm,
    Requirement,
    Resources,
    Taint,
    TaintEffect,
    Toleration,
    TolerationOp,
    TopologySpreadConstraint,
    UnsatisfiableAction,
)


def __getattr__(name):
    # BatchScheduler / CycleResult live in sched/cycle.py, which imports jax.
    # Resolved on first use so that `import kubernetes_tpu` — and with it
    # every launcher that only wants a helper module (bench.py's parent) —
    # stays off jax: one process per chip, and the launcher is not it.
    if name in ("BatchScheduler", "CycleResult"):
        from .sched import cycle

        return getattr(cycle, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

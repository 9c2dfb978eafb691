"""The accelerator-pool configuration's controls, for `run_cell(sabotage=)`
and the control runs on the chip (`chip_control_binpack.py`): each takes away
one thing `gpu-binpack-5k` rests on, at the measured scheduler, and the run
must come out `correct: false` by the count named. The engines, the
representation and the checks are left alone."""

from __future__ import annotations


def default_provider_scores(cluster, server) -> None:
    """The Policy withheld: the measured scheduler scores as the default
    provider does (LeastAllocated, BalancedAllocation, the spreading
    scores), which push pods apart. Every placement is still valid, so
    nothing is over anything: the small accelerator pods are spread over the
    empty nodes of the pool, and the whole-node pods that come after them in
    the queue find none: `pods_never_bound`, and nothing else. (The engine's
    configuration is traced: the same executable runs.)"""
    server.scheduler.engine_config = None


def ignore_extended_resources(cluster, server) -> None:
    """The measured scheduler's pods lose what they ask of extended
    resources at decode (cpu and memory stay). An accelerator node then
    takes as many accelerator pods as its cpu and memory hold, nine
    one-GPU pods of 10 CPU on its 96 CPU: `nodes_over_extended_resource`,
    and `extended_bindings_refused_at_their_turn` at the replay."""
    from dataclasses import replace

    real = server._to_pod

    def to_pod(obj):
        pod = real(obj)
        if pod.requests.scalars:
            pod.requests = replace(pod.requests, scalars=())
        return pod

    server._to_pod = to_pod


CONTROLS = {"default_provider_scores": default_provider_scores,
            "ignore_extended_resources": ignore_extended_resources}

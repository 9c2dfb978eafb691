"""Tensor kernels: the reference's per-(pod,node) Go predicates/priorities
re-expressed as batched XLA computations over interned class tables."""

import os

ENGINES = ("waves", "runs", "scan")


def configured_engine() -> str:
    """The assignment engine `KTPU_ASSIGN` names, read per call (tests set
    it after constructing a scheduler): 'waves' (default — wave-parallel
    dense admission, ops/waves.py), 'runs' (run-length-collapsed sequential
    admission, ops/runs.py — bit-equal to the scan with the serial chain
    shrunk from P pod-steps to #class-runs steps), or 'scan' (the literal
    sequential-assume lax.scan, ops/assign.py) kept for debugging and as
    the executable spec both other engines are tested against. The ONE
    reader of the variable: the cache asks it whether to emit a RunPlan,
    `sched/cycle.py plan_engine` what a wave dispatches. Unrecognized
    values normalize to 'waves': downstream routing keys on exact engine
    names, so a typo must land on a known engine, not fall through the
    dispatch untyped."""
    eng = os.environ.get("KTPU_ASSIGN", "waves")
    return eng if eng in ENGINES else "waves"

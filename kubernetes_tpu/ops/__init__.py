"""Tensor kernels: the reference's per-(pod,node) Go predicates/priorities
re-expressed as batched XLA computations over interned class tables."""

#: the assignment engines: 'waves' (ops/waves.py, wave-parallel dense
#: admission) serves; 'scan' (ops/assign.py, the literal sequential-assume
#: lax.scan) is the executable spec 'waves' is tested against and the one
#: program that honours a per-pod spec.nodeName. `sched/cycle.py
#: plan_engine` is the one place a wave's engine is chosen.
ENGINES = ("waves", "scan")

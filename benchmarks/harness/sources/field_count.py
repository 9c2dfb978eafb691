"""How many of the window's waves carry the plain number `field` on their
record above zero (`preempt_preemptors`: waves that ran a preemption pass
for some pod). ONE number (reduce it with `first`). A program that records
no such field on any wave gives nothing."""


def read(obs: dict, spec: dict):
    have = [w[spec["field"]] for w in obs["waves"] if spec["field"] in w]
    return sum(1 for v in have if v > 0) if have else None

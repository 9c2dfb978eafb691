"""What the harness itself observed from the client's side, by name: a list
of samples (`bind_latency_ms`, `create_call_ms`, `generator_late_ms`) or one
number (`drain_pods_per_s`, `first_bind_s`, `ingest_s`, `setup_s`,
`achieved_rate_pct`). An observation the cell's traffic does not make is
absent, and the metric is left out."""


def read(obs: dict, spec: dict):
    return obs["series"].get(spec["series"])

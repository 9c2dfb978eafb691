"""A cumulative, process-wide total that a wave's record carries as it stood
when the record was finished (`field`: a dict on the record, `xla_total`;
`key`: the total under it: `programs`, `cache_misses`, `backend_s`,
`trace_lower_s`: what XLA cost the process since its first compile, by the
program's own account). ONE number (reduce it with `first`).

`at: "first"`, the one reading there is, gives the value on the window's
FIRST record that has the field: what the process had paid when its first
measured wave ended, which is set-up, warm-up included (a compile inside the
window is `correct: false` already, by `compilations_in_window`). A program
whose records carry no such field gives nothing."""


def read(obs: dict, spec: dict):
    assert spec["at"] == "first", spec
    for w in obs["waves"]:
        total = w.get(spec["field"]) or {}
        if spec["key"] in total:
            return total[spec["key"]]
    return None

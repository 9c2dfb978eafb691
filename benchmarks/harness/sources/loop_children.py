"""What ran inside a lap of the server loop, from the `children` field of
the `loop` a wave's record carries: `{path: [count, total_s, max_s]}`, a
path's segments being its parents, the first of them the lap (`start/
pods-sync/list/apiserver.list/store.list/kv`: the KV backend's range scan,
inside the store's list, inside the apiserver's, inside the pod informer's
first list, inside the server's start). One sample per wave whose `loop`
has the `path`: the total seconds under it. A server's start is on the
first record of its life alone, so reduce it with `first`.

The window rule is `loop.py`'s: a `loop` that began before the window
opened (no earlier than the last wave's start less `window_s`) is left out.
A program that records no `loop`, or no `children` on it, gives nothing."""


def read(obs: dict, spec: dict):
    waves = [w for w in obs["waves"] if (w.get("loop") or {}).get("children")]
    if not waves:
        return None
    opened_after = obs["waves"][-1]["t_start"] - obs["window_s"]
    return [w["loop"]["children"][spec["path"]][1] for w in waves
            if w["loop"]["t_start"] >= opened_after
            and spec["path"] in w["loop"]["children"]] or None

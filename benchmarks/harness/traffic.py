"""The one general load generator. A traffic mix is a data file
(traffic/<name>.json) of parameters; this module turns it, with the
configuration's own sizes and the seed, into the work of a run.

kinds
  backlog   the configuration's `backlog_pods` are at the apiserver before the
            scheduler starts; nothing is sent inside the window.
  arrivals  open loop: creates of new pods at `rate_share_of_sustained` x the
            configuration's `sustained_rate_per_s`, each due at a fixed
            instant, and the delete of each pod `lifetime_s` after its
            create was due, so the population is steady. Creates stop
            `tail_s` before the window closes, so that every pod has the time
            the slowest sound run needs to bind.
"""

from __future__ import annotations

import threading
import time


def arrival_plan(traffic: dict, cfg: dict, seconds: float,
                 n_groups: int) -> dict:
    """How many creates a window of `seconds` carries and when each is due
    (offsets from the window's start). The count is a multiple of the group
    count so that every seed sends the same number from every group."""
    rate = cfg["sustained_rate_per_s"] * traffic["rate_share_of_sustained"]
    n = int((seconds - traffic["tail_s"]) * rate)
    n -= n % n_groups
    if n <= 0:
        raise SystemExit("traffic: the window is too short for one create "
                         "per group")
    return {"rate": rate, "creates": n,
            "create_due": [i / rate for i in range(n)],
            "lifetime": traffic["lifetime_s"]}


class Generator:
    """Sends a prepared schedule from one thread and records, per operation,
    how late it was sent and how long the call took. It only calls
    create/delete: every object was built in set-up."""

    def __init__(self, client, watch, pods: list, plan: dict, t0: float,
                 window_end: float):
        self.client, self.watch = client, watch
        ops = [(due, 0, i) for i, due in enumerate(plan["create_due"])]
        ops += [(due + plan["lifetime"], 1, i)
                for i, due in enumerate(plan["create_due"])
                if due + plan["lifetime"] < window_end - t0]
        ops.sort()
        self.ops, self.pods, self.t0 = ops, pods, t0
        self.names = [p["metadata"]["name"] for p in pods]
        self.late_ms: list = []
        self.create_call_ms: list = []
        self.sent_creates = 0
        self.deletes = 0
        self.deletes_skipped = 0
        self.errors: list = []
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-generator")

    def start(self) -> None:
        self._thread.start()

    def join(self, timeout: float) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def _run(self) -> None:
        pc = time.perf_counter
        for due, is_delete, i in self.ops:
            wait = self.t0 + due - pc()
            if wait > 0:
                time.sleep(wait)
            sent = pc()
            try:
                if is_delete:
                    # only a pod the client has seen bound leaves: deleting
                    # an unbound one would hide a failure
                    if self.names[i] in self.watch.bound:
                        self.client.pods.delete(self.names[i], "default")
                        self.deletes += 1
                    else:
                        self.deletes_skipped += 1
                    continue
                self.client.pods.create(self.pods[i])
                self.sent_creates += 1
                self.create_call_ms.append((pc() - sent) * 1000.0)
                self.late_ms.append((sent - self.t0 - due) * 1000.0)
            except Exception as e:  # noqa: BLE001 - a refused operation is a
                self.errors.append(repr(e)[:200])   # result, not a crash

"""`local_policy`: the `local` wiring with the configuration's scheduler
Policy handed to the system as an operator hands it over: a
`KubeSchedulerConfiguration` whose `algorithmSource.policy` holds the Policy
inline, given to `SchedulerServer(config=...)`, which builds its own
`Scheduler` from it (queue, framework, the engines' plugin composition,
the preemptor through the API). The capacities and the bind-intent ledger are
`local`'s.

`counters()["zero"]` gains `pods_on_untolerated_taint` (checks/accelerators.py
`counts()` over the apiserver's listing when the run is over;
`nodes_over_extended_resource` and the replay are the check's own), and
`counters()["info"]` `gpu_nodes_opened`, the accelerator nodes that hold a pod
of the backlog, beside `gpu_nodes_opened_reference`, what the plain sequential
reference opens for the same queue (`sources/binpack_nodes.py` reads both).

A tree whose fused score row has no RequestedToCapacityRatio
(`ops/lattice.py EngineConfig` without `w_rtc`: every tree before ISSUE 53)
drops the Policy's argument and scores the priority over cpu and memory
against the cycle-start state: it spreads the accelerator pods one a node
and leaves whole-node pods pending until the window's end. `Cluster` says so
and ends the run at once, before anything is built, so that such a tree
fails this cell cleanly."""

from __future__ import annotations

from ..checks import accelerators
from ..sources import binpack_nodes
from . import local

#: pods the kind creates for the window carry this prefix (kinds/backlog.py)
BACKLOG_PREFIX = "job-"


class Cluster(local.Cluster):
    def __init__(self, cfg: dict):
        from kubernetes_tpu.ops.lattice import EngineConfig

        if "w_rtc" not in EngineConfig._fields:
            raise SystemExit(
                "wiring local_policy: this tree's fused score row has no "
                "RequestedToCapacityRatio (ops/lattice.py EngineConfig "
                "w_rtc): the Policy's shape and per-resource weights would "
                "be dropped and the accelerator pods spread one a node. The "
                "cell needs ISSUE 53's score and fill claim; this tree "
                "cannot run it.")
        super().__init__(cfg)
        # the resource axis holds the pool's extended resource beside the
        # four fixed slots from the first cycle on
        self.dims = self.dims.grown_for(R=4 + 1)

    def scheduler_config(self) -> dict:
        """The KubeSchedulerConfiguration the deployment runs under."""
        return {"apiVersion": "kubescheduler.config.k8s.io/v1alpha1",
                "kind": "KubeSchedulerConfiguration",
                "schedulerName": "default-scheduler",
                "disablePreemption": not self.cfg["preemption"],
                "algorithmSource": {"policy": {
                    "inline": self.cfg["policy"]}}}

    def new_server(self):
        from kubernetes_tpu.sched.ledger import BindIntentLedger
        from kubernetes_tpu.sched.server import SchedulerServer

        server = SchedulerServer(
            self.client, config=self.scheduler_config(),
            base_dims=self.dims, batch_size=self.dims.P,
            cycle_interval=self.cfg["assumed"]["cycle_interval_s"],
            batch_window=self.cfg["assumed"]["batch_window_s"],
            ledger=BindIntentLedger(self.api.storage, identity="bench")
            if self.cfg["bind_intent_ledger"] else None)
        self.servers.append(server)
        return server

    def counters(self, server) -> dict:
        out = super().counters(server)
        if server.pod_informer is None:
            return out   # not started: set-up reads the relists alone
        nodes = self.client.nodes.list()["items"]
        pods = self.client.pods.list("default")["items"]
        found = accelerators.counts(nodes, pods, {"cfg": self.cfg})
        out["zero"]["pods_on_untolerated_taint"] = len(
            found["pods_on_untolerated_taint"])
        out["info"]["pods_on_untolerated_taint_first"] = \
            found["pods_on_untolerated_taint"][:3]
        # the backlog as the measured scheduler's informers listed it (by
        # name: its queue's order inside the one priority), on the cluster
        # as it stood before the window
        backlog = sorted((p for p in pods if p["metadata"]["name"]
                          .startswith(BACKLOG_PREFIX)),
                         key=lambda p: p["metadata"]["name"])
        names = {p["metadata"]["name"] for p in backlog}
        before = [p for p in pods if p["metadata"]["name"] not in names
                  and (p.get("spec") or {}).get("nodeName")]
        queue = [{**p, "spec": {k: v for k, v in p["spec"].items()
                                if k != "nodeName"}} for p in backlog]
        resource = self.cfg["pool"]["resource"]
        want = accelerators.sequential(nodes, before, queue,
                                       self.cfg["policy"])
        got = {p["metadata"]["name"]: p["spec"].get("nodeName")
               for p in backlog}
        opened = accelerators.opened(nodes, got, resource)
        reference = accelerators.opened(nodes, want, resource)
        binpack_nodes.note(opened, reference)
        out["info"].update(
            gpu_nodes_opened=opened, gpu_nodes_opened_reference=reference,
            reference_left_pending=sum(1 for v in want.values()
                                       if v is None))
        return out

"""`local_pv`: the `local` wiring, with the scheduler's own volume state held
to the plain reference. `counters()["zero"]` gains

  node_volume_state_wrong   nodes whose attached-volume count in the
      scheduler's RESIDENT planes (what the next wave would decide on: the
      per-driver counts of volumes one pod names, `vol_cnt`, plus the bits of
      the shared-volume words, `vol_any`) differs from the reference's count
      of distinct attachable volumes on that node over the apiserver's
      listing (checks/volumes.py `attached_counts`)
  nodes_over_volume_limit, volume_conflicts,
  pods_bound_off_their_pv_topology, pods_bound_with_unbound_claims
      the reference's four counts over the same listing, by name

At upstream's numbers the attach limit never refuses a node (2,000 volumes
over 5,000 nodes of 39), so the four alone would pass a scheduler that
ignores volumes; the first would not. A program whose node planes have no
`vol_cnt` (a tree before the volumes were wired) is read by its `vol_any`
alone, and comes out wrong on every node that holds a volume pod."""

from __future__ import annotations

from ..checks import volumes
from . import local


class Cluster(local.Cluster):
    def resident_volume_counts(self, server) -> dict:
        """{node: volumes attached} as the scheduler's resident planes have
        it."""
        import numpy as np

        snap = self._resident_snapshot(server)
        nodes = snap.tables.nodes
        words = np.asarray(nodes.vol_any)
        count = np.unpackbits(words.view(np.uint8), axis=-1).sum(-1)
        if hasattr(nodes, "vol_cnt"):
            count = count + np.asarray(nodes.vol_cnt).sum(-1)
        return {name: int(count[i])
                for i, name in enumerate(snap.node_order) if name}

    def counters(self, server) -> dict:
        out = super().counters(server)
        if server.pod_informer is None:
            return out   # not started: set-up reads the relists alone
        client, ctx = self.client, {"cfg": self.cfg}
        nodes = client.nodes.list()["items"]
        pods = client.pods.list("default")["items"]
        want = volumes.attached_counts(nodes, pods, ctx)
        have = self.resident_volume_counts(server)
        wrong = sorted(n for n in set(want) | set(have)
                       if want.get(n, 0) != have.get(n, 0))
        out["zero"]["node_volume_state_wrong"] = len(wrong)
        out["info"]["node_volume_state_wrong_first"] = [
            (n, want.get(n, 0), have.get(n, 0)) for n in wrong[:3]]
        out["info"]["volumes_attached"] = sum(want.values())
        for name, items in volumes.counts(nodes, pods, ctx).items():
            out["zero"][name] = len(items)
        return out

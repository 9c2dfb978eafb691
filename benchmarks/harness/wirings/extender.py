"""The wiring `extender`: the TPU backend behind a stock kube-scheduler at the
Scheduler Extender boundary. APIServer + native store + Client.local; the
served extender (`kubernetes_tpu/extender/served.py`: node and pod informers
feed its mirror, `bind` assumes and writes through the apiserver, the verbs
compile ahead) over a real HTTP socket on loopback; and the stock scheduler's
stand-in (`kinds/extender_loop.py` `StandIn`), which runs from the server's
`start()`, so that warm-up's throw-away pods bind through the same path.

`Served` is the facade `cell.py` drives as it drives a scheduler server: its
`scheduler` has the extender's mirror as `cache`, the stand-in's queue as
`queue`, the extender's flight recorder (one record a POD, in the wave
record's shape) as `telemetry`, and what `start()` compiled ahead as
`prewarmer.warm_log`.
"""

from __future__ import annotations

from ..kinds.extender_loop import StandIn
from .local import DEFAULT_DIMS


def serving_dims(cfg: dict):
    """The extender's capacities, provisioned from the configuration's own
    numbers so that no verb crosses a bucket and recompiles: N and D for its
    nodes, E through grown_for (the bound-pod axis doubles), P left at Dims'
    floor of 8 rows, the bucket the ONE pod of a verb rides in."""
    from kubernetes_tpu.state.dims import Dims, bucket

    return Dims(N=bucket(cfg["nodes"]), D=bucket(cfg["nodes"]),
                **{**DEFAULT_DIMS, **cfg.get("dims", {})}).grown_for(
                    E=cfg["existing_capacity_pods"])


class _CompiledAhead:
    """`cell.warm_up` waits for a scheduler's background compile-ahead; the
    served extender's is over when its `start()` returns."""

    def __init__(self, served):
        self.served = served

    def wait(self, timeout=None) -> None:
        pass

    @property
    def warm_log(self) -> list:
        return self.served.warm_log


class _View:
    def __init__(self, served, standin):
        self.cache = served.backend.cache
        self.queue = standin
        self.telemetry = served.backend.telemetry
        self.prewarmer = _CompiledAhead(served)


class Served:
    def __init__(self, served, standin):
        self.served, self.standin = served, standin
        self.scheduler = _View(served, standin)
        self._stopped = False

    @property
    def wave_errors(self) -> int:
        return len(self.standin.loop_errors)

    @property
    def last_wave_error(self):
        return self.standin.loop_errors[-1] if self.standin.loop_errors \
            else None

    def start(self) -> "Served":
        self.served.start()
        self.standin.start(self.served.url)
        return self

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self.standin.stop()
            self.served.stop()


class Cluster:
    """One apiserver, one client, and the served extenders (each with its
    stand-in scheduler) run against them."""

    def __init__(self, cfg: dict):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client

        try:   # before anything is built: a tree without it ends at once
            from kubernetes_tpu.extender import ServedExtender
        except ImportError:
            raise SystemExit(
                "benchmark: the wiring `extender` needs a served extender "
                "(kubernetes_tpu.extender.ServedExtender: informer-fed "
                "mirror, bind through the apiserver, compile-ahead); this "
                "tree has none") from None
        self.served_extender = ServedExtender
        self.cfg = cfg
        self.api = APIServer()
        self.client = Client.local(self.api)
        self.kvstore = type(self.api.storage.kv).__name__
        self.dims = serving_dims(cfg)
        self.servers: list = []

    def new_server(self) -> Served:
        policy = self.cfg["extender_policy"]
        served = self.served_extender(
            self.client, base_dims=self.dims,
            verbs={v: policy[v + "Verb"]
                   for v in ("filter", "prioritize", "bind")})
        server = Served(served, StandIn(self.client, policy))
        self.servers.append(server)
        return server

    @staticmethod
    def adopt_warmth(warm, fresh) -> None:
        """A failover lands on a process whose executables are loaded. The
        verbs' programs are the process's own (module-level `jax.jit`s):
        the fresh extender's compile-ahead finds them loaded and runs each
        once. Mirror, encoder and snapshot stay cold."""

    @staticmethod
    def warm_patch_ladder(server) -> int:
        """The served extender's own `start()` ran the ladder
        (`ExtenderBackend.compile_ahead`): nothing is left to compile."""
        return 0

    def counters(self, server) -> dict:
        from kubernetes_tpu.client.informers import INFORMER_RELISTS

        standin, binder = server.standin, server.served.binder
        zero = {"extender_call_errors": len(standin.errors),
                "extender_pods_given_up": len(standin.gave_up),
                "extender_pods_left_no_node": len(standin.unschedulable),
                "standin_loop_errors": len(standin.loop_errors),
                "bind_pushback_failures": int(binder.pushback_failures)}
        info = {"informer_relists": int(INFORMER_RELISTS.total()),
                "standin_turns": standin.turns,
                "bind_pushback_retries": int(binder.pushback_retries),
                "http_requests_served": server.served.http.requests_served,
                "extender_start_s": getattr(server.served, "start_log", None),
                "compiled_ahead": [(d.N, d.P, d.E, name)
                                   for d, name in server.served.warm_log],
                "standin_last_error": standin.errors[-1]
                if standin.errors else None}
        return {"zero": zero, "info": info}

    @staticmethod
    def array_platforms(server) -> list:
        """Where the mirror's resident planes live, as the next verb would
        see them (no pending pod), under the backend's own lock."""
        import jax

        from kubernetes_tpu.sched.cycle import snapshot_with_keys

        backend = server.served.backend
        with backend._mu:
            snap, _keys = snapshot_with_keys(
                backend.cache, backend.encoder, [], backend.base_dims)
        return sorted({d.platform
                       for a in jax.tree.leaves((snap.tables, snap.existing))
                       for d in a.devices()})

    def close(self) -> None:
        for s in self.servers:
            s.stop()
        self.api.close()

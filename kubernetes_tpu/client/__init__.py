"""Client machinery: typed clients, informers, workqueues, leader election.

TPU-native analog of SURVEY.md layer 5 (`staging/src/k8s.io/client-go`).
"""

from kubernetes_tpu.client.events import EventBroadcaster, EventRecorder
from kubernetes_tpu.client.informers import (
    Indexer,
    InformerFactory,
    Lister,
    SharedInformer,
    pods_by_node_index,
)
from kubernetes_tpu.client.leaderelection import (
    LeaderElectionConfig,
    LeaderElector,
)
from kubernetes_tpu.client.rest import (
    Client,
    HTTPTransport,
    LocalTransport,
    ResourceClient,
)
from kubernetes_tpu.client.watchmux import (
    TENANT_LABEL,
    MuxRoute,
    WatchMux,
)
from kubernetes_tpu.client.workqueue import (
    DelayingQueue,
    RateLimiter,
    RateLimitingQueue,
    WorkQueue,
)

__all__ = [
    "Client", "DelayingQueue", "EventBroadcaster", "EventRecorder",
    "HTTPTransport", "Indexer", "InformerFactory", "LeaderElectionConfig",
    "LeaderElector", "Lister", "LocalTransport", "MuxRoute", "RateLimiter",
    "RateLimitingQueue", "ResourceClient", "SharedInformer", "TENANT_LABEL",
    "WatchMux", "WorkQueue", "pods_by_node_index",
]

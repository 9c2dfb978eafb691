"""CLI + cluster bootstrap.

TPU-native analog of SURVEY.md layer 10 (`staging/src/k8s.io/kubectl`,
`cmd/kubeadm`).

The names below resolve on first use: `Cluster` and `Kubectl` import the
scheduler, and with it `jax`, which `python -m kubernetes_tpu.cli apiserver`
(cli/apiserver.py: the apiserver alone, a process that must never open the
chip) may not pay for.
"""

__all__ = ["Cluster", "ClusterConfig", "Kubectl", "main"]

_HOME = {"Cluster": "cluster", "ClusterConfig": "cluster",
         "Kubectl": "kubectl", "main": "kubectl"}


def __getattr__(name: str):
    if name not in _HOME:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{_HOME[name]}"), name)

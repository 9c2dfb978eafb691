"""Shared component infrastructure: metrics, feature gates, tracing, version.

TPU-native analog of SURVEY.md layer 11
(`staging/src/k8s.io/component-base`).
"""

from kubernetes_tpu.component.featuregate import (
    ALPHA,
    BETA,
    DEFAULT_FEATURE_GATES,
    FeatureGate,
    FeatureSpec,
    GA,
)
from kubernetes_tpu.component.metrics import (
    Counter,
    DEFAULT_REGISTRY,
    Gauge,
    Histogram,
    Registry,
)
from kubernetes_tpu.component.trace import Trace

VERSION = {"gitVersion": "v1.17.0-tpu.1", "major": "1", "minor": "17+",
           "platform": "jax/xla-tpu"}

__all__ = ["ALPHA", "BETA", "Counter", "DEFAULT_FEATURE_GATES",
           "DEFAULT_REGISTRY", "FeatureGate", "FeatureSpec", "GA", "Gauge",
           "Histogram", "Registry", "Trace", "VERSION"]

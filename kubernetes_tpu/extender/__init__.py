"""Scheduler-extender boundary: the TPU lattice as an out-of-process extender
(server) and extender webhooks callable from our own scheduler (client).
Reference: pkg/scheduler/core/extender.go + apis/extender/v1/types.go."""

from .backend import ExtenderBackend
from .client import ExtenderConfig, ExtenderError, HTTPExtender
from .server import ExtenderServer
from .served import ServedExtender
from .wire import (
    ExtenderArgs,
    ExtenderBindingArgs,
    ExtenderBindingResult,
    ExtenderFilterResult,
    ExtenderPreemptionArgs,
    ExtenderPreemptionResult,
    HostPriority,
    HostPriorityList,
    MAX_EXTENDER_PRIORITY,
    MetaVictims,
    Victims,
)

__all__ = [
    "ExtenderBackend", "ExtenderConfig", "ExtenderError", "HTTPExtender",
    "ExtenderServer", "ServedExtender", "ExtenderArgs", "ExtenderBindingArgs",
    "ExtenderBindingResult", "ExtenderFilterResult", "ExtenderPreemptionArgs",
    "ExtenderPreemptionResult", "HostPriority", "HostPriorityList",
    "MAX_EXTENDER_PRIORITY",
    "MetaVictims", "Victims",
]

"""Test configuration: force the CPU backend with 8 virtual devices.

Mirrors the reference's test ladder (SURVEY.md §4): unit kernels and golden
semantics tests run on the XLA CPU backend ("XLA-on-CPU interpreter" rungs);
multi-chip sharding tests use the 8 virtual devices. Set KTPU_TEST_TPU=1 to run
the suite against the real chip instead.
"""

import gc
import os

import pytest

_FORCE_CPU = os.environ.get("KTPU_TEST_TPU") != "1"

if _FORCE_CPU:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    from kubernetes_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()


def _map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """XLA:CPU maps every compiled kernel's code separately (a small program
    takes ~40 mappings) and the suite compiles thousands of programs in one
    process: half way through it reaches vm.max_map_count (65,530) and dies
    inside the compiler. Once a module leaves the process past half of that,
    drop jax's executable caches — that unmaps them; what a later module
    needs again it recompiles or loads from the persistent cache."""
    yield
    if _map_count() > 30_000:
        import jax

        jax.clear_caches()


@pytest.fixture(autouse=True)
def _thaw_what_a_start_froze():
    """A server's start moves everything its process holds out of the cyclic
    collector's walk (utils/platform.py `listing_heap`), and a server is
    garbage only by a cycle (its handlers point back at it): frozen, a
    test's stopped server and the cluster it listed would stay for the
    worker's life, hundreds of them a worker. Hand them back after each
    test; a process that serves starts once and keeps what it froze."""
    yield
    gc.unfreeze()

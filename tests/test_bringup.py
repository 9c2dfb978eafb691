"""Bring-up contracts (ISSUE 21): where the compile cache goes, that the
native store is built from source, that bench.py reports a failed stage in
its exit code, and that chip_smoke.py's legs run (here at a tiny shape on
the CPU) while the script itself refuses to start without a TPU."""

import ctypes
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, drop=(), timeout=300):
    e = {k: v for k, v in os.environ.items() if k not in drop}
    e.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=timeout)


class TestCompileCacheDir:
    CODE = ("from kubernetes_tpu.utils.platform import enable_compile_cache;"
            "import jax; d = enable_compile_cache();"
            "assert d == jax.config.jax_compilation_cache_dir; print(d)")

    def test_environment_places_the_cache(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: no directory is set in code."""
        r = _run(["-c", self.CODE],
                 env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == str(tmp_path)

    def test_default_is_the_fixed_checkout_path(self):
        r = _run(["-c", self.CODE], drop=("JAX_COMPILATION_CACHE_DIR",))
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == os.path.join(REPO, ".cache", "xla")


def test_native_store_builds_from_a_fresh_tree(tmp_path, monkeypatch):
    """No committed libkvstore.so: the first use builds it from
    native/kvstore.cpp with native/Makefile, and it loads."""
    from kubernetes_tpu.storage import native

    tracked = subprocess.run(["git", "ls-files", "native"], cwd=REPO,
                             capture_output=True, text=True).stdout.split()
    assert "native/libkvstore.so" not in tracked
    fresh = tmp_path / "native"
    fresh.mkdir()
    for name in ("Makefile", "kvstore.cpp"):
        shutil.copy(os.path.join(REPO, "native", name), fresh / name)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(fresh))
    so = native._build_lib()
    assert so == str(fresh / "libkvstore.so") and os.path.exists(so), \
        native._build_error
    assert hasattr(ctypes.CDLL(so), "kv_txn_put")
    assert sorted(os.listdir(fresh)) == ["Makefile", "kvstore.cpp",
                                         "libkvstore.so"]


class TestBenchExitCode:
    def _bench(self, stages, tmp_path):
        r = _run(["bench.py"], env={
            "BENCH_STAGES": stages, "BENCH_FORCE_CPU": "1",
            "BENCH_OUT": str(tmp_path / "bench.json"),
            "BENCH_STAGE_TIMEOUT": "240"})
        line = json.loads(r.stdout.strip().splitlines()[-1])
        return r.returncode, line["detail"]["stages"]

    def test_failed_stage_fails_the_run(self, tmp_path):
        rc, stages = self._bench("8x16,8x16xnosuchkind", tmp_path)
        assert "cycle_s" in stages["8x16 flagship"]
        assert stages["8x16 nosuchkind"]["rc"] not in (0, "skip")
        assert rc == 1

    def test_exit_code_rule(self):
        import bench

        ok = {"ok": True, "pods_per_sec": 1.0}
        assert bench._exit_code([ok]) == 0
        assert bench._exit_code([ok, {"ok": False, "skipped": "budget"}]) == 0
        assert bench._exit_code([ok, {"ok": False, "error": "boom"}]) == 1
        assert bench._exit_code([{"ok": False, "rc": -9}]) == 1

    def test_parent_never_imports_jax(self):
        """One process per chip: the launcher must stay off JAX (and off
        anything that imports it) so the stage children can have the chip."""
        r = _run(["-c", "import sys, bench; "
                        "assert 'jax' not in sys.modules, 'bench imports jax'"])
        assert r.returncode == 0, r.stderr


class TestChipSmoke:
    def test_script_refuses_to_run_without_a_tpu(self):
        r = _run(["chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        assert r.stdout == "" and "needs a TPU" in r.stderr

    def test_oracle_leg(self):
        import chip_smoke

        r = chip_smoke.oracle_leg(seed=3, n_nodes=8, n_pods=24)
        assert r["ok"], r["mismatches"]
        assert r["filter_pairs_checked"] == 8 * 24

    def test_serving_leg(self):
        import chip_smoke

        r = chip_smoke.serving_leg(24, 150, 20, timeout=240, quiet=0.5)
        assert r["ok"], r["failures"]
        assert r["kvstore_backend"] == "NativeKV"
        assert r["cycles"] >= 3 and r["patch_cycles"] >= 2
        assert r["waves"][0]["snapshot_mode"] == "full"
        assert r["preempt_bound"] == 4 and r["preempt_victims_evicted"] >= 1
        assert r["intents_unretired"] == 0 and r["double_bound"] == 0
        assert not any(r["supervisor"][k]
                       for k in chip_smoke.SUPERVISOR_ZERO)
        # the burst ran the prewarmer's AOT executable, and none was dropped
        assert r["prewarm"]["type_error_drops"] == 0

    def test_extender_leg(self):
        import chip_smoke

        r = chip_smoke.extender_leg(40)
        assert r["ok"], r["failures"]
        assert r["http_requests_served"] == 8

    def test_invariant_checker_sees_violations(self):
        """The full-size check is only worth its exit code if it fails on a
        bad cluster: overcommit a node, break anti-affinity and skew."""
        import dataclasses

        import chip_smoke
        from kubernetes_tpu.api.v1 import node_to_v1, pod_to_v1
        from kubernetes_tpu.models.workloads import flagship_pods, make_nodes

        nodes = make_nodes(4, zones=2, cpu="1", pods=3)
        pods = [dataclasses.replace(p, node_name="node-0")
                for p in flagship_pods(8, groups=2)]
        bad = chip_smoke.check_invariants(
            [node_to_v1(n) for n in nodes], [pod_to_v1(p) for p in pods],
            check_spread=True)
        assert any("pods 8 > allocatable 3" in b for b in bad)
        assert any(b.startswith("anti-affinity") for b in bad)
        assert any(b.startswith("spread") for b in bad)
        ok = chip_smoke.check_invariants(
            [node_to_v1(n) for n in nodes], [pod_to_v1(pods[0])],
            check_spread=True)
        assert ok == []

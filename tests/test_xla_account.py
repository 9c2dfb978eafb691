"""The XLA account (ISSUE 51; sched/telemetry.py `XlaAccount`,
docs/OBSERVABILITY.md "Compilations on the record"): which program this
process compiled or loaded, for whom, and why.

The process has ONE account (its listener pair went in with conftest's
`enable_compile_cache()`), so every case reads it from a mark of its own
and compiles functions of its own.
"""

import json
import logging
import threading
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu.api.types import Pod, Resources
from kubernetes_tpu.models.workloads import make_nodes
from kubernetes_tpu.sched.metrics import XLA_PROGRAMS, XLA_SECONDS
from kubernetes_tpu.sched.prewarm import BucketPrewarmer
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.sched.supervisor import DispatchSupervisor
from kubernetes_tpu.sched.telemetry import (
    SchedulerTelemetry, XlaAccount, _differs, _sig_fields, describe_compile,
    xla_account, xla_scope)
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.utils.platform import enable_compile_cache

ACCT = xla_account()


def _fresh(mark):
    """The entries that ended since `mark`."""
    return ACCT.since(mark)[0].get("xla_compiled", [])


def _program(salt: float):
    """A jitted function no other case has compiled."""
    @jax.jit
    def salted(x):
        return (x * salt).sum()
    return salted


@pytest.fixture
def own_cache(tmp_path):
    """A persistent cache of the case's own that stores every compile,
    however short; both settings, and the cache jax had open, come back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
        cc.reset_cache()


# --------------------------------------------------------------------------- #
# the hook: one listener pair, one entry a program
# --------------------------------------------------------------------------- #


def test_one_listener_pair_a_process_however_many_ask():
    from jax._src import monitoring

    for _ in range(3):
        assert xla_account() is ACCT
        enable_compile_cache()
        SchedulerTelemetry(name="another")
    durations = [cb for cb in monitoring.get_event_duration_listeners()
                 if getattr(cb, "__self__", None) is ACCT]
    events = [cb for cb in monitoring.get_event_listeners()
              if getattr(cb, "__self__", None) is ACCT]
    assert len(durations) == 1 and len(events) == 1


def test_a_programs_events_fold_into_one_entry_miss_then_hit(own_cache):
    f = _program(3.25)
    mark, before = ACCT.mark(), ACCT.totals()
    with xla_scope("case", ("k", 5), ("name", "n")):
        f(jnp.ones(5))
    mine = [e for e in _fresh(mark) if e["fun"] == "salted"]
    assert len(mine) == 1
    e = mine[0]
    assert (e["stage"], e["sig"]) == ("case", {"name": "k", "n": 5})
    assert e["cache"] == "miss" and e["differs"] is None
    assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
    assert e["saved_s"] is None and e["on_path"] is False
    assert e["thread"] == threading.current_thread().name
    assert e["t_start"] < e["t_end"] and e["seq"] is None
    after = ACCT.totals()
    assert after["cache_misses"] >= before["cache_misses"] + 1
    assert after["programs"] >= before["programs"] + 1
    assert after["backend_s"] > before["backend_s"]
    assert after["trace_lower_s"] > before["trace_lower_s"]

    jax.clear_caches()   # the executables in memory go: the next call loads
    mark = ACCT.mark()
    with xla_scope("case", ("k", 5), ("name", "n")):
        f(jnp.ones(5))
    again = [e for e in _fresh(mark) if e["fun"] == "salted"]
    assert len(again) == 1
    assert again[0]["cache"] == "hit" and again[0]["saved_s"] is not None
    assert again[0]["differs"] == {}   # the signature of the one before
    assert ACCT.totals()["cache_misses"] == after["cache_misses"] \
        + sum(e["cache"] == "miss" for e in _fresh(mark))


def test_a_compile_under_the_minimum_time_is_asked_for_and_never_stored(
        own_cache):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 100.0)
    f = _program(4.5)
    for _round in range(2):   # not stored the first time, so not found
        mark = ACCT.mark()
        f(jnp.ones(6))
        mine = [e for e in _fresh(mark) if e["fun"] == "salted"]
        assert [e["cache"] for e in mine] == ["unstored"]
        assert mine[0]["stage"] is None   # a site nobody wrapped
        jax.clear_caches()


def test_two_dims_that_differ_in_one_field_say_which():
    f = _program(5.75)
    key = lambda d: (d, "waves", (), False, None)
    names = ("dims", "engine", "extras", "gang", "mesh")
    mark = ACCT.mark()
    with xla_scope("cycle-case", key(Dims(S=64)), names):
        f(jnp.ones(7))
    with xla_scope("cycle-case", key(Dims(S=72)), names):
        f(jnp.ones(8))
    first, second = [e for e in _fresh(mark) if e["fun"] == "salted"]
    assert first["differs"] is None
    assert second["differs"] == {"S": [64, 72]}
    assert second["sig"]["S"] == 72 and second["sig"]["N"] == Dims().N
    assert second["sig"]["engine"] == "waves" and second["sig"]["mesh"] is None
    assert "S 64 -> 72" in describe_compile(second)


@pytest.mark.parametrize("seen, sig, want", [
    ([], {"N": 8}, None),
    ([{"N": 8, "E": 8}], {"N": 8, "E": 8}, {}),
    # the nearest of the earlier ones, not the newest
    ([{"N": 8, "E": 16}, {"N": 64, "E": 64}], {"N": 8, "E": 32},
     {"E": [16, 32]}),
    # a field only one side has
    ([{"N": 8}], {"N": 8, "fleet": 4}, {"fleet": [None, 4]})])
def test_differs_is_against_the_nearest_earlier_signature(seen, sig, want):
    assert _differs(seen, sig) == want


@pytest.mark.parametrize("sig, names, want", [
    (None, (), None),
    ((Dims(N=16), "scan"), ("dims", "engine"), dict(
        {f: getattr(Dims(N=16), f) for f in Dims.__dataclass_fields__},
        engine="scan")),
    (("a", 3), ("just-one",), {"k0": "a", "k1": 3}),   # names that do not fit
    ("shape", (), {"k0": "shape"}),
    ({"N": 8, "mesh": ((2, 2), (0, 1, 2, 3))}, (),
     {"N": 8, "mesh": [[2, 2], [0, 1, 2, 3]]})])
def test_a_scopes_key_by_field(sig, names, want):
    got = _sig_fields(sig, names)
    assert got == want
    json.dumps(got)   # what a record and the endpoint can carry


def test_a_narrower_scope_keeps_what_it_leaves_open():
    acct, said = XlaAccount(), []
    with acct.scope("verb", on_path=True, seq=7, sink=said.append):
        with acct.scope(None, (Dims(),), ("dims",)) as inner:
            assert (inner.stage, inner.on_path, inner.seq) == ("verb", True, 7)
            assert acct._local.scope is inner
        assert acct._local.scope.sig is None
    assert acct._local.scope is None


# --------------------------------------------------------------------------- #
# who waited: the supervisor's scope against the prewarmer's
# --------------------------------------------------------------------------- #


def test_an_inline_compile_under_the_supervisor_is_on_path_and_cold(caplog):
    f, events = _program(6.5), []
    sup = DispatchSupervisor()
    sup.event_sink = lambda kind, detail: events.append((kind, detail))
    sup.wave_seq = lambda: 17
    key = (Dims(), "waves", (), False, None)
    mark = ACCT.mark()
    with caplog.at_level(logging.INFO, logger="kubernetes_tpu.sched.telemetry"):
        sup.run("cycle", key, lambda: float(f(jnp.ones(9))))
    e, = [e for e in _fresh(mark) if e["fun"] == "salted"]
    assert (e["stage"], e["on_path"], e["cold"], e["seq"]) == \
        ("cycle", True, True, 17)
    assert e["thread"] == "ktpu-dispatch-cycle"
    assert e["sig"]["engine"] == "waves" and e["sig"]["N"] == Dims().N
    # narrated to the record in flight, and on the log
    said = [d for kind, d in events if kind == "compile" and "`salted`" in d]
    assert len(said) == 1 and said[0].startswith("wave 17 waited ")
    assert f"cache {e['cache']}" in said[0] and "cold" in said[0]
    assert any("`salted`" in r.getMessage() for r in caplog.records)
    # the key has a budget now: the next call at it is not cold
    g = _program(6.75)
    mark = ACCT.mark()
    sup.run("cycle", key, lambda: float(g(jnp.ones(9))))
    e, = [e for e in _fresh(mark) if e["fun"] == "salted"]
    assert e["cold"] is False and e["differs"] == {}


def test_a_long_on_path_compile_is_a_warning_with_what_set_it_apart(
        caplog, monkeypatch):
    from kubernetes_tpu.sched import telemetry

    monkeypatch.setattr(telemetry, "XLA_WARN_S", 0.0)
    f = _program(7.5)
    with caplog.at_level(logging.INFO, logger="kubernetes_tpu.sched.telemetry"):
        with xla_scope("loud-case", (Dims(S=64),), ("dims",), on_path=True,
                       seq=3):
            f(jnp.ones(10))
        with xla_scope("loud-case", (Dims(S=72),), ("dims",), on_path=True,
                       seq=4):
            f(jnp.ones(11))
        with xla_scope("loud-case", (Dims(S=80),), ("dims",), on_path=False):
            f(jnp.ones(12))
    mine = [r for r in caplog.records if "`salted`" in r.getMessage()]
    assert [r.levelno for r in mine] == [logging.WARNING, logging.WARNING,
                                         logging.INFO]
    line = mine[1].getMessage()
    assert line.startswith("wave 4 waited ") and "S 64 -> 72" in line
    assert "cache " in line and "backend " in line
    assert " spent " in mine[2].getMessage()


def test_a_background_compile_under_the_prewarmer_is_off_the_path():
    d = Dims()
    mark = ACCT.mark()
    pw = BucketPrewarmer()
    pw._compile(d, "waves", (), False)
    pw._compile_preempt(d, 8)
    assert [e for _d, e in pw.warm_log] == ["waves", "preempt"]
    mine = {e["fun"]: e for e in _fresh(mark) if e["stage"] == "prewarm"}
    assert {"_schedule_batch_impl", "_preempt"} <= set(mine)
    cycle, burst = mine["_schedule_batch_impl"], mine["_preempt"]
    assert cycle["on_path"] is False and cycle["cold"] is None
    assert cycle["sig"]["engine"] == "waves" and cycle["sig"]["P"] == d.P
    assert cycle["sig"]["fleet"] is None and cycle["sig"]["gang"] is False
    assert burst["sig"]["program"] == "preempt" and burst["sig"]["burst"] == 8
    assert burst["sig"]["P"] == 1   # the burst's key leaves P out
    assert all(e["stage"] is not None for e in _fresh(mark))


# --------------------------------------------------------------------------- #
# where it lands: the record, /metrics, /debug/compiles
# --------------------------------------------------------------------------- #


def _pod(i):
    return Pod(name=f"p{i}", creation_index=i,
               requests=Resources.make(cpu="100m", memory="8Mi"))


def _scheduler(**kw):
    clk = {"t": 0.0}
    s = Scheduler(binder=RecordingBinder(), batch_size=64,
                  clock=lambda: clk["t"], **kw)
    for n in make_nodes(8):
        s.on_node_add(n)
    return s


def test_a_wave_that_compiled_says_so_and_the_next_steady_one_does_not():
    jax.clear_caches()   # whatever ran before: this wave compiles its cycle
    s = _scheduler()
    for i in range(3):
        s.on_pod_add(_pod(i))
    s.schedule_pending()
    first = s.telemetry.recorder.records()[-1]
    made = first["xla_compiled"]
    cycle, = [e for e in made if e["fun"] == "_schedule_batch_impl"]
    assert cycle["stage"] == "cycle" and cycle["on_path"] and cycle["cold"]
    assert cycle["seq"] == first["seq"]
    assert all(e["stage"] is not None and e["on_path"] for e in made)
    assert first["xla_total"]["programs"] >= len(made)
    assert ("compile", describe_compile(cycle)[:200]) in \
        first["supervisor_events"]
    # the second wave's snapshot is a patch: its first rung compiles on the
    # loop's thread, under the wave's scope; the third compiles nothing
    waves = [first]
    for n in (1, 2):
        for i in range(3 * n, 3 * n + 3):
            s.on_pod_add(_pod(i))
        s.schedule_pending()
        waves.append(s.telemetry.recorder.records()[-1])
    second, steady = waves[1:]
    assert {e["fun"] for e in second.get("xla_compiled", ())} <= \
        {"_patch_rows", "convert_element_type"}
    assert all(e["stage"] in ("wave", "snapshot")
               for e in second.get("xla_compiled", ()))
    assert steady["seq"] == first["seq"] + 2
    assert "xla_compiled" not in steady
    assert steady["xla_total"] == second["xla_total"]
    assert second["xla_total"]["programs"] == first["xla_total"]["programs"] \
        + len(second.get("xla_compiled", ()))
    json.dumps(waves)


def test_a_recorder_made_later_still_carries_what_the_process_paid():
    before = ACCT.totals()
    assert before["programs"] > 0   # this process has compiled by now
    tel = SchedulerTelemetry(name="made-late")
    span = tel.wave_span()
    span.mark("pump")
    rec = tel.finish_wave(span)
    assert rec["xla_total"]["programs"] >= before["programs"]
    assert "xla_compiled" not in rec


def test_telemetry_off_leaves_both_fields_off():
    s = _scheduler()
    s.telemetry.enabled = False
    s.on_pod_add(_pod(0))
    s.schedule_pending()
    assert s.telemetry.recorder.records() == []
    tel = SchedulerTelemetry(name="off", enabled=False)
    assert tel.finish_wave(tel.wave_span()) is None and tel._xla is None


def test_the_endpoint_and_the_two_series_answer():
    from kubernetes_tpu.sched.server import TelemetryGateway

    f = _program(8.25)
    progs = XLA_PROGRAMS.total()
    secs = XLA_SECONDS.value(stage="endpoint-case", part="backend")
    with xla_scope("endpoint-case", ("x",), ("name",)):
        f(jnp.ones(13))
    gw = TelemetryGateway(SchedulerTelemetry(name="gw"))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw.port}"
        doc = json.load(urllib.request.urlopen(base + "/debug/compiles"))
        text = urllib.request.urlopen(base + "/metrics").read().decode()
    finally:
        gw.stop()
    mine = [e for e in doc["entries"] if e["stage"] == "endpoint-case"]
    assert mine and mine[-1]["fun"] == "salted"
    assert doc["totals"] == ACCT.totals()
    assert XLA_PROGRAMS.total() >= progs + 1
    assert XLA_SECONDS.value(stage="endpoint-case", part="backend") > secs
    cache = mine[-1]["cache"]
    assert f'scheduler_xla_programs_total{{stage="endpoint-case",' \
        f'cache="{cache}"}}' in text
    for part in ("trace", "lower", "backend"):
        assert 'scheduler_xla_compile_seconds_total{stage="endpoint-case",' \
            f'part="{part}"}}' in text


def test_the_account_keeps_the_newest_entries_and_counts_them_all():
    acct = XlaAccount()
    for i in range(XlaAccount.KEEP + 5):
        acct.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25,
                         fun_name=f"f{i}")
        acct.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                         0.5, fun_name=f"jit(f{i})")
        acct.on_event("/jax/compilation_cache/compile_requests_use_cache")
        acct.on_event("/jax/compilation_cache/cache_hits")
        acct.on_duration("/jax/compilation_cache/compile_time_saved_sec", 30.)
        acct.on_duration("/jax/core/compile/backend_compile_duration", 1.0,
                         fun_name=f"jit(f{i})")
    kept = acct.entries()
    assert len(kept) == XlaAccount.KEEP and kept[-1]["fun"] == \
        f"f{XlaAccount.KEEP + 4}"
    assert kept[-1]["cache"] == "hit" and kept[-1]["saved_s"] == 30.0
    assert (kept[-1]["trace_s"], kept[-1]["lower_s"], kept[-1]["backend_s"]) \
        == (0.25, 0.5, 1.0)
    assert acct.totals() == {
        "programs": XlaAccount.KEEP + 5, "cache_misses": 0,
        "backend_s": XlaAccount.KEEP + 5.0,
        "trace_lower_s": 0.75 * (XlaAccount.KEEP + 5)}
    fields, mark = acct.since(acct.mark() - 2)
    assert [e["fun"] for e in fields["xla_compiled"]] == \
        [f"f{XlaAccount.KEEP + 3}", f"f{XlaAccount.KEEP + 4}"]
    assert mark == acct.mark() and "xla_compiled" not in acct.since(mark)[0]
    # a mark older than what is kept gives what is kept
    assert len(acct.since(0)[0]["xla_compiled"]) == XlaAccount.KEEP


def test_a_program_traced_inside_another_is_the_outer_ones_seconds():
    acct = XlaAccount()
    T, L, B = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    for inner in ("multiply", "_reduce_sum"):   # jits inside `outer`
        acct.on_duration(T, 0.01, fun_name=inner)
    acct.on_duration(T, 2.0, fun_name="outer")
    acct.on_duration(T, 0.01, fun_name="less")  # the lowering's own helper
    acct.on_duration(L, 0.5, fun_name="jit(outer)")
    acct.on_duration(B, 1.0, fun_name="jit(outer)")
    # a backend event with no lowering on this thread before it
    acct.on_duration(B, 3.0, fun_name="jit(elsewhere)")
    one, bare = acct.entries()
    assert (one["fun"], one["trace_s"], one["lower_s"], one["backend_s"],
            one["cache"]) == ("outer", 2.0, 0.5, 1.0, "off")
    assert (bare["fun"], bare["trace_s"], bare["lower_s"]) == \
        ("elsewhere", 0.0, 0.0)
    assert acct.totals()["trace_lower_s"] == 2.5


def test_a_listener_that_fails_does_not_fail_the_compile(monkeypatch):
    acct = XlaAccount()
    monkeypatch.setattr(acct, "_finish", lambda *a: 1 / 0)
    acct.on_duration("/jax/core/compile/backend_compile_duration", 1.0,
                     fun_name="jit(f)")   # does not raise
    assert acct.entries() == []


# --------------------------------------------------------------------------- #
# the benchmark's reader, and the account against the benchmark's own listener
# --------------------------------------------------------------------------- #


def _obs(*records):
    return {"window_s": 40.0, "waves": list(records)}


@pytest.mark.parametrize("records, at, want", [
    # a parent's records: no such field, nothing (not a crash, not a zero)
    ([{"t_start": 1.0}, {"t_start": 2.0}], "first", None),
    ([], "first", None),
    ([{"xla_total": {"programs": 18, "backend_s": 1.5}},
      {"xla_total": {"programs": 18, "backend_s": 1.5}}], "first", 1.5),
    # the first record that HAS the field, where an earlier one has not
    ([{"t_start": 1.0}, {"xla_total": {"backend_s": 2.5}}], "first", 2.5),
    # a later record's larger total (a compile in the window) is not read
    ([{"xla_total": {"backend_s": 2.5}}, {"xla_total": {"backend_s": 4.0}}],
     "first", 2.5),
    # the field without the key asked for
    ([{"xla_total": {"programs": 3}}], "first", None)])
def test_record_total_reads_the_first_records_value_or_nothing(
        records, at, want):
    from benchmarks.harness.sources import record_total

    spec = {"kind": "record_total", "field": "xla_total", "key": "backend_s",
            "at": at}
    assert record_total.read(_obs(*records), spec) == want


def test_the_four_setup_metrics_through_their_files():
    from benchmarks.harness import cell

    bench = cell.load_json(cell.ROOT, "BENCHMARK.json")
    total = {"programs": 18, "cache_misses": 2, "backend_s": 12.5,
             "trace_lower_s": 7.25}
    rec = {"t_start": 104.0, "duration_s": 1.0, "phases": [],
           "stats": {"attempted": 10}, "xla_total": total}
    obs = {"window_s": 40.0, "bound_in_window": 10, "series": {},
           "memory": {}, "trace": None, "rehearse": True, "waves": [rec]}
    want = {"setup_xla_backend_s": 12.5, "setup_xla_trace_lower_s": 7.25,
            "setup_xla_cache_misses": 2.0, "setup_xla_programs": 18.0}
    for w in bench["workloads"]:   # all nine cells report the four
        out = cell.compute_metrics(bench, "per_layer", w["name"], obs)
        assert {n: out[n]["value"] for n in want} == want
        old = cell.compute_metrics(bench, "per_layer", w["name"], {
            **obs, "waves": [{k: v for k, v in rec.items()
                              if k != "xla_total"}]})
        assert not set(want) & set(old)
    mine = [m for m in bench["per_layer"] if m["name"] in want]
    at = bench["per_layer"].index(mine[0])   # appended as one block
    assert len(mine) == 4 and bench["per_layer"][at:at + 4] == mine
    assert all(m["moves"] == "setup_s" and m["better"] == "lower"
               and m["layer"] == "dispatch / engines" for m in mine)


_COLD_START = """
import json, sys
sys.path.insert(0, {root!r})
from benchmarks.harness import cell
from benchmarks.harness.probes import CompileCounter
from kubernetes_tpu.sched.telemetry import xla_account

counter = CompileCounter()   # an independent listener, armed from the start
counter.arm()
code, result = cell.run_cell({workload!r}, 2 ** 31 + 51, 12.0, True,
                             rehearse=True)
counter.disarm()
acct = xla_account()
print("XLA " + json.dumps({{
    "code": code, "correct": result["correct"], "events": counter.events,
    "metrics": {{n: v["value"] for n, v in result["metrics"].items()}},
    "totals": acct.totals(), "count": acct.count,
    "entries": acct.entries()}}))
"""


@pytest.mark.parametrize("workload, stages", [
    ("flagship-5k.backlog", {"cycle", "prewarm", "patch-ladder", "snapshot"}),
    ("extender-5k.filter-prioritize", {"compile-ahead", "patch-ladder"})])
def test_a_cold_start_at_the_rehearsal_size_leaves_no_site_unwrapped(
        workload, stages):
    """A process of its own runs a cell's traced rehearsal: warm-up's wave,
    the prewarmer's compile-ahead (cycle and preempt), the patch ladder, the
    measured server's waves, the harness's own snapshots, the extender's
    compile-ahead and verbs. Every compile is inside a scope, and the
    account counts what the benchmark's own listener counts."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.run(
        [sys.executable, "-c", _COLD_START.format(root=root,
                                                  workload=workload)],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    said = [ln for ln in child.stdout.splitlines() if ln.startswith("XLA ")]
    assert child.returncode == 0 and said, child.stderr[-2000:]
    got = json.loads(said[-1][4:])
    assert got["code"] == 0 and got["correct"] is True
    made, totals, events = got["entries"], got["totals"], got["events"]
    # an independent listener saw the same backend events
    assert totals["programs"] == len(events) == got["count"] > 0
    assert sum(s for _f, s in events) == pytest.approx(
        totals["backend_s"], abs=1e-3 * len(events))
    assert len(made) == min(len(events), XlaAccount.KEEP)
    unwrapped = [(e["fun"], e["thread"]) for e in made if e["stage"] is None]
    assert unwrapped == []
    assert stages <= {e["stage"] for e in made}
    # warm-up's inline compile is on the path, cold, and said so on stderr
    # (the extender's verbs dispatch inline, under no supervisor)
    inline = [e for e in made if e["on_path"] and e["cold"]]
    assert all(e["stage"] in ("cycle", "preempt", "scores") for e in inline)
    assert bool(inline) == ("extender" not in workload)
    for e in inline:
        if e["trace_s"] + e["lower_s"] + e["backend_s"] > 1.0:
            assert f"for `{e['fun']}` (stage {e['stage']}, cold)" \
                in child.stderr
    # and the cell's traced line reads the four from the first record
    four = {n: v for n, v in got["metrics"].items()
            if n.startswith("setup_xla_")}
    assert set(four) == {"setup_xla_backend_s", "setup_xla_trace_lower_s",
                         "setup_xla_cache_misses", "setup_xla_programs"}
    assert 0 < four["setup_xla_programs"] <= totals["programs"]
    assert 0 < four["setup_xla_backend_s"] <= totals["backend_s"]

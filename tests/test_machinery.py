"""apimachinery analog: selectors, quantities, meta, scheme, watch, errors.

Table-driven in the style of apimachinery's pkg/labels/selector_test.go and
pkg/api/resource/quantity_test.go.
"""

import pytest

from kubernetes_tpu.machinery import errors, labels, meta, quantity, scheme, watch


class TestSelectors:
    @pytest.mark.parametrize("expr,lbls,want", [
        ("", {"a": "b"}, True),
        ("a=b", {"a": "b"}, True),
        ("a=b", {"a": "c"}, False),
        ("a==b", {"a": "b"}, True),
        ("a!=b", {"a": "c"}, True),
        ("a!=b", {"a": "b"}, False),
        ("a!=b", {}, True),  # NotEquals matches absent key
        ("a in (b,c)", {"a": "c"}, True),
        ("a in (b,c)", {"a": "d"}, False),
        ("a notin (b,c)", {"a": "d"}, True),
        ("a notin (b,c)", {}, True),
        ("a", {"a": "anything"}, True),
        ("a", {}, False),
        ("!a", {}, True),
        ("!a", {"a": ""}, False),
        ("a>5", {"a": "6"}, True),
        ("a>5", {"a": "5"}, False),
        ("a<5", {"a": "4"}, True),
        ("a=b,c=d", {"a": "b", "c": "d"}, True),
        ("a=b,c=d", {"a": "b"}, False),
        ("x in (a,b), y notin (c)", {"x": "a", "y": "z"}, True),
        ("app.kubernetes.io/name=web", {"app.kubernetes.io/name": "web"}, True),
    ])
    def test_parse_and_match(self, expr, lbls, want):
        assert labels.parse(expr).matches(lbls) is want

    @pytest.mark.parametrize("bad", [
        "a==", "=b", "a in", "a in (", "a in b", ",", "a=b,", "a@b=c",
        "in (a)", "a in ()",
    ])
    def test_parse_errors(self, bad):
        with pytest.raises(labels.SelectorParseError):
            labels.parse(bad)

    def test_label_selector_dict(self):
        sel = labels.from_label_selector({
            "matchLabels": {"app": "web"},
            "matchExpressions": [
                {"key": "tier", "operator": "In", "values": ["fe", "be"]},
                {"key": "legacy", "operator": "DoesNotExist"},
            ],
        })
        assert sel.matches({"app": "web", "tier": "fe"})
        assert not sel.matches({"app": "web", "tier": "db"})
        assert not sel.matches({"app": "web", "tier": "fe", "legacy": "1"})
        # nil selector matches nothing; empty selector matches everything
        assert not labels.from_label_selector(None).matches({"a": "b"})
        assert labels.from_label_selector({}).matches({"a": "b"})

    def test_roundtrip_str(self):
        s = "a=b,c in (d,e),!f,g"
        sel = labels.parse(s)
        assert labels.parse(str(sel)).matches({"a": "b", "c": "d", "g": "x"})


class TestQuantity:
    @pytest.mark.parametrize("s,milli", [
        ("0", 0), ("1", 1000), ("100m", 100), ("1500m", 1500),
        ("1.5", 1500), ("0.1", 100), ("2k", 2_000_000),
        ("1Ki", 1024_000), ("1Mi", 1024**2 * 1000), ("128Mi", 128 * 1024**2 * 1000),
        ("1G", 10**9 * 1000), ("1e3", 10**3 * 1000), ("1E3", 10**3 * 1000),
        ("-2", -2000), ("+3", 3000),
    ])
    def test_parse(self, s, milli):
        assert quantity.parse(s).milli == milli

    @pytest.mark.parametrize("bad", ["", "abc", "1.2.3", "1ZiB", "e3", "1 Gi x"])
    def test_parse_errors(self, bad):
        with pytest.raises(quantity.QuantityError):
            quantity.parse(bad)

    @pytest.mark.parametrize("s,out", [
        ("100m", "100m"), ("1500m", "1500m"), ("1", "1"), ("2000", "2k"),
        ("128Mi", "128Mi"), ("1024Ki", "1Mi"), ("1Gi", "1Gi"), ("1000", "1k"),
        ("0", "0"),
    ])
    def test_canonical_string(self, s, out):
        assert str(quantity.parse(s)) == out

    def test_arithmetic_and_cmp(self):
        assert quantity.cmp("1", "1000m") == 0
        assert quantity.cmp("1Gi", "1G") > 0
        assert str(quantity.parse("1") + quantity.parse("500m")) == "1500m"
        assert quantity.parse("2").value() == 2
        assert quantity.parse("1500m").value() == 2  # ceil, like Quantity.Value()
        assert quantity.parse("250m").milli_value() == 250
        got = quantity.add_resources({"cpu": "1"}, {"cpu": "500m", "memory": "1Gi"})
        assert quantity.parse(got["cpu"]).milli == 1500
        assert got["memory"] == "1Gi"

    def test_sub_milli_rounds_up(self):
        assert quantity.parse("1.0005").milli == 1001


class TestMeta:
    def test_accessors_and_keys(self):
        pod = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": "web-1", "namespace": "prod",
                            "labels": {"app": "web"}}}
        assert meta.name(pod) == "web-1"
        assert meta.namespaced_key(pod) == "prod/web-1"
        assert meta.split_key("prod/web-1") == ("prod", "web-1")
        assert meta.split_key("node-1") == ("", "node-1")
        assert meta.gvk(pod) == ("", "v1", "Pod")
        rs = {"apiVersion": "apps/v1", "kind": "ReplicaSet", "metadata": {}}
        assert meta.gvk(rs) == ("apps", "v1", "ReplicaSet")

    def test_controller_ref(self):
        owner = {"apiVersion": "apps/v1", "kind": "ReplicaSet",
                 "metadata": {"name": "rs", "uid": "u1"}}
        ref = meta.owner_reference(owner)
        child = {"metadata": {"ownerReferences": [ref]}}
        got = meta.controller_ref(child)
        assert got and got["uid"] == "u1" and got["kind"] == "ReplicaSet"
        assert meta.controller_ref({"metadata": {}}) is None

    def test_deep_copy_isolated(self):
        a = {"metadata": {"labels": {"k": "v"}}}
        b = meta.deep_copy(a)
        b["metadata"]["labels"]["k"] = "changed"
        assert a["metadata"]["labels"]["k"] == "v"


class TestScheme:
    def _scheme(self):
        s = scheme.Scheme()
        def default_pod(o):
            o.setdefault("spec", {}).setdefault("schedulerName", "default-scheduler")
        def validate_pod(o):
            return ["spec.containers: Required value"] if not o.get("spec", {}).get("containers") else []
        s.register(scheme.ResourceInfo("", "v1", "Pod", "pods", short_names=("po",),
                                       subresources=("status", "binding"),
                                       defaulter=default_pod, validator=validate_pod))
        s.register(scheme.ResourceInfo("apps", "v1", "Deployment", "deployments",
                                       short_names=("deploy",)))
        return s

    def test_lookup(self):
        s = self._scheme()
        assert s.lookup_resource("", "pods").kind == "Pod"
        assert s.lookup_resource("", "po").kind == "Pod"
        assert s.lookup_resource("apps", "deploy").kind == "Deployment"
        assert s.lookup_resource("apps", "deployments").list_kind == "DeploymentList"
        assert s.lookup_resource("", "nothere") is None

    def test_default_validate_roundtrip(self):
        s = self._scheme()
        pod = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": "p"}, "spec": {"containers": [{"name": "c", "image": "i"}]}}
        s.default(pod)
        assert pod["spec"]["schedulerName"] == "default-scheduler"
        s.validate(pod)  # passes
        bad = {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "p"}}
        with pytest.raises(errors.StatusError) as ei:
            s.validate(bad)
        assert ei.value.code == 422
        data = scheme.Scheme.encode(pod)
        assert scheme.Scheme.decode(data) == pod


class TestWatch:
    def test_stream_and_stop(self):
        w = watch.Watch()
        w.send(watch.Event(watch.ADDED, {"metadata": {"name": "a"}}))
        w.send(watch.Event(watch.MODIFIED, {"metadata": {"name": "a"}}))
        ev = w.next(timeout=1)
        assert ev.type == watch.ADDED
        w.stop()
        ev2 = w.next(timeout=1)
        assert ev2 is not None and ev2.type == watch.MODIFIED
        assert w.next(timeout=0.1) is None
        assert not w.send(watch.Event(watch.ADDED, {}))  # post-stop send refused

    def test_an_event_taken_says_when_it_was_buffered(self, monkeypatch):
        """`buffered_at` (ISSUE 47): the consumer finds when the event it
        took last went into the buffer, whichever way it went in."""
        import time as _time

        now = [50.0]
        monkeypatch.setattr(_time, "monotonic", lambda: now[0])
        w = watch.Watch()
        w.send(watch.Event(watch.ADDED, {"n": 1}))
        now[0] = 50.25
        assert w.offer(watch.Event(watch.ADDED, {"n": 2}))
        now[0] = 51.0
        assert w.next(timeout=1).object == {"n": 1}
        assert w.buffered_at == 50.0
        assert next(iter(w)).object == {"n": 2}
        assert w.buffered_at == 50.25

    def test_slow_watcher_terminated(self):
        w = watch.Watch(capacity=2)
        assert w.send(watch.Event(watch.ADDED, {"n": 1}))
        assert w.send(watch.Event(watch.ADDED, {"n": 2}))
        assert not w.send(watch.Event(watch.ADDED, {"n": 3}), timeout=0.05)
        assert w.stopped


class TestErrors:
    def test_taxonomy(self):
        e = errors.new_not_found("pods", "x")
        assert errors.is_not_found(e) and e.code == 404
        assert errors.is_conflict(errors.new_conflict("pods", "x", "rv mismatch"))
        assert errors.is_already_exists(errors.new_already_exists("pods", "x"))
        assert errors.is_gone(errors.new_gone("compacted"))
        st = e.status()
        assert st["kind"] == "Status" and st["code"] == 404
        back = errors.from_status(st)
        assert errors.is_not_found(back)

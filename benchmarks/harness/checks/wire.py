"""The wire's own guarantees, for a wiring that puts a socket between the
scheduler and the apiserver (`wirings/http.py`); this module imports nothing
of the program and opens its own connection with the standard library.

  bindings_acknowledged_not_listed
        of the run's pods, each Binding the scheduler's transport was
        acknowledged (a 2xx to its POST `pods/<name>/binding`) is looked up
        AFTER the window in a list of the namespace's pods made over a NEW
        connection: one for each that the list has on another node or on
        none, and one for each that the client's own watch saw on another
        node or never saw. An acknowledgement the store does not hold, or
        holds elsewhere, is a lost or altered write.
  wire_request_errors
        requests of either side's transport (the scheduler's, the
        benchmark's client's) that ended in a transport error or a 5xx, plus
        the re-dials taken after a kept-alive connection was found closed:
        the transports' own counters, whole run. A run that needed a retry
        measured the retry.

The wiring hands over what the check cannot make itself (`hand`): the
apiserver's URL, the acknowledgements its instrumented transport kept
(plain `{pod name: node name}`), and a function giving the transports'
counters summed. Under a wiring that hands nothing there is no wire: both
counts read 0 and the replay says it looked at nothing. A third count of the
same guarantee, `watch_streams_broken`, is among the wiring's own counters
(`Cluster.counters()["zero"]`), printed beside its limit like these.
"""

from __future__ import annotations

import http.client
import json
import urllib.parse

NAMES = ("wire_request_errors", "bindings_acknowledged_not_listed")

#: what the wiring of THIS run handed over; `cfg` says which run it was
HANDED: dict = {}


def hand(cfg: dict, url: str, acknowledged: dict, counters) -> None:
    HANDED.clear()
    HANDED.update(cfg=cfg, url=url, acknowledged=acknowledged,
                  counters=counters)


def _handed(ctx: dict) -> dict:
    return HANDED if HANDED.get("cfg") is ctx["cfg"] else {}


def list_pods(url: str, namespace: str = "default") -> dict:
    """`{pod name: node name or ""}` by one GET over a connection of its
    own, closed again."""
    split = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(split.netloc, timeout=120)
    try:
        conn.request("GET", f"{split.path}/api/v1/namespaces/{namespace}/pods",
                     headers={"Accept": "application/json",
                              "Connection": "close"})
        r = conn.getresponse()
        body = r.read()
        if r.status != 200:
            raise SystemExit(f"check wire: the list over a new connection "
                             f"answered {r.status}: {body[:200]!r}")
    finally:
        conn.close()
    return {p["metadata"]["name"]: (p.get("spec") or {}).get("nodeName", "")
            for p in json.loads(body)["items"]}


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    handed = _handed(ctx)
    if not handed:
        return []
    c = handed["counters"]()
    return [f"request that ended in a transport error or a 5xx (#{i + 1})"
            for i in range(int(c["http_errors"]))] \
        + [f"re-dial after a closed connection (#{i + 1})"
           for i in range(int(c["http_retries"]))]


def replay(nodes: list, prebound: list, history: list, by_name: dict,
           shapes: list, ctx: dict) -> tuple:
    handed = _handed(ctx)
    if not handed:
        return 0, []
    acknowledged = {n: node for n, node in
                    dict(handed["acknowledged"]).items() if n in by_name}
    listed = list_pods(handed["url"])
    seen = {name: node for what, name, node in history if what == "bound"}
    bad = []
    for name, node in acknowledged.items():
        if listed.get(name) != node:
            bad.append(f"{name}: acknowledged on {node}, the list over a "
                       f"new connection has it on {listed.get(name)!r}")
        if seen.get(name) != node:
            bad.append(f"{name}: acknowledged on {node}, the client's watch "
                       f"saw it on {seen.get(name)!r}")
    return len(acknowledged), bad

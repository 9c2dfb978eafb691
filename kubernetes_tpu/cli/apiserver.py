"""`python -m kubernetes_tpu.cli apiserver`: the apiserver alone, in a
process of its own (cmd/kube-apiserver): an `APIServer` over the native
store behind `HTTPGateway`, until SIGTERM or SIGINT.

The first line of its standard output is one JSON object, written once the
socket listens: `{"url", "store", "pid"}` (`store` is the type of the KV
backend: `NativeKV` where native/kvstore.cpp builds). `/healthz` answers
from then on; `/metrics` is the process's own account
(`apiserver_request_duration_seconds`, `storage_txn_duration_seconds`,
`process_cpu_seconds_total`). A scheduler is a process of its own over
`Client.http(url)` (`SchedulerServer`, `APIBinder`, `APIBindIntentLedger`).

This module imports no jax, and neither does anything it starts: a process
that serves the store must never take the chip from the one that schedules.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
from typing import List, Optional


def apiserver_main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="apiserver",
                                description=__doc__.split("\n\n")[0])
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=6443, help="0: any free port")
    p.add_argument("--data-dir", default=None,
                   help="make the store durable (WAL + snapshots) here")
    p.add_argument("--exit-with-parent", action="store_true",
                   help="end when the process that started this one is gone")
    args = p.parse_args(argv)

    from kubernetes_tpu.apiserver import APIServer, HTTPGateway

    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    api = APIServer(data_dir=args.data_dir)   # steady_heap() is its first act
    gateway = HTTPGateway(api, host=args.host, port=args.port).start()
    print(json.dumps({"url": gateway.url,
                      "store": type(api.storage.kv).__name__,
                      "pid": os.getpid()}), flush=True)
    parent = os.getppid()
    try:
        while not done.wait(1.0 if args.exit_with_parent else None):
            if os.getppid() != parent:
                break   # orphaned: whoever would have ended us is gone
    finally:
        gateway.stop()
        api.close()
    return 0


if __name__ == "__main__":
    sys.exit(apiserver_main())

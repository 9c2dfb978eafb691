"""The `http` wiring's controls, for `run_cell(sabotage=)` and the control
runs (`chip_control_http.py`): each forges the wire underneath ONE Binding of
the measured scheduler, where its transport dials, so that the transport's
own counters and the wire check see what they would see of a real fault. The
run must come out `correct: false`."""

from __future__ import annotations

import http.client


def _lossy(server, request, getresponse) -> None:
    """The measured scheduler's transport dials connections whose `request`
    and `getresponse` go through the control for the first Binding sent."""
    transport = server.client.transport
    state = {"done": False}

    class Forged(http.client.HTTPConnection):
        chosen = False

        def request(self, method, url, body=None, headers={}, **kw):
            self.chosen = (method == "POST" and url.endswith("/binding")
                           and not state["done"])
            if self.chosen:
                state["done"] = True
                return request(self, super().request, method, url, body,
                               headers)
            return super().request(method, url, body=body, headers=headers,
                                   **kw)

        def getresponse(self):
            if self.chosen:
                self.chosen = False
                return getresponse(self, super().getresponse)
            return super().getresponse()

    transport._dial = Forged


def drop_answer_after_store(cluster, server) -> None:
    """One Binding's POST reaches the server and is stored; its answer is
    lost on the way back (the connection is reset before a byte of it). The
    transport re-dials once and sends the Binding again, the server refuses
    the second (already assigned), and the pod ends bound: only the wire
    saw anything. Caught by `wire_request_errors` (the retry taken)."""

    def request(conn, send, method, url, body, headers):
        return send(method, url, body=body, headers=headers)

    def getresponse(conn, receive):
        receive().read()   # the server stored it and answered
        raise ConnectionResetError("control: the answer was lost")

    _lossy(server, request, getresponse)


class _Acknowledgement:
    """A 201 nobody sent."""

    status, will_close = 201, False

    def read(self) -> bytes:
        return b"{}"

    def getheader(self, name, default=None):
        return "application/json" if name == "Content-Type" else default


def forge_acknowledgement(cluster, server) -> None:
    """One Binding is acknowledged without ever being sent: the scheduler
    holds a 2xx for a write the store never saw. Caught by
    `bindings_acknowledged_not_listed` (and by `pods_never_bound`)."""

    def request(conn, send, method, url, body, headers):
        return None

    def getresponse(conn, receive):
        return _Acknowledgement()

    _lossy(server, request, getresponse)


CONTROLS = {f.__name__: f for f in (drop_answer_after_store,
                                    forge_acknowledgement)}

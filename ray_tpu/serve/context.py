"""Per-request serve context.

Reference parity: serve/context.py — _serve_request_context contextvar
carrying request id / multiplexed model id into user code.
"""
from __future__ import annotations

import contextvars
import dataclasses


@dataclasses.dataclass
class RequestContext:
    request_id: str = ""
    multiplexed_model_id: str = ""
    app_name: str = ""
    deployment: str = ""
    # the front path's clock (serve/metrics.py, "front stages"): the
    # proxy's perf_counter_ns() as the request entered ``_dispatch`` and
    # the host that read it. 0 outside a proxied request
    ingress_ns: int = 0
    ingress_host: str = ""


_request_context: contextvars.ContextVar[RequestContext] = \
    contextvars.ContextVar("rtpu_serve_request_context",
                           default=RequestContext())


def get_request_context() -> RequestContext:
    return _request_context.get()


def set_request_context(**fields) -> contextvars.Token:
    return _request_context.set(RequestContext(**fields))


def reset_request_context(token: contextvars.Token) -> None:
    _request_context.reset(token)


_host = ""


def host_name() -> str:
    """This host's name, asked for once a process."""
    global _host
    if not _host:
        import socket
        _host = socket.gethostname()
    return _host


def local_ingress_ns(context=None) -> int:
    """The arrival stamp of the request this code runs for — the current
    one's, or that of a handle's ``context`` dict — if THIS host's clock
    took it, else 0: perf_counter is one clock for the processes of one
    host, and a front stage is never a difference of two machines'."""
    if context is None:
        ctx = _request_context.get()
        ns, host = ctx.ingress_ns, ctx.ingress_host
    else:
        ns, host = context.get("ingress_ns", 0), context.get(
            "ingress_host", "")
    return ns if ns and host == host_name() else 0


def get_multiplexed_model_id() -> str:
    """Inside a deployment: the model id the current request was routed
    with (reference: serve.get_multiplexed_model_id)."""
    return _request_context.get().multiplexed_model_id

"""HTTP front and router (serve/proxy.py): a request's mean time from the
entry of ``ProxyActor._dispatch`` to the executor thread that makes its
handle call — the route table's refresh, the ingress lookup, the body's
parse (10-16k ids in the long cells), admission and the wait for a thread
of the loop's default executor. Stage ``intake`` of
``rtpu_serve_front_stage_seconds`` between the run's two readings
(``_front.stage_ms``); None unless it counted the client's requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "intake")

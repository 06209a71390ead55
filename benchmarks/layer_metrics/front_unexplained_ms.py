"""HTTP front and router: ``front_overhead_ms`` (mean client TTFT less mean
engine TTFT) less the stages of it the program stamps, which lie end to
end — the way in (``ingress_to_submit_ms``), the pump's first chunk (stage
``first_chunk``: the first token's booking -> the stamp of the ring write
that took its chunk; ``first_chunk_lag_ms``'s interval, but over the
requests of the WHOLE run as every other term here, where the engine's
counters cover the window alone: in a cell of a hundred long answers the
two populations differ by hundreds of ms), both rings' first hop
(``ring_hop_first_ms``), the router's relay (``relay_first_ms``) and the
proxy's write (``proxy_write_first_ms``). What is left are the two
stretches no process of the system can stamp: the client's send ->
``_dispatch``, and the socket write -> the client's read
(``proxy_loop_lag_ms`` and ``gen_late_p90_ms`` say which side is late).
The first chunk's share is this line's own arithmetic: ``front_overhead_ms``
less the five stages' readers and this. None unless every part reads."""
from . import front_overhead_ms
from ._front import stage_ms


def read(ctx: dict):
    parts = [front_overhead_ms.read(ctx)]
    parts += [stage_ms(ctx, stage) for stage in (
        "to_submit", "first_chunk", "first_hop", "first_relay",
        "first_write")]
    if any(p is None for p in parts):
        return None
    return parts[0] - sum(parts[1:])

"""HTTP front and router, the rings (serve/handle.py
``ChannelResponseGenerator``): for a stream's first item, the time from
its write stamp (taken as the write began, in the writing process) to the
reading handle's ``read`` having returned, summed over the request's two
rings — the model replica's, read by the router's drain thread, and the
router's, read by the proxy's stream thread. Stage ``first_hop`` between
the run's two readings; None unless both counted the client's requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "first_hop")

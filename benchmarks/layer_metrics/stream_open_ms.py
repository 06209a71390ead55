"""HTTP front and router (serve/handle.py ``DeploymentHandle.remote``, the
stream branch): the actor round trip that opens a stream, from where
``router_wait_ms`` ends to the response generator in hand, summed over the
request's two hops — the proxy's handle to ``openai-router`` and the
router's drain thread's to the model deployment (which holds the whole of
``LLMServer._submit``: tokenise, prefix import, ``engine.submit``). Stage
``open`` between the run's two readings; None unless both counted the
client's requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "open")

"""One module per kind of load; a traffic file names its kind under
``generator``. Each offers ``async def run(traffic, rng, vocab, client,
window)``: send requests through ``client`` until ``window.end`` and return
when every request has ended. What a mix is — lengths, rates, sharing — is
in its traffic file; a new mix of an existing kind adds no code."""

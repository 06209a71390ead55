"""Program families (the model module's ``decode_paged``): device ms a
decode step, from the device trace.

The device time of the slice's decode-program executions over the device
steps they ran: an execution of ``jit_rtpu_decode_w<w>`` runs ``w`` steps
(``reduce/xplane.py`` ``WINDOW``; a program without a window in its name
runs one). Until PR 33 this was the median duration of one execution, which
is two numbers: ~8 steps where most executions of the slice are ``decode_w8``
and one step where most are ``decode_w1`` (ledger, PR 32, the OLMoE cell:
164.91 on one side and 20.47 on the other of a PR that did not touch the
program). The level in the ledger breaks at PR 33. None when the run was
not traced or the reduction counted no steps."""
from ._common import trace


def read(ctx: dict):
    t = trace(ctx)
    fam = t and t["families"].get("decode")
    if not fam or not fam.get("steps"):
        return None
    return 1e3 * fam["total_s"] / fam["steps"]

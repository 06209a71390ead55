"""Program families (models/llama.py prefill_paged_rows): median device
duration of one execution of a prefill program, from the device trace."""
from ._common import family_median_ms


def read(ctx: dict):
    return family_median_ms(ctx, "prefill")

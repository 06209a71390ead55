"""Program families (models/llama.py decode_paged): median device duration
of one execution of a decode program, from the device trace."""
from ._common import family_median_ms


def read(ctx: dict):
    return family_median_ms(ctx, "decode")

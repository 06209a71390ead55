"""Trainer: median device duration of one execution of the jitted train
step, from the device trace."""
from ._common import family_median_ms


def read(ctx: dict):
    return family_median_ms(ctx, "train_step")

"""Process start to the opening of the measured window: loading, weights,
warm-up (and compilation, in a run that compiles), the reference check and
the generator's warm-up period."""


def read(ctx: dict):
    return ctx["setup_s"]

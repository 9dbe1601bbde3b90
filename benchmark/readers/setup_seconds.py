"""Process start to the opening of the window, host clock, less the
seconds inside the first ``jax.devices()`` (the TPU runtime's own
start-up): imports, data and weights from the seed, loading, the
warm-up call and, in a run that compiles, compilation."""


def read(ctx, params):
    return ctx.setup_s

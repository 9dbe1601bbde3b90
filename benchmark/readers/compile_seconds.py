"""Backend-compile seconds summed over set-up (``jax.monitoring``
duration events that ended before the window opened): compiling and,
where the persistent cache has the program, reading it back."""


def read(ctx, params):
    return ctx.compile_seconds_in_setup()

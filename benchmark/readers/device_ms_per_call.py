"""Device busy milliseconds per call of the traced part (device 0)."""


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    return 1000.0 * ctx.trace.device(0).busy_s() / len(ctx.traced_calls)

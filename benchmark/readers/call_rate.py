"""Work completed by the window's calls over the time they took.

``work`` names the unit each call carries (``ctx.call(name, unit=n)``).
The time runs from the first call's start to the last call's end: what
one caller gets, whatever lies between the calls included, and not
``--seconds`` (the last call runs past the window's end).
"""


def read(ctx, params):
    calls = ctx.window_calls()
    if not calls:
        return None
    work = sum(c.work[params["work"]] for c in calls)
    return work / (calls[-1].end - calls[0].start)

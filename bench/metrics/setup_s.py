"""Process start to the start of the cell's traffic: weight draw,
engine build, compile or cache load and warm-up; the lead-in of the
traffic is not in it."""


def read(ctx):
    return ctx.setup_s

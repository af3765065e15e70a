"""Backend compiles inside the measured window (JAX's own compile
events); 0 when warm-up covered every program the traffic reached."""


def read(ctx):
    return float(ctx.win.compiles)

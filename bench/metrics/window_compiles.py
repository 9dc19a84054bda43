"""Backend compilations inside the measured window (the program's
``jax.monitoring`` compile log); 0 when every shape was warmed up."""


def read(ctx):
    return float(ctx["compiles"])

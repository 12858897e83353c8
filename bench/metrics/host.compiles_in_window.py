"""Compilations and compile-cache loads inside the window, counted by a
``jax.monitoring`` listener; set-up should leave none."""


def read(ctx):
    return float(len(ctx.compiles))

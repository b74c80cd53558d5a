"""Lowerings (traces that may compile) inside the serving window, counted
through ``jax.monitoring``; every shape the mix uses is warmed in set-up,
so each one here is a stall a request waited on."""


def read(ctx):
    return float(ctx["window"].compiles)

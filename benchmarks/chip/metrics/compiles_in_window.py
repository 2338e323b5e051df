"""Compiles (or compile-cache loads) that JAX reported between the
window's start and its end, counted by the harness with a
`jax.monitoring` listener. The window should hold none: each one stalls
every lane. Read as `compiles_in_window.<moves>`, as `idle_pct` is."""


def read(trace, window, cell):
    return window["compiles"]

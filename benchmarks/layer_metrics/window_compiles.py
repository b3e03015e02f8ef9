"""Programs handed to the backend compiler inside the measured window
(``jax.monitoring`` compile events, counted by the driver). Should read 0:
anything else is compilation charged to ``fit_s``."""


def read(ctx):
    return ctx["counters"].get("window_compiles")

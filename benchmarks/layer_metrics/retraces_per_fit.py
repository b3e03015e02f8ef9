"""Programs traced again per fit: the ``jax.compile`` spans with
``stage=trace`` under the fit, a trace inside another's trace (a jit called
while an outer one is traced) not counted apart. A note lists them by the
span that caused them (``executor.node`` operator, ``solver.*``)."""

from benchmarks.layer_metrics import span_account


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    fits = found["fits"]
    owners = {k: round(n / fits, 2) for k, n in sorted(found["traces"].items())}
    ctx["notes"].append(
        f"retraces per fit by owning span and program: {owners}; traces nested in "
        f"these, per fit: {round(found['nested_traces'] / fits, 2)}")
    value = sum(found["traces"].values()) / fits
    return int(value) if value == int(value) else value

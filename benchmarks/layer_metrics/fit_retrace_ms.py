"""Host time per fit that JAX spends tracing, lowering and compiling or
fetching programs again: the united ``jax.compile`` spans (the program's
compile ledger) under the fit's ``pipeline.build`` + ``pipeline.fit``."""

from benchmarks.layer_metrics import span_account


def read(ctx):
    return span_account.layer_ms(ctx, span_account.RETRACE)

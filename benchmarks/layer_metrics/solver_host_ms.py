"""Host time per fit in the solver's own phases: self time of
``estimator.fit`` and ``solver.*`` (scaler fits and applies, the stacked
copy, the dispatch of the fused BCD, the streamed fit's one program), with
compiles and drains under them taken out."""

from benchmarks.layer_metrics import span_account


def read(ctx):
    return span_account.layer_ms(ctx, span_account.SOLVER)

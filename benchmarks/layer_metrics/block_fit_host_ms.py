"""Host SELF time per fit under ``pipeline.build`` + ``pipeline.fit`` in the
block cell, from the program's spans (``span_account``'s four layers): what
the host itself spends on a fit — retracing, the pipeline layer (the
optimizer's sample of a 50-branch featurizer among it), the solver's own
dispatches — with its waits for the device left out. The note gives the
four layers, the sample's share, the traces a fit and the spans by name."""

from collections import Counter

from benchmarks.layer_metrics import span_account

SAMPLE = "optimizer.rule.NodeOptimizationRule"


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    fits = found["fits"]
    layers = {k: round(us / fits / 1e3, 3) for k, us in found["layers_us"].items()}
    spans = span_account.session_spans() or []
    whole = Counter()
    for s in spans:
        if s["name"] != "jax.compile":
            whole[s["name"]] += s["dur_us"]
    longest = {name: round(us / fits / 1e3, 3) for name, us in whole.most_common(12)}
    ctx["notes"].append(
        f"block_fit_host_ms per fit over {fits} fits: layers {layers} (the wait is left out "
        f"of the metric); the optimizer's sample ({SAMPLE}, whole duration) "
        f"{round(whole[SAMPLE] / fits / 1e3, 3)} ms; traces a fit "
        f"{round(sum(found['traces'].values()) / fits, 2)} (+{found['nested_traces'] / fits:.1f} "
        f"nested); spans by whole duration, ms a fit: {longest}")
    host_us = sum(us for layer, us in found["layers_us"].items() if layer != span_account.WAIT)
    return host_us / fits / 1e3

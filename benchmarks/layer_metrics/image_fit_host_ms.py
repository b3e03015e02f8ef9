"""Host SELF time per fit under ``pipeline.build`` + ``pipeline.fit`` in the
image cell, from the program's spans (``span_account``'s four layers): the
filter draw's dispatch and the graph, the optimizer, the executor and the
solver's own dispatches, with the host's waits for the device left out.
The note gives the four layers, the traces a fit (a sweep of new pipelines
should trace nothing again after its first fit), what ``pipeline.build``
says and the spans by name."""

from collections import Counter

from benchmarks.layer_metrics import span_account


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
    longest = {name: round(us / fits / 1e3, 3) for name, us in whole.most_common(10)}
    build = next((s.get("args", {}) for s in reversed(spans)
                  if s["name"] == "pipeline.build"), {})
    ctx["notes"].append(
        f"image_fit_host_ms per fit over {fits} fits: layers {layers} (the wait is left out "
        f"of the metric); traces a fit {round(sum(found['traces'].values()) / fits, 2)} "
        f"(+{found['nested_traces'] / fits:.1f} nested): {found['traces']}; pipeline.build "
        f"says {build}; spans by whole duration, ms a fit: {longest}")
    host_us = sum(us for layer, us in found["layers_us"].items() if layer != span_account.WAIT)
    return host_us / fits / 1e3

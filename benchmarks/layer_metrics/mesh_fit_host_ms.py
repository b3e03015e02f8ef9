"""Host SELF time per fit under ``pipeline.build`` + ``pipeline.fit`` in the
mesh cell, from the program's spans (``span_account``'s four layers): what
the host itself spends on a fit over sharded rows — retracing, the pipeline
layer, the solver's one dispatch — with its waits for the devices left out.

The note says what a sound mesh fit shows none of: the traces a fit (a
sweep over new pipelines should compile nothing after the warm-up fit), any
``data.shard`` span under a fit (the rows are placed once, in set-up), and
``estimator.fit``'s attributes (``engine="stream_mesh"``, ``devices``,
``psum_bytes``) with ``solver.stream_fit``'s (``mesh_shape``, ``rows_local``,
``tile_rows``). A program without such a session gives None."""

from benchmarks.layer_metrics import span_account


def in_the_fits(spans):
    """The spans under a root ``pipeline.build`` / ``pipeline.fit``: the
    session also holds what the benchmark does between and after the fits
    (the scoring of the probe rows traces and compiles its own programs)."""
    by_id = {s["span_id"]: s for s in spans}

    def under_a_fit(s):
        while s["parent_id"] in by_id:
            s = by_id[s["parent_id"]]
        return s["parent_id"] is None and s["name"] in span_account.ROOTS

    return [s for s in spans if under_a_fit(s)]


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    fits = found["fits"]
    layers = {k: round(us / fits / 1e3, 3) for k, us in found["layers_us"].items()}
    spans = in_the_fits(span_account.session_spans() or [])
    stages = {}
    for s in spans:
        if s["name"] == "jax.compile":
            stage = s.get("args", {}).get("stage", "?")
            stages[stage] = stages.get(stage, 0) + 1
    shards = [{**s.get("args", {}), "ms": round(s["dur_us"] / 1e3, 3)}
              for s in spans if s["name"] == "data.shard"]
    said = {name: next((s.get("args", {}) for s in spans if s["name"] == name), None)
            for name in ("estimator.fit", "solver.stream_fit")}
    ctx["notes"].append(
        f"mesh_fit_host_ms per fit over {fits} fits: layers {layers} (the wait is left out "
        f"of the metric); traces a fit {round(sum(found['traces'].values()) / fits, 2)} "
        f"(+{found['nested_traces'] / fits:.1f} nested) by owner {found['traces']}; "
        f"jax.compile spans under the fits by stage {stages or 'none'}; data.shard spans under "
        f"the fits {shards or 'none'}; estimator.fit says {said['estimator.fit']}; "
        f"solver.stream_fit says {said['solver.stream_fit']}")
    host_us = sum(us for layer, us in found["layers_us"].items() if layer != span_account.WAIT)
    return host_us / fits / 1e3

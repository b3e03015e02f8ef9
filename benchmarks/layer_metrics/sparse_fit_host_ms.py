"""Host SELF time per fit under ``pipeline.fit`` in the sparse cell, from the
program's spans (``span_account``): what the host itself spends on a fit —
retracing, the pipeline layer, the solver's own phases — with its waits for
the device (``executor.drain``: the read of the solve's loss is the one that
matters here) left out. The note gives the four layers, the wait among
them, and the sparse solver's spans one by one."""

from benchmarks.layer_metrics import span_account

SOLVER_SPANS = ("estimator.fit", "solver.chunk_tiles", "solver.gram_fold", "fold.segment",
                "solver.lbfgs", "solver.gather_lbfgs")


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    fits = found["fits"]
    layers = {k: round(us / fits / 1e3, 3) for k, us in found["layers_us"].items()}
    spans = span_account.session_spans() or []
    each = {name: round(sum(s["dur_us"] for s in spans if s["name"] == name) / fits / 1e3, 3)
            for name in SOLVER_SPANS}
    attrs = next((s["args"] for s in spans if s["name"] == "estimator.fit"
                  and "engine" in s.get("args", {})), {})
    ctx["notes"].append(
        f"sparse_fit_host_ms per fit over {fits} fits: layers {layers} (the wait is left "
        f"out of the metric); solver spans, whole durations {each}; estimator.fit "
        f"attributes {attrs}")
    host_us = sum(us for layer, us in found["layers_us"].items() if layer != span_account.WAIT)
    return host_us / fits / 1e3

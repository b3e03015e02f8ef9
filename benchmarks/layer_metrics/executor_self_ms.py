"""Host time per fit in the pipeline layer itself: self time of
``pipeline.build``, ``pipeline.fit``, ``fit.*``, ``optimizer.rule.*``,
``verify.pre_pass``, ``executor.node`` and ``cost.select`` — what is left of
them once compiles, drains and the solver's spans are taken out. Its note is
the whole account: the four times, their sum, the spanned total they
partition, and the window's seconds per fit that the spans do not cover."""

from benchmarks.layer_metrics import span_account


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    fits = found["fits"]
    ms = {k: us / fits / 1e3 for k, us in found["layers_us"].items()}
    spanned = {k: us / fits / 1e3 for k, us in found["spanned_us"].items()}
    total, window = sum(spanned.values()), 1e3 * ctx["window"]["window_s"] / max(ctx["window"]["fits"], 1)
    ctx["notes"].append(
        f"span account per fit over {fits} fits, ms: fit_retrace_ms {ms['retrace']:.3f} + "
        f"device_wait_ms {ms['wait']:.3f} + executor_self_ms {ms['executor']:.3f} + "
        f"solver_host_ms {ms['solver']:.3f} = {sum(ms.values()):.3f}; spanned "
        f"{total:.3f} (pipeline.build {spanned.get('pipeline.build', 0.0):.3f}, pipeline.fit "
        f"{spanned.get('pipeline.fit', 0.0):.3f}); traced window {window:.3f} a fit, so "
        f"{window - total:.3f} is the benchmark's own (reset, final wait, fence, gc)")
    return ms["executor"]

"""Device idle time per fit while a span of the PROGRAM was open: every gap
of the device's busy union split by the innermost ``ks.*`` host annotation
over each part of it (``jax.compile`` spans laid on the profile's clock
included), the mean over the device planes. The note gives the time by span
and, beside it, the ``outside`` part — idle under no program span: the
benchmark's own (``gc.collect``, ``PipelineEnv.reset``, ``and_then``, the
fence). From the program's own account of the window's profile
(``device_account.py``)."""

from benchmarks.layer_metrics import device_account

LISTED = 8


def read(ctx):
    found = device_account.of_window(ctx)
    if found is None:
        return None
    outside = device_account.OUTSIDE
    inside = device_account.per_fit_ms(
        ctx, found, lambda p: sum(ns for s, ns in p["idle_ns_by_span"].items() if s != outside))
    out = device_account.per_fit_ms(
        ctx, found, lambda p: p["idle_ns_by_span"].get(outside, 0.0))
    by_span: dict = {}
    for p in found["planes"]:
        for s, ns in p["idle_ns_by_span"].items():
            if s != outside:
                by_span[s] = by_span.get(s, 0.0) + ns
    scale = len(found["planes"]) * ctx["window"]["fits"] * 1e6
    top = sorted(by_span.items(), key=lambda kv: -kv[1])[:LISTED]
    ctx["notes"].append(
        f"idle_in_program_ms: {device_account.spread(inside)} ms a fit and device idle under "
        f"a program span, {device_account.spread(out)} under none (the benchmark's own); by "
        f"innermost span: " + (", ".join(f"{s} {ns / scale:.3f}" for s, ns in top) or "none"))
    return sum(inside) / len(inside)

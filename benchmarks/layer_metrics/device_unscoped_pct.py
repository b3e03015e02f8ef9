"""Share of the device's busy time that the phase account does not name:
the self time of operations under no ``ks.*`` name scope over all self
time, summed over the device planes (lower is better: what is unscoped can
be seen only by its compiler-given name). The note lists the five largest
unscoped operations with their program. From the program's own account of
the window's profile (``device_account.py``)."""

from benchmarks.layer_metrics import device_account


def read(ctx):
    found = device_account.of_window(ctx)
    if found is None:
        return None
    planes = found["planes"]
    total = sum(sum(p["by_scope_ns"].values()) for p in planes)
    if not total:
        return None
    unscoped = sum(p["by_scope_ns"].get(device_account.UNSCOPED, 0.0) for p in planes)
    largest: dict = {}
    for p in planes:
        for op, ns in p["unscoped_ops_ns"].items():
            largest[op] = largest.get(op, 0.0) + ns
    fits = ctx["window"]["fits"]
    top = sorted(largest.items(), key=lambda kv: -kv[1])[:5]
    ctx["notes"].append(
        "device_unscoped_pct: "
        + device_account.spread(device_account.per_fit_ms(
            ctx, found, lambda p: p["by_scope_ns"].get(device_account.UNSCOPED, 0.0)))
        + " ms a fit and device under no ks.* scope; the largest (program/operation, ms a "
        "fit and device): "
        + (", ".join(f"{op} {ns / len(planes) / fits / 1e6:.3f}" for op, ns in top) or "none"))
    return 100.0 * unscoped / total

"""The mesh fit's collective: summed self time of the all-reduce operations
of the trace — ``all-reduce*``, and ``psum*``, the name XLA gives the one it
keeps from a ``jax.lax.psum`` — per fit and per device, in ms. ``trace.py`` sums an
operation's self time over the device planes and divides by their number,
so this is the mean over the mesh's devices: on each, the time from its own
arrival at the all-reduce to the result — the wait for the slowest device's
fold included, which is part of what the collective costs a fit.

The note gives the bytes a device hands one round (from the configuration's
shapes: the float32 Gramian, FᵀY, the column sums and Σy²) and the GB/s
they imply through a ring's 2 (p − 1) / p passes. A trace with no such
operation — one device, or a program that reduces otherwise — gives None.
"""

import math

PREFIXES = ("all-reduce", "psum")


def round_bytes(config) -> int:
    """What one device contributes to the fit's one all-reduce round."""
    d, k = config["num_cosines"] * config["block_size"], config["num_classes"]
    return 4 * (d * d + d * k + d + k + 1)


def read(ctx):
    trace, window, config = ctx["trace"], ctx["window"], ctx["config"]
    if trace is None or not window["fits"]:
        return None
    found = {name: s for name, s in trace["op_seconds"].items() if name.startswith(PREFIXES)}
    if not found:
        ctx["notes"].append("allreduce_ms: no all-reduce operation in the trace: nothing to read")
        return None
    per_fit_s = sum(found.values()) / window["fits"]
    devices = math.prod((config.get("mesh") or {}).get("shape") or [1])
    nbytes = round_bytes(config)
    moved = 2 * (devices - 1) / devices * nbytes if devices > 1 else 0
    ctx["notes"].append(
        f"allreduce_ms: {per_fit_s * 1e3:.3f} ms a fit and device (mean over the device "
        f"planes) in {sorted(found)}; a round carries {nbytes} bytes a device, "
        f"{moved / 1e9:.3f} GB through each device's links over {devices} devices: "
        f"{moved / per_fit_s / 1e9:.1f} GB/s if the time were all transfer")
    return per_fit_s * 1e3

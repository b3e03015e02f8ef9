"""The ``cosine_features`` Mosaic kernel's share of its roofline in the block
cell, where it featurizes one block's slab at a time: the least time the
chip could take for the calls the window made — every fit makes each of its
``num_cosines`` blocks again in each of its ``num_epochs`` sweeps, all rows a
call (``arith.cosine_features_cost`` of one call: the rows and the block's
bank read once, the slab written once) — over the summed device time of the
kernel's events in the trace. No such event (the tier on XLA's fusions, the
parent of the PR that added this) gives nothing."""

from benchmarks import arith

KERNEL = "cosine_features"


def read(ctx):
    trace, config, window = ctx["trace"], ctx["config"], ctx["window"]
    if trace is None or not window["fits"]:
        return None
    # the trace names a call ``cosine_features.<n>``: match the whole base name
    kernel_s = sum(s for name, s in trace["op_seconds"].items()
                   if name.split(".")[0] == KERNEL)
    if kernel_s <= 0:
        return None
    calls = window["fits"] * config["num_cosines"] * config["num_epochs"]
    flops, nbytes = arith.cosine_features_cost(
        window["rows"], config["d_in"], config["block_size"])
    least_s, bound = arith.least_seconds(
        calls * flops, calls * nbytes, arith.peaks(ctx["device_kind"]))
    ctx["notes"].append(
        f"{KERNEL} (block slabs): {kernel_s:.4f} s on the device for {calls} calls of "
        f"{window['rows']} rows x {config['block_size']} features in {window['fits']} fits; "
        f"least {least_s:.4f} s, bound by {bound}")
    return 100.0 * least_s / kernel_s

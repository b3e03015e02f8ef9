"""The ``cosine_features`` Mosaic kernel's share of its roofline: the least
time the chip could take for the rows the window featurized (every fit
featurizes every row once), over the summed device time of the kernel's
events in the trace. Nothing to read — no such event — gives nothing."""

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
    flops, nbytes = arith.cosine_features_cost(
        window["fits"] * window["rows"], config["d_in"],
        config["num_cosines"] * config["block_size"])
    least_s, bound = arith.least_seconds(flops, nbytes, arith.peaks(ctx["device_kind"]))
    ctx["notes"].append(
        f"{KERNEL}: {kernel_s:.4f} s on the device for {window['fits']} fits; "
        f"least {least_s:.4f} s, bound by {bound}")
    return 100.0 * least_s / kernel_s

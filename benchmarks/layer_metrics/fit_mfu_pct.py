"""The whole fit's share of the chip's peak: the operations the
configuration's algorithm needs for one fit (``arith.FIT_FLOPS``, from the
cell's shapes), over the window's seconds per fit times the peak FLOP/s."""

from benchmarks import arith


def read(ctx):
    config, window = ctx["config"], ctx["window"]
    if not window["fits"]:
        return None
    flops = arith.FIT_FLOPS[config["fit_flops"]](
        window["rows"], config["d_in"], config["num_cosines"] * config["block_size"],
        config["num_classes"], config["block_size"], config["num_epochs"])
    fit_s = window["window_s"] / window["fits"]
    return 100.0 * flops / (fit_s * arith.peaks(ctx["device_kind"])["flops_per_s"])

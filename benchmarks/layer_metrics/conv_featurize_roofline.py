"""The convolution featurize's share of its roofline: the least time the
chip could take for the images the window featurized — one filter product a
window (``arith_conv.conv_featurize_cost``: the images read once and the
pooled features written once, the same count whatever implements it) — over
the device's self time under ``ks.conv_featurize`` in the program's own
account of the window's profile. No account, or no such scope in it, gives
nothing."""

from benchmarks import arith, arith_conv
from benchmarks.layer_metrics import device_account

SCOPE = "ks.conv_featurize"


def read(ctx):
    found = device_account.of_window(ctx)
    if found is None:
        return None
    planes = found["planes"]
    device_s = sum(p["by_scope_ns"].get(SCOPE, 0.0) for p in planes) / len(planes) / 1e9
    if device_s <= 0:
        return None
    config, window = ctx["config"], ctx["window"]
    images = window["fits"] * window["rows"]
    flops, nbytes = arith_conv.conv_featurize_cost(images, config)
    least_s, bound = arith.least_seconds(flops, nbytes, arith.peaks(ctx["device_kind"]))
    ctx["notes"].append(
        f"conv_featurize_roofline: {device_s:.4f} s under {SCOPE} for {images} images in "
        f"{window['fits']} fits ({flops:.4e} operations, {nbytes:.4e} bytes); least "
        f"{least_s:.4f} s, bound by {bound}")
    return 100.0 * least_s / device_s

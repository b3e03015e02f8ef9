"""Device time per fit making the convolution's features: the self time of
the operations under the name scope ``ks.conv_featurize`` — windows, their
normalisation, the whitener's means, the filter products, the rectifier,
the pools and the flattening, one program over the images in batches —
from the program's own account of the window's profile
(``device_account.py``). The note gives beside it, not in the sum, the
filter draw (``ks.patch_whiten``) and the solve's scopes, and the share of
the device's time under no ``ks.*`` scope (``device_unscoped_pct``'s
reckoning, which does not list this cell)."""

from benchmarks.layer_metrics import device_account

SCOPES = ("ks.conv_featurize",)
BESIDE = ("ks.patch_whiten", "ks.center", "ks.split", "ks.stack", "ks.gram_corr_fold",
          "ks.bcd_step")


def read(ctx):
    value = device_account.scopes_ms(ctx, "conv_featurize_device_ms", SCOPES, beside=BESIDE)
    found = device_account.of_window(ctx)
    if value is not None:
        planes = found["planes"]
        total = sum(sum(p["by_scope_ns"].values()) for p in planes)
        unscoped = sum(p["by_scope_ns"].get(device_account.UNSCOPED, 0.0) for p in planes)
        ctx["notes"].append(f"conv_featurize_device_ms: {100.0 * unscoped / total:.3f}% of the "
                            f"device's self time under no ks.* scope")
    return value

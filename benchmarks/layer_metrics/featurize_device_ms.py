"""Device time per fit making features: the self time of the operations
under the name scopes ``ks.featurize`` (the streamed and the resident tier)
and ``ks.block_featurize`` (the block tier), from the program's own account
of the window's profile (``device_account.py``)."""

from benchmarks.layer_metrics import device_account

SCOPES = ("ks.featurize", "ks.block_featurize")


def read(ctx):
    return device_account.scopes_ms(ctx, "featurize_device_ms", SCOPES)

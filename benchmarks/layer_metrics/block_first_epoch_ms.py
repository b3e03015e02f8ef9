"""Epoch 1 of a block-streamed fit, ms: the host's wait for the first of
the fit's two dispatches — 50 block Gramians, their factors, and the first
sweep's featurize, correlation, solve and update (``block_epochs``). Its
ratio to ``block_later_epoch_ms`` is what the stash is worth."""

from benchmarks.layer_metrics import block_epochs


def read(ctx):
    return block_epochs.epoch_ms(ctx, first=True)

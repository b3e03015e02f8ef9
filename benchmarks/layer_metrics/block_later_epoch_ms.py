"""One of epochs 2+ of a block-streamed fit, ms: the host's wait for the
second dispatch over the ``num_epochs - 1`` sweeps it runs — featurize,
correlation, two triangular solves and the residual update a block step,
the Gramian and its factor read from the stash (``block_epochs``)."""

from benchmarks.layer_metrics import block_epochs


def read(ctx):
    return block_epochs.epoch_ms(ctx, first=False)

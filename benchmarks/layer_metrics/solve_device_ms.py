"""Device time per fit solving: the self time of the operations under
``ks.bcd`` (block coordinate descent on the normal equations, with the
centring before it and the fitted loss after it), ``ks.bcd_step`` (the
resident solver's block steps), ``ks.block_factor`` (the block tier's
epoch-1 systems from their summed panels: centring, mirror, Cholesky factor,
the stash's writes), ``ks.block_update`` (the block tier's correlation, solve
and residual update) and ``ks.lbfgs_gram`` (L-BFGS on the Gramian) — each
apart in the note — from the program's own account of the window's profile
(``device_account.py``). The note also gives, not in this sum, what the
resident solver moves before it solves: ``ks.center`` (column sums and
subtraction), ``ks.split`` and ``ks.stack``."""

from benchmarks.layer_metrics import device_account

SCOPES = ("ks.bcd", "ks.bcd_step", "ks.block_factor", "ks.block_update", "ks.lbfgs_gram")
BESIDE = ("ks.center", "ks.split", "ks.stack")


def read(ctx):
    return device_account.scopes_ms(ctx, "solve_device_ms", SCOPES, beside=BESIDE)

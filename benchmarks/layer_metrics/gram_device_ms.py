"""Device time per fit building Gramians and correlations: the self time of
the operations under ``ks.gram_fold`` (the streamed fold), ``ks.block_gram``
(the block tier's epoch-1 Gramians), ``ks.gram_corr_fold`` (the resident
solver's Gramian/correlation kernel) and ``ks.sparse_densify`` +
``ks.sparse_gram_acc`` (the sparse fold) — each apart in the note — from the
program's own account of the window's profile (``device_account.py``). On a
mesh the note also gives ``ks.gram_psum``, the fold's one all-reduce round, a
device plane apart (the skew between the devices; it is ``allreduce_ms``'s
time and is not in this sum)."""

from benchmarks.layer_metrics import device_account

SCOPES = ("ks.gram_fold", "ks.block_gram", "ks.gram_corr_fold", "ks.sparse_densify",
          "ks.sparse_gram_acc")
BESIDE = ("ks.gram_psum",)


def read(ctx):
    return device_account.scopes_ms(ctx, "gram_device_ms", SCOPES, beside=BESIDE)

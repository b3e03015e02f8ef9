"""The accumulating Gramian kernels' share of their roofline: the least time
the chip could take to fold the rows the window folded
(``arith_sparse.gram_fold_cost``: syrk + correlation, slabs, COO and
Gramian traffic — the same count whatever implements the fold) over the
summed self time of the trace's ``gram_corr_sym_acc`` / ``gram_sym_acc``
calls. Where no such call ran — the fold took XLA's ``dot`` — there is
nothing to read, and a note says so."""

from benchmarks import arith, arith_sparse

KERNELS = ("gram_corr_sym_acc", "gram_sym_acc")
CHUNK_ROWS = 65536  # SparseLBFGSwithL2's gram_chunk_rows


def kernel_seconds(trace):
    """Self time of the accumulate kernels' calls (``<kernel>.<n>``)."""
    return sum(s for name, s in trace["op_seconds"].items()
               if name.split(".")[0] in KERNELS)


def read(ctx):
    trace, config, window = ctx["trace"], ctx["config"], ctx["window"]
    if trace is None or not window["fits"]:
        return None
    kernel_s = kernel_seconds(trace)
    if kernel_s <= 0:
        ctx["notes"].append(f"gram_acc_roofline: no {' / '.join(KERNELS)} call in the "
                            "trace (the fold took the XLA path): nothing to read")
        return None
    flops, nbytes = arith_sparse.gram_fold_cost(
        window["fits"] * window["rows"], config["num_features"], config["lanes"],
        config["num_targets"], min(CHUNK_ROWS, window["rows"]))
    least_s, bound = arith.least_seconds(flops, nbytes, arith.peaks(ctx["device_kind"]))
    ctx["notes"].append(
        f"gram_acc: {kernel_s:.4f} s on the device for {window['fits']} fits; "
        f"least {least_s:.4f} s, bound by {bound}")
    return 100.0 * least_s / kernel_s

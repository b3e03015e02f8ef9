"""Share of the device's busy time OUTSIDE the accumulating Gramian kernels
(``gram_corr_sym_acc`` / ``gram_sym_acc``): the densify scatter, slicing and
layout copies, the L-BFGS loop — by subtraction, so it names no other
operation. 100 where no such kernel ran."""

from benchmarks.layer_metrics import gram_acc_roofline


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    busy_s = sum(trace["op_seconds"].values())  # self times: nested ops counted once
    if busy_s <= 0:
        return None
    return 100.0 * (1.0 - gram_acc_roofline.kernel_seconds(trace) / busy_s)

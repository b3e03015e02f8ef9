"""The sparse L-BFGS fit as the Amazon cell drives it (PR 28): chunks sliced
and laned inside the fold program instead of copied, hyperparameters as
operands of the compiled solve, the iteration count on the fitted mapper,
and the spans, attributes and counters the fit leaves under a tracer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning import lbfgs
from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2


def rows(n=700, d=96, w=5, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([np.sort(rng.choice(d, w, replace=False)) for _ in range(n)]).astype(np.int32)
    val = rng.normal(size=(n, w)).astype(np.float32)
    Y = rng.normal(size=(n, 2)).astype(np.float32)
    return Dataset({"indices": jnp.asarray(idx), "values": jnp.asarray(val)}, n=n), Dataset.of(jnp.asarray(Y))


def weights(model):
    return np.concatenate([np.asarray(model.x), np.asarray(model.b_opt)[None]])


@pytest.mark.parametrize("chunk_rows", [700, 256, 100])
def test_chunks_sliced_inside_the_fold_match_the_gather_engine(chunk_rows):
    """Whole, ragged (700 = 2 x 256 + 188: the last chunk starts at 444 and
    masks what chunk 1 folded) and many small chunks: one set of iterates."""
    data, labels = rows()
    how = dict(lam=1e-3, num_iterations=60, convergence_tol=1e-6, num_features=96)
    want = SparseLBFGSwithL2(**how).fit(data, labels)
    got = SparseLBFGSwithL2(solver="gram", gram_chunk_rows=chunk_rows, **how).fit(data, labels)
    np.testing.assert_allclose(weights(got), weights(want), rtol=0, atol=2e-5)
    assert 5 < got.lbfgs_iterations < 60 and abs(got.lbfgs_iterations - want.lbfgs_iterations) <= 2
    assert got.lbfgs_loss == pytest.approx(want.lbfgs_loss, rel=1e-5)


def test_padding_rows_past_n_fold_nothing():
    """Rows past the true n are masked dead inside the chunk source, whatever
    they hold (the fold's own sums agree to float32 rounding; the converged
    weights follow)."""
    data, labels = rows()
    pad = lambda a: jnp.concatenate([a, jnp.ones((68,) + a.shape[1:], a.dtype)])
    padded = Dataset({k: pad(v) for k, v in data.data.items()}, n=700)
    fit = lambda X, Y: weights(SparseLBFGSwithL2(
        lam=1e-3, num_iterations=60, convergence_tol=1e-6, num_features=96, solver="gram",
        gram_chunk_rows=256).fit(X, Y))
    np.testing.assert_allclose(fit(padded, Dataset(pad(labels.array), n=700)),
                               fit(data, labels), rtol=0, atol=2e-5)


def test_laned_row_chunks_are_equal_across_fits_and_lane_the_intercept():
    source = lbfgs._LanedRowChunks(4, 9, 6)
    assert source == lbfgs._LanedRowChunks(4, 9, 6) and hash(source) == hash(lbfgs._LanedRowChunks(4, 9, 6))
    idx = jnp.arange(12, dtype=jnp.int32).reshape(6, 2) % 9
    val = jnp.full((6, 2), 2.0)
    Y = jnp.arange(6.0)[:, None]
    i1, v1, y = source(jnp.int32(1), idx, val, Y)  # rows 2..5 re-sliced; 4 and 5 are chunk 1's
    assert i1.shape == (4, 3) and v1.shape == (4, 3)
    np.testing.assert_array_equal(np.asarray(i1[:2]), -1)  # chunk 0 folded rows 2 and 3
    np.testing.assert_array_equal(np.asarray(i1[2:, :2]), np.asarray(idx[4:]))
    np.testing.assert_array_equal(np.asarray(i1[2:, 2]), 9)  # the ones column's lane
    np.testing.assert_array_equal(np.asarray(y[:, 0]), [0, 0, 4, 5])


def test_a_ridge_sweep_reuses_one_compiled_solve():
    """lam, the iteration cap, the tolerance and n are operands: a new value
    of any of them is no new program."""
    data, labels = rows(n=512)
    fit = lambda lam, its: SparseLBFGSwithL2(
        lam=lam, num_iterations=its, convergence_tol=0.0, num_features=96,
        solver="gram").fit(data, labels)
    from keystone_tpu.utils.profiling import compile_ledger

    compile_ledger()  # its listeners are registered at the first call
    fit(1e-3, 5)

    with compile_ledger().measure() as seen:
        a, b = fit(1e-2, 5), fit(1e-4, 7)
    assert seen["programs_compiled"] == 0, seen
    assert a.lbfgs_iterations == 5 and b.lbfgs_iterations == 7
    assert not np.allclose(weights(a), weights(b))


def test_spans_attributes_and_counters_under_a_tracer_and_nothing_without():
    data, labels = rows(n=512)
    est = SparseLBFGSwithL2(lam=1e-3, num_iterations=6, convergence_tol=0.0, num_features=96,
                            solver="gram", gram_chunk_rows=128)
    assert not obs.enabled() and obs.span("solver.gram_fold") is obs.span("solver.lbfgs")
    obs.set_on_open("estimator.fit", engine="x")  # no tracer: nothing to set, nothing raised
    est.fit_datasets([data, labels])  # untraced: the hooks are the shared no-op
    with obs.tracing() as tracer:
        est.fit_datasets([data, labels])
        SparseLBFGSwithL2(lam=1e-3, num_iterations=6, convergence_tol=0.0,
                          num_features=96).fit_datasets([data, labels])
    names = [s["name"] for s in tracer.spans()]
    for name in ("solver.chunk_tiles", "solver.gram_fold", "solver.lbfgs", "solver.gather_lbfgs"):
        assert name in names, names
    gram, gather = tracer.spans("estimator.fit")
    assert gram["args"] == {"estimator": "SparseLBFGSwithL2", "engine": "gram", "compress": None,
                            "slab_dtype": "float32", "chunks": 4, "d_pad": 512, "pallas": False}
    assert gather["args"]["engine"] == "gather"
    drains = [s for s in tracer.spans("executor.drain") if s["args"].get("site") == "solver_loss"]
    assert len(drains) == 2  # the one wait of each fit, filed as a wait
    counters = [(e["name"], e["value"]) for e in tracer.events if e["type"] == "counter"]
    assert ("sparse.rows_folded", 512.0) in counters and ("sparse.nnz_folded", 512.0 * 6) in counters
    assert counters.count(("lbfgs.iterations", 6.0)) == 2


def test_set_on_open_reaches_the_innermost_span_of_that_name():
    with obs.tracing() as tracer:
        with obs.span("outer", a=1):
            with obs.span("estimator.fit"):
                with obs.span("inner"):
                    obs.set_on_open("estimator.fit", engine="gram")
                    obs.set_on_open("no.such.span", x=1)
    by_name = {s["name"]: s["args"] for s in tracer.spans()}
    assert by_name == {"outer": {"a": 1}, "estimator.fit": {"engine": "gram"}, "inner": {}}


def test_name_scopes_of_the_sparse_fold_are_in_the_lowered_program():
    source = lbfgs._LanedRowChunks(128, 96, 512)
    program = lbfgs._gram_streamed_program(source, 4, 97, 2, False, jnp.dtype(jnp.float32), False)
    data, labels = rows(n=512)
    text = program.lower((data.data["indices"], data.data["values"], labels.array),
                         lbfgs._solve_operands(1e-3, 5, 1e-4, 512)).as_text(debug_info=True)
    for scope in ("ks.sparse_densify", "ks.sparse_gram_acc", "ks.lbfgs_gram"):
        assert scope in text, scope

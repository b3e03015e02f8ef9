"""The sparse L-BFGS fit as the Amazon cell drives it (PR 28): chunks sliced
inside the fold program instead of copied, hyperparameters as operands of
the compiled solve, the iteration count on the fitted mapper, the spans,
attributes and counters the fit leaves under a tracer, the slab type that
follows the values' range (PR 29): rows and targets that bfloat16 holds
exactly fold through bfloat16 slabs to the same Gramian — and the intercept
as a border of the fold (PR 38): the ones column rides the targets, the
slab is the rows' own d columns wide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning import lbfgs
from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2
from keystone_tpu.ops.sparse import gram_pad_dim, gram_tile_pairs, sparse_gram_stream


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


def test_laned_row_chunks_are_equal_across_fits_and_hand_the_ones_column_to_the_targets():
    source = lbfgs._LanedRowChunks(4, 6)
    assert source == lbfgs._LanedRowChunks(4, 6) and hash(source) == hash(lbfgs._LanedRowChunks(4, 6))
    idx = jnp.arange(12, dtype=jnp.int32).reshape(6, 2) % 9
    val = jnp.full((6, 2), 2.0)
    Y = jnp.arange(6.0)[:, None]
    i1, v1, y = source(jnp.int32(1), idx, val, Y)  # rows 2..5 re-sliced; 4 and 5 are chunk 1's
    assert i1.shape == (4, 2) and v1.shape == (4, 2)  # no lane for the intercept
    np.testing.assert_array_equal(np.asarray(i1[:2]), -1)  # chunk 0 folded rows 2 and 3
    np.testing.assert_array_equal(np.asarray(i1[2:]), np.asarray(idx[4:]))
    assert y.shape == (4, 2) and y.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(y[:, 0]), [0, 0, 4, 5])
    np.testing.assert_array_equal(np.asarray(y[:, 1]), [0, 0, 1, 1])  # the ones column: the rows it folds


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
                            "slab_dtype": "float32", "slab_exact": False, "chunks": 4, "d_pad": 512,
                            "tile_pairs": 1, "intercept": "border",
                            "pallas": False, "densify": "contract"}  # normal values: the probe ran and refused
    assert gather["args"]["engine"] == "gather"
    sites = [s["args"].get("site") for s in tracer.spans("executor.drain")]
    assert sites.count("solver_loss") == 2  # the one wait of each fit, filed as a wait
    assert sites.count("slab_probe") == 1  # and the gram fit's read of the probe's verdict
    counters = [(e["name"], e["value"]) for e in tracer.events if e["type"] == "counter"]
    assert ("sparse.rows_folded", 512.0) in counters and ("sparse.nnz_folded", 512.0 * 5) in counters  # the lanes that exist
    assert counters.count(("lbfgs.iterations", 6.0)) == 2
    assert counters.count(("sparse.exact_bf16_fits", 0.0)) == 1


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
    source = lbfgs._LanedRowChunks(128, 512)
    program = lbfgs._gram_streamed_program(source, 4, 96, 2, False, jnp.dtype(jnp.float32), False, True)
    data, labels = rows(n=512)
    text = program.lower((data.data["indices"], data.data["values"], labels.array),
                         lbfgs._solve_operands(1e-3, 5, 1e-4, 512)).as_text(debug_info=True)
    for scope in ("ks.sparse_densify", "ks.sparse_gram_acc", "ks.lbfgs_gram"):
        assert scope in text, scope


# -- the slab type follows the values' range (PR 29) -------------------------

GRAM = dict(lam=1e-3, num_iterations=60, convergence_tol=1e-6, num_features=96, solver="gram",
            gram_chunk_rows=256)  # 700 rows = 2 x 256 + a ragged 188


def exact_rows(kind="binary", n=700, d=96, w=5, seed=0):
    """Rows as sparse text featurizers emit them — binary or count (1-3)
    term frequencies, float32 — with +-1 targets; row 3 repeats an id, so
    the densify scatter-ADDS two of its lanes into one slab entry."""
    data, _ = rows(n, d, w, seed)
    rng = np.random.default_rng(seed + 1)
    idx = np.array(data.data["indices"])
    idx[3, 1] = idx[3, 0]
    val = np.ones((n, w), np.float32) if kind == "binary" else rng.integers(1, 4, (n, w)).astype(np.float32)
    Y = np.where(rng.normal(size=(n, 2)) > 0, 1.0, -1.0).astype(np.float32)
    return Dataset({"indices": jnp.asarray(idx), "values": jnp.asarray(val)}, n=n), Dataset.of(jnp.asarray(Y))


def with_values(data, fill):
    """``data`` with row 5's values replaced by ``fill`` (one per lane)."""
    val = np.array(data.data["values"])
    val[5] = fill
    return Dataset({"indices": data.data["indices"], "values": jnp.asarray(val)}, n=data.n)


def folded(data, labels, val_dtype, d=96, chunk_rows=256):
    """(G, AtY) of the fit's own fold, its border laid around the (d, d)
    block: the normal equations of [X, 1] on the common (d + 1) block."""
    source = lbfgs._LanedRowChunks(chunk_rows, data.n)
    fold = jax.jit(lambda i, v, y: sparse_gram_stream(
        lambda cid: source(cid, i, v, y), -(-data.n // chunk_rows), d, 2,
        val_dtype=val_dtype, pipeline=False, border=True))
    G, AtY, _, ysum = map(np.asarray, fold(data.data["indices"], data.data["values"], labels.array))
    s = AtY[:d, 2]
    return (np.block([[G[:d, :d], s[:, None]], [s[None], np.float32(data.n)]]),
            np.concatenate([AtY[:d, :2], ysum[None]]))


def traced_fit(data, labels, **how):
    with obs.tracing() as tracer:
        model = SparseLBFGSwithL2(**{**GRAM, **how}).fit_datasets([data, labels])
    (span,) = tracer.spans("estimator.fit")
    return model, span["args"], tracer


@pytest.mark.parametrize("kind", ["binary", "counts"])
def test_rows_exact_in_bfloat16_fold_to_the_same_gramian_in_one_pass(kind):
    data, labels = exact_rows(kind)
    G16, AtY16 = folded(data, labels, jnp.bfloat16)
    G32, AtY32 = folded(data, labels, jnp.float32)
    np.testing.assert_array_equal(G16, G32)
    np.testing.assert_array_equal(AtY16, AtY32)
    assert G32[:96, :96].max() > 2  # the repeated id and the counts are in it


@pytest.mark.parametrize("kind", ["binary", "counts"])
def test_no_flag_and_exact_rows_fit_through_bfloat16_slabs(kind):
    data, labels = exact_rows(kind)
    got, attrs, tracer = traced_fit(data, labels)
    want, flagged, _ = traced_fit(data, labels, gram_dtype="f32")
    assert (attrs["slab_dtype"], attrs["slab_exact"], attrs["d_pad"]) == ("bfloat16", True, 1024)
    # (c) the explicit flag wins, and no probe runs for it
    assert (flagged["slab_dtype"], flagged["d_pad"]) == ("float32", 512) and "slab_exact" not in flagged
    np.testing.assert_allclose(weights(got), weights(want), rtol=0, atol=1e-6)
    assert got.lbfgs_iterations == want.lbfgs_iterations
    counters = [(e["name"], e["value"]) for e in tracer.events if e["type"] == "counter"]
    assert ("sparse.exact_bf16_fits", 1.0) in counters
    assert [s["args"]["site"] for s in tracer.spans("executor.drain")] == ["slab_probe", "solver_loss"]


REFUSED = {
    "normal_values": lambda data, labels: (rows()[0], labels),
    "a_value_of_a_tenth": lambda data, labels: (with_values(data, 0.1), labels),
    "a_row_summing_to_257": lambda data, labels: (with_values(data, [253, 1, 1, 1, 1]), labels),
    # drawn when the intercept's 1 shared the slab (256 + 1 = 257); it rides the targets now, the line stays
    "a_row_summing_to_256": lambda data, labels: (with_values(data, [-252, 1, 1, 1, 1]), labels),
    "a_nan": lambda data, labels: (with_values(data, np.nan), labels),
    "targets_of_a_tenth": lambda data, labels: (data, Dataset.of(0.1 * labels.array)),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_probe_refuses_what_bfloat16_would_round(case):
    """float32 slabs, and weights equal to the bit with the flagged float32
    fit's — the parent's path, the same compiled program."""
    data, labels = REFUSED[case](*exact_rows())
    got, attrs, _ = traced_fit(data, labels, num_iterations=8)
    want, _, _ = traced_fit(data, labels, num_iterations=8, gram_dtype="f32")
    assert (attrs["slab_dtype"], attrs["slab_exact"], attrs["d_pad"]) == ("float32", False, 512)
    np.testing.assert_array_equal(weights(got), weights(want))


@pytest.mark.parametrize("fill,exact", [([251, 1, 1, 1, 1], True), ([-251, 1, -1, 1, 1], True),
                                         ([-0.0, 0, 0, 0, 0], True), ([252, 1, 1, 1, 1], False),
                                         ([np.inf, 0, 0, 0, 0], False), ([1e30, 0, 0, 0, 0], False),
                                         ([0.5, 0.5, 0, 0, 0], False)])
def test_the_probe_draws_its_line_at_a_row_sum_of_255(fill, exact):
    data, labels = exact_rows()
    verdict = lbfgs._slabs_exact_in_bf16(with_values(data, fill).data["values"], labels.array)
    assert bool(verdict) is exact


def test_a_wider_rule_would_be_caught_a_repeated_id_summing_to_257_rounds_in_bfloat16():
    """Every VALUE of this row is exact in bfloat16 (128, 129 and ones);
    their sum in one slab entry is not: a probe that looked at the values
    alone would change the Gramian."""
    data, labels = exact_rows()
    idx = np.array(data.data["indices"])
    idx[5, 1] = idx[5, 0]
    data = with_values(Dataset({"indices": jnp.asarray(idx), "values": data.data["values"]}, n=data.n),
                       [128, 129, 1, 1, 1])
    assert not bool(lbfgs._slabs_exact_in_bf16(data.data["values"], labels.array))
    col = idx[5, 0]
    assert folded(data, labels, jnp.float32)[0][col, col] - folded(data, labels, jnp.bfloat16)[0][col, col] \
        == 257.0 ** 2 - 256.0 ** 2


def test_values_under_a_trace_cannot_be_observed_and_keep_float32_slabs(monkeypatch):
    data, labels = exact_rows()
    seen = {}

    def no_fold(*args, val_dtype, info, **kwargs):
        seen.update(val_dtype=val_dtype)
        info.update(iterations=0)
        return jnp.zeros((97, 2)), 0.0

    monkeypatch.setattr(lbfgs, "run_lbfgs_gram_streamed", no_fold)
    monkeypatch.setattr(lbfgs, "_report_solve", lambda *a, **k: None)  # its read cannot be traced either
    est = SparseLBFGSwithL2(**GRAM)
    jax.make_jaxpr(lambda v: est._fit_gram(data.data["indices"], v, labels.array, 96, 700, {}))(
        data.data["values"])
    assert seen["val_dtype"] == jnp.float32


def test_the_selectors_gram_choice_folds_binary_rows_in_bfloat16_and_counts_the_fits():
    """Through the public entry under the sparse cell's rehearsal budget
    (16 MB, one machine): no engine and no slab type named anywhere."""
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
    from keystone_tpu.workflow import PipelineEnv

    data, labels = exact_rows(n=4096, d=1024, w=12)
    with obs.tracing() as tracer:
        for lam in (1e-3, 1e-4):
            PipelineEnv.get_or_create().reset()
            LeastSquaresEstimator(lam=lam, hbm_bytes=16e6, num_machines=1).with_data(data, labels).fit()
    fits = [s["args"] for s in tracer.spans("estimator.fit")]
    assert [(a["engine"], a["slab_dtype"], a["slab_exact"]) for a in fits] == [("gram", "bfloat16", True)] * 2
    counted = [e["value"] for e in tracer.events
               if e["type"] == "counter" and e["name"] == "sparse.exact_bf16_fits"]
    assert sum(counted) == 2.0


# -- the intercept is a border of the fold (PR 38) ----------------------------


@pytest.mark.parametrize("gram_dtype,tile", [("f32", 512), (None, 1024)])
@pytest.mark.parametrize("d", [96, 512, 513, 1024])
def test_the_fit_says_its_intercept_is_a_border_and_pads_its_own_width(d, gram_dtype, tile):
    """``d_pad`` is ``gram_pad_dim(d)``, not of d + 1: a width that is a
    multiple of the tile pads to itself, and the pairs a chunk call folds
    are the pairs of that width."""
    data, labels = exact_rows(n=300, d=d)
    _, attrs, _ = traced_fit(data, labels, num_features=d, num_iterations=2, gram_dtype=gram_dtype)
    val_dtype = jnp.float32 if gram_dtype == "f32" else jnp.bfloat16
    tiles = -(-d // tile)
    assert attrs["intercept"] == "border"
    assert attrs["d_pad"] == gram_pad_dim(d, val_dtype) == tiles * tile
    assert attrs["tile_pairs"] == gram_tile_pairs(d, val_dtype) == tiles * (tiles + 1) // 2
    assert attrs["d_pad"] <= gram_pad_dim(d + 1, val_dtype)  # never worse than the laned column


def test_the_intercept_is_regularised_with_the_rest():
    """Targets of mean 3: a small ridge learns the mean as the intercept, a
    large one drives it to 0 with the weights (LBFGS.scala:208-281 — the
    ones column is a feature like another), as the gather engine's does."""
    data, labels = exact_rows()
    labels = Dataset.of(labels.array + 3.0)
    fit = lambda lam, **how: SparseLBFGSwithL2(**{**GRAM, **how, "lam": lam}).fit(data, labels)
    free, held = fit(1e-6), fit(1e4)
    assert np.all(np.abs(np.asarray(free.b_opt)) > 1.0)
    assert np.all(np.abs(weights(held)) < 1e-3) and np.all(np.asarray(held.b_opt) > 0)
    gather = fit(1e4, solver="gather")
    np.testing.assert_allclose(weights(held), weights(gather), rtol=1e-4, atol=1e-9)


def test_a_stray_id_at_d_is_dropped_not_added_to_the_intercept():
    """An id outside [0, d) adds to no column: the slab has no intercept
    column for it to land on."""
    data, labels = exact_rows()
    idx = np.array(data.data["indices"])
    stray = Dataset({"indices": jnp.asarray(np.where(idx == idx[7, 0], 96, idx)),
                     "values": data.data["values"]}, n=data.n)
    G, _ = folded(stray, labels, jnp.bfloat16)
    assert G[96, 96] == 700.0 and G[idx[7, 0]].sum() == 0.0
    assert G[:96, 96].sum() == 700.0 * 5 - (idx == idx[7, 0]).sum()  # the column sums count every lane kept

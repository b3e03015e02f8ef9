"""The sparse fold's slab is WRITTEN by a one-hot contraction
(``ops/sparse_densify.py``) where it used to be scatter-added into: both
forms — the XLA contraction and the Pallas kernel — against the old scatter,
kept here as the oracle, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops import sparse_densify
from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2
from keystone_tpu.ops.sparse import gram_pad_dim
from keystone_tpu.ops.sparse_densify import densify_form, densify_rows

ROWS, LANES = 16, 12


def scatter_oracle(indices, values, d, d_pad, val_dtype):
    """``sparse_gram_fold.densify_chunk`` as it was before the contraction."""
    c, w = indices.shape
    mask = (indices >= 0) & (indices < d)
    safe = jnp.where(mask, indices, 0).astype(jnp.int32)
    vals = jnp.where(mask, values, 0).astype(val_dtype)
    rows = jnp.broadcast_to(jnp.arange(c)[:, None], (c, w))
    return jnp.zeros((c, d_pad), val_dtype).at[rows, safe].add(vals)


def distinct_ids(rng, d):
    return np.sort(np.stack([rng.choice(d, LANES, replace=False) for _ in range(ROWS)]),
                   axis=1).astype(np.int32)


def case_rows(case, d, val_dtype):
    """(indices, values) of one case: ``d`` live columns, the slab's type."""
    rng = np.random.default_rng(31)
    idx = distinct_ids(rng, d)
    val = np.ones((ROWS, LANES), np.float32)
    if case == "repeated":  # the values add: 128 + 127 is exact in bfloat16 too
        idx[:, 1] = idx[:, 0]
        val[:, 0], val[:, 1] = 128.0, 127.0
    elif case == "dead_lanes":
        idx[::2, LANES // 2:] = -1
        idx[5, :] = -1  # a whole dead row
    elif case == "past_d":  # an id at or past d is masked, not wrapped or clipped
        idx[:, -1] = d + rng.integers(0, 700, ROWS)
    elif case == "compressed":  # int16 ids, bfloat16 values, as `compress` hands them
        val = jnp.asarray(rng.normal(size=val.shape), jnp.bfloat16)
        return jnp.asarray(idx, jnp.int16), val
    elif case == "real":
        val = rng.normal(size=val.shape).astype(np.float32)
        if val_dtype == jnp.bfloat16:  # a bfloat16 slab of real values is `gram_dtype="bf16"`'s
            val = np.asarray(jnp.asarray(val, jnp.bfloat16).astype(jnp.float32))
    else:
        assert case == "distinct", case
    return jnp.asarray(idx), jnp.asarray(val)


# the cases of one slab share a compiled program (the interpreted kernel unrolls 136 tiles)
jitted_densify = jax.jit(densify_rows, static_argnames=("d", "d_pad", "val_dtype", "use_pallas", "interpret"))
CASES = ("distinct", "repeated", "dead_lanes", "past_d", "compressed", "real")


@pytest.mark.parametrize("form", ["contract", "kernel"])
@pytest.mark.parametrize("d_pad", [512, 1024, 17408])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("val_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_written_slab_equals_the_scattered_one(val_dtype, case, d_pad, form):
    d = d_pad - 7  # a ragged edge: the last columns are padding
    idx, val = case_rows(case, d, val_dtype)
    want = scatter_oracle(idx, val, d, d_pad, val_dtype)
    kernel = form == "kernel"
    assert densify_form(kernel, ROWS, d_pad, val_dtype) == form
    got = jitted_densify(idx, val, d, d_pad, val_dtype, use_pallas=kernel, interpret=True)
    assert got.dtype == want.dtype and got.shape == (ROWS, d_pad)
    assert jnp.array_equal(got, want)
    assert float(jnp.abs(want.astype(jnp.float32)).sum()) > 0  # the oracle is not empty


def test_a_ragged_chunk_and_a_wide_row_take_the_xla_form_and_agree():
    """No whole number of tiles (33 rows, 1,030 rows), and more lanes than
    one lane tile (130: the kernel pads them to 256)."""
    rng = np.random.default_rng(7)
    d, d_pad = 1000, 1024
    for rows, lanes in ((33, 5), (1030, 3), (16, 130)):
        idx = jnp.asarray(rng.integers(-1, d + 20, (rows, lanes)), jnp.int32)
        val = jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32)
        want = scatter_oracle(idx, val, d, d_pad, jnp.float32)
        if rows % 8:
            assert densify_form(True, rows, d_pad, jnp.float32) == "contract"
        got = densify_rows(idx, val, d, d_pad, jnp.float32, use_pallas=True, interpret=True)
        # repeated ids add in another order than the scatter's: to rounding, not to the bit
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_the_kernel_is_left_where_it_does_not_apply():
    assert sparse_densify.kernel_block_rows(65536, 17408, jnp.bfloat16) == 128
    assert sparse_densify.kernel_block_rows(65536, 16896, jnp.float32) == 64
    assert sparse_densify.kernel_block_rows(24, 512, jnp.bfloat16) is None  # 24 rows: no 16-row tiles
    assert sparse_densify.kernel_block_rows(24, 512, jnp.float32) == 8
    assert sparse_densify.kernel_block_rows(64, 65536, jnp.float32) is None  # too wide to unroll
    assert sparse_densify.kernel_block_rows(64, 512, jnp.float64) is None
    assert densify_form(False, 65536, 17408, jnp.bfloat16) == "contract"
    with pytest.raises(ValueError, match="no kernel"):
        sparse_densify.contract_kernel(jnp.zeros((24, 4), jnp.int32), jnp.zeros((24, 4), jnp.bfloat16), 512)
    with pytest.raises(AssertionError):
        densify_rows(jnp.zeros((8, 4), jnp.int32), jnp.zeros((8, 4)), 100, 200, jnp.float32)
    for d in (1, 511, 512, 513, 16385):  # the ids split at 128 with no remainder: assert it
        assert gram_pad_dim(d, jnp.float32) % 512 == 0 and gram_pad_dim(d, jnp.bfloat16) % 1024 == 0
    # the scratch's stride is an odd number of 8-sublane tiles, never under the row's lane tiles
    assert [sparse_densify._scratch_height(t) for t in (1, 8, 9, 64, 120, 128, 136)] == [8, 8, 24, 72, 120, 136, 136]


def sparse_fit(chunk_rows=128):
    rng = np.random.default_rng(3)
    n, d = 256, 40
    idx = np.sort(np.stack([rng.choice(d, 6, replace=False) for _ in range(n)]), axis=1).astype(np.int32)
    X = Dataset({"indices": jnp.asarray(idx), "values": jnp.ones(idx.shape, jnp.float32)}, n=n)
    Y = Dataset.of(jnp.asarray(np.sign(rng.normal(size=(n, 2))), jnp.float32))  # exact in bfloat16
    with obs.tracing() as tracer:
        model = SparseLBFGSwithL2(lam=1e-2, num_iterations=6, num_features=d, solver="gram",
                                  gram_chunk_rows=chunk_rows).fit_datasets([X, Y])
    (span,) = tracer.spans("estimator.fit")
    return np.concatenate([np.asarray(model.x), np.asarray(model.b_opt)[None]]), span["args"]


def test_a_fit_says_how_it_densified_and_both_forms_fit_alike(monkeypatch):
    W, attrs = sparse_fit()
    assert (attrs["pallas"], attrs["densify"], attrs["slab_dtype"]) == (False, "contract", "bfloat16")
    monkeypatch.setenv("KEYSTONE_PALLAS", "1")  # the kernels, interpreted off the chip
    Wk, attrs = sparse_fit()
    assert (attrs["pallas"], attrs["densify"], attrs["slab_dtype"]) == (True, "kernel", "bfloat16")
    np.testing.assert_allclose(Wk, W, rtol=0, atol=1e-5)  # the same slab; the fold kernel rounds apart
    _, attrs = sparse_fit(chunk_rows=72)  # 72 rows are no whole number of 16-row tiles
    assert (attrs["pallas"], attrs["densify"]) == (True, "contract")


@pytest.mark.parametrize("val_dtype,d_pad,lanes", [
    (jnp.bfloat16, 17408, 83), (jnp.float32, 16896, 83),  # a laned ones column: d + 1 = 16,385
    (jnp.bfloat16, 16384, 82), (jnp.float32, 16384, 82),  # the cell's since PR 38: the scratch strides by 136, not 128
], ids=["bfloat16", "float32", "bfloat16-16384", "float32-16384"])
def test_mosaic_takes_the_kernel_at_the_amazon_cell_chunk_shape(one_chip, compile_for_chip, val_dtype, d_pad, lanes):
    """Compiled here for the described chip (nothing runs): the interpreter
    cannot say whether Mosaic accepts the strided reads and the row loads."""
    rows = jax.ShapeDtypeStruct((65536, lanes), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((65536, lanes), jnp.float32, sharding=one_chip)
    compiled = compile_for_chip(
        lambda i, v: densify_rows(i, v, 16302 + lanes, d_pad, val_dtype, use_pallas=True, interpret=False),
        rows, vals)
    assert "tpu_custom_call" in compiled.as_text() and "sparse_densify" in compiled.as_text()
    # the slab and nothing of its size beside it (the scatter held a second one)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * 65536 * d_pad


def test_the_amazon_cell_chunk_loop_keeps_its_gramian_in_its_carry(one_chip, compile_for_chip, monkeypatch):
    """PR 38, compiled here for the described chip (nothing runs): the
    estimator's own fold + solve program at the cell's shapes — the slab is
    the rows' 16,384 columns wide (no column for the intercept), and the
    chunk loop's body, where the accumulate kernel updates its aliased
    Gramian, holds no copy of it (the parent copied 1.21 GB in front of
    each of the 64 calls). What the copy cost is the chip's to say."""
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.learning import lbfgs

    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)  # Mosaic, not the interpreter
    n, w, d, k, c = 4194304, 82, 16384, 2, 65536
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    program = lbfgs._gram_streamed_program.__wrapped__(  # no cache entry: the kernels are patched
        lbfgs._LanedRowChunks(c, n), n // c, d, k, True, jnp.dtype(jnp.bfloat16), False, True)
    compiled = compile_for_chip(
        program, (shape((n, w), jnp.int32), shape((n, w), jnp.float32), shape((n, k), jnp.float32)),
        (shape((), jnp.float32), shape((), jnp.int32), shape((), jnp.float32), shape((), jnp.float32)))
    text = compiled.as_text()
    assert "bf16[65536,16384]" in text and "[65536,17408]" not in text
    blocks = text.split("\n\n")  # one computation a block
    (loop_body,) = [b for b in blocks if 'custom_call_target="tpu_custom_call"' in b and "gram_corr_sym_acc" in b]
    gramian_copies = [line for line in loop_body.splitlines() if "= f32[16384,16384]" in line and " copy(" in line]
    assert gramian_copies == []
    # the slab, the Gramian and its mirror: 5.1 GB where 17,408 columns took 5.5
    assert compiled.memory_analysis().temp_size_in_bytes < 5.2e9

"""Statistical featurization nodes (reference: nodes/stats/).

All dense nodes operate whole-batch on (n, d) arrays so XLA fuses the
elementwise work into surrounding GEMMs; per-item ``apply`` handles single
datums. Randomized nodes take explicit integer seeds (JAX PRNG keys derive
from them), replacing the reference's implicit global RNG draws.
"""

from __future__ import annotations

import functools
import weakref
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.ops.util import FunctionNode
from keystone_tpu.workflow import Estimator, Transformer


# ---------------------------------------------------------------------------
# StandardScaler
# ---------------------------------------------------------------------------


# An eagerly dispatched operation is a program of its own and takes no name
# scope from its caller, so a device profile cannot say what phase it served.
# The scaler's operations are the small programs below, under the scope
# ``ks.center``; the row count is static, so no scalar crosses to the device.


@functools.partial(jax.jit, static_argnames=("n",))
def _column_means(X, n: int):
    with jax.named_scope("ks.center"):
        return jnp.sum(X, axis=0) / n


@jax.jit
def _subtract_mean(X, mean):
    with jax.named_scope("ks.center"):
        return X - mean


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _column_moments(X, n: int, eps: float):
    """Column means and standard deviations over ``n`` rows, padding rows
    (zero) aside: sum((x - mean)^2) over real rows = sum(x^2) - n*mean^2,
    over n - 1; a deviation that is not a number or under ``eps`` reads 1."""
    with jax.named_scope("ks.center"):
        mean = jnp.sum(X, axis=0) / n
        var = (jnp.sum(X * X, axis=0) - n * mean * mean) / max(n - 1, 1)
        std = jnp.sqrt(jnp.maximum(var, 0.0))
        return mean, jnp.where(
            jnp.isnan(std) | jnp.isinf(std) | (jnp.abs(std) < eps), 1.0, std)


@jax.jit
def _standardize(X, mean, std):
    with jax.named_scope("ks.center"):
        return (X - mean) / std


class StandardScalerModel(Transformer):
    """Subtract column means (and optionally divide by stds)
    (reference: nodes/stats/StandardScaler.scala:16-32)."""

    def __init__(self, mean, std=None):
        self.mean = jnp.asarray(mean)
        self.std = None if std is None else jnp.asarray(std)

    def apply(self, x):
        # Traced inside another program (a fused chain) the subtraction is
        # inlined there and reads as ``ks.center`` within that phase.
        if self.std is not None:
            return _standardize(jnp.asarray(x), self.mean, self.std)
        return _subtract_mean(jnp.asarray(x), self.mean)

    def batch_apply(self, data: Dataset) -> Dataset:
        return data.map_batch(self.apply)


class StandardScaler(Estimator):
    """Column mean/std via a single sharded pass — sums compile to per-shard
    reductions + all-reduce, replacing treeAggregate(MultivariateOnlineSummarizer)
    (reference: nodes/stats/StandardScaler.scala:37-60)."""

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def fit(self, data: Dataset) -> StandardScalerModel:
        # Padding rows are zero: sums are exact; divide by the true count.
        X = jnp.asarray(data.array)
        if not self.normalize_std_dev:
            return StandardScalerModel(_column_means(X, int(data.n)))
        return StandardScalerModel(*_column_moments(X, int(data.n), float(self.eps)))


# ---------------------------------------------------------------------------
# Random features
# ---------------------------------------------------------------------------


class CosineRandomFeaturesModel(Transformer):
    """x -> cos(x Wᵀ + b): Rahimi-Recht random features
    (reference: nodes/stats/CosineRandomFeatures.scala:19-45).

    The (num_out, num_in) projection is a single batch GEMM — the per-partition
    broadcast-W GEMM of the reference becomes one MXU matmul over the sharded
    batch with W replicated.
    """

    def __init__(self, W, b):
        self.W = jnp.asarray(W)
        self.b = jnp.asarray(b)
        if self.b.shape[0] != self.W.shape[0]:
            raise ValueError("# of rows in W and size of b should match")
        # (mesh, wrapped fn) — a fresh shard_map-of-lambda per call would
        # defeat jit's trace cache and recompile every batch.
        self._sharded_fused = None

    def device_operands(self):
        """Stage-fusion contract (workflow/fusion.py): row-local cos-GEMM
        whose bank rides into a fused program as arguments, so every bank
        of one shape runs one compiled program (a λ-sweep draws or
        rebuilds a ~7 MB bank per branch per fit).

        The XLA form — inside a fused program XLA fuses the cosine into
        the matmul epilogue; the standalone batch path below still
        prefers the Pallas kernel, and fused STREAMED fits recover it via
        the bank extraction (streaming_ls._extract_bank)."""
        return (), (self.W, self.b)

    @staticmethod
    def device_apply(static_key, params, X):
        W, b = params
        return jnp.cos(X @ W.T + b)

    def batch_apply(self, data: Dataset) -> Dataset:
        import jax.tree_util as jtu
        from jax.sharding import PartitionSpec as P

        from keystone_tpu.ops import pallas_ops
        from keystone_tpu.parallel import mesh as mesh_lib

        mesh = data.mesh
        multi = mesh is not None and mesh_lib.axis_size(mesh, mesh_lib.DATA_AXIS) > 1
        if pallas_ops.pallas_enabled() and multi:
            # Row-sharded input: run the fused kernel per shard under
            # shard_map (W/b replicate into the body; no collective needed —
            # the featurization is embarrassingly row-parallel). The wrapper
            # is cached per mesh so repeat batches reuse the compiled program.
            if self._sharded_fused is None or self._sharded_fused[0] is not mesh:
                W, b = self.W, self.b
                self._sharded_fused = (
                    mesh,
                    mesh_lib.shard_map(
                        lambda X: pallas_ops.cosine_features(X, W, b),
                        mesh=mesh,
                        in_specs=P(mesh_lib.DATA_AXIS),
                        out_specs=P(mesh_lib.DATA_AXIS),
                        check_vma=False,  # pallas outputs carry no vma info
                    ),
                )
            return data.map_batch(self._sharded_fused[1])._rezero_padding()
        leaves = jtu.tree_leaves(data.data)
        # A batch under one row tile of the kernel (the optimizer's few
        # sample rows) gains nothing from it and would lower it anew for
        # this instance's closure — 59 ms a branch, in every fit of a
        # sweep (PERF.md section 5): XLA's shared eager programs take it.
        small = bool(leaves) and leaves[0].shape[0] < pallas_ops._TILE_M
        if not small and pallas_ops.pallas_direct_ok(*leaves):
            # Fused Pallas matmul+cos: the pre-activation never hits HBM.
            return data.map_batch(
                lambda X: pallas_ops.cosine_features(X, self.W, self.b)
            )._rezero_padding()
        return data.map_batch(self.device_fn())._rezero_padding()


# Drawn banks by what they were drawn from. A sweep builds a new pipeline a
# fit and every one draws the same bank from the same seed; arrays are
# immutable, so equal draws can be ONE set of device buffers. Weak-valued:
# an entry lives exactly as long as some pipeline holds its arrays — no
# bound, no eviction order, nothing pinned after a sweep ends.
_DRAWN_BANKS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def cosine_draw_key(
    num_input_features: int,
    num_output_features: int,
    gamma: float,
    seed: int,
    cauchy: bool = False,
) -> tuple:
    """What a :func:`CosineRandomFeatures` draw is a function of: its
    arguments, the dtype the draw comes out in (it follows
    ``jax_enable_x64``) and the device a draw would land on."""
    return (
        "cosine", int(num_input_features), int(num_output_features),
        float(gamma), int(seed), "cauchy" if cauchy else "gaussian",
        jnp.result_type(float).name, jax.config.jax_default_device,
    )


def shared_bank(key, draw: Callable[[], tuple]):
    """``(W, b, shared)``: the bank an earlier ``shared_bank(key, ...)``
    made, where something still holds both of its arrays (``shared``
    True), else ``draw()``'s, kept for the next caller for as long as this
    one keeps them."""
    W, b = _DRAWN_BANKS.get((key, "W")), _DRAWN_BANKS.get((key, "b"))
    if W is not None and b is not None:
        return W, b, True
    W, b = draw()
    _DRAWN_BANKS[(key, "W")], _DRAWN_BANKS[(key, "b")] = W, b
    return W, b, False


def CosineRandomFeatures(
    num_input_features: int,
    num_output_features: int,
    gamma: float,
    seed: int = 0,
    cauchy: bool = False,
) -> CosineRandomFeaturesModel:
    """Draw W ~ gaussian(·γ) (or cauchy(·γ)), b ~ U[0, 2π]
    (reference: CosineRandomFeatures.scala:50-61). Equal draws share their
    device arrays (:func:`shared_bank`); the model's ``shared_draw`` says
    whether this one took an earlier draw's."""

    def draw():
        kw, kb = jax.random.split(jax.random.key(seed))
        shape = (num_output_features, num_input_features)
        sample = jax.random.cauchy if cauchy else jax.random.normal
        return (sample(kw, shape) * gamma,
                jax.random.uniform(kb, (num_output_features,)) * (2 * jnp.pi))

    W, b, shared = shared_bank(
        cosine_draw_key(num_input_features, num_output_features, gamma,
                        seed, cauchy),
        draw,
    )
    model = CosineRandomFeaturesModel(W, b)
    model.shared_draw = shared
    return model


def padded_pow2(n: int) -> int:
    """The FFT padding width every padded-FFT path shares: the next power
    of two ≥ n (minimum 2, so a width-1 input still has a non-trivial
    transform)."""
    return 1 << max(int(n - 1).bit_length(), 1)


def rfft_real_half(x, p: int, axis: int = -1):
    """Re(rfft(x))[bins 0..p/2) along ``axis`` — the shared epilogue of
    every padded-FFT path (``PaddedFFT`` single/batch, the packed
    gather's odd branch, and the SRHT sketch fold): the input is real
    and already padded to ``p``, and only the real parts of the first
    ``p // 2`` bins survive, so ``rfft`` computes the same DFT bins with
    half the butterfly work and a (p/2+1)-wide complex intermediate
    instead of p-wide. One implementation, so the bin convention (DC
    included, Nyquist dropped) cannot drift between callers — the
    batched-vs-single parity test in tests/test_learning_nodes.py pins
    it."""
    out = jnp.real(jnp.fft.rfft(x, axis=axis))
    return jax.lax.slice_in_dim(out, 0, p // 2, axis=axis)


def srht_chunk_sketch(dense_rows, signs, sample_bins, scale):
    """One block-SRHT fold step (Drineas et al., "Faster Least Squares
    Approximation"): sign-flip the chunk's rows, zero-pad the ROW axis to
    a power of two, mix with the real-FFT butterfly, keep Re of the
    first p/2 bins (:func:`rfft_real_half` — its fourth caller), and
    gather the chunk's sampled bins.

    ``dense_rows (c, d)``, ``signs (c,)`` ±1, ``sample_bins (m_c,)`` in
    ``[0, p//2)``; returns ``scale · (m_c, d)``. Stacking every chunk's
    sampled bins gives the block-diagonal SRHT ``S A`` of the whole row
    stream — each chunk is sketched independently, so the transform
    streams chunk-by-chunk and composes with the prefetch/resident
    tiers (``ops/learning/sketch.py``)."""
    c = dense_rows.shape[0]
    p = padded_pow2(c)
    Z = dense_rows * signs[:, None]
    Zp = jnp.pad(Z, ((0, p - c), (0, 0)))
    H = rfft_real_half(Zp, p, axis=0)  # (p//2, d)
    return scale * jnp.take(H, sample_bins, axis=0)


@dataclass(frozen=True)
class PaddedFFT(Transformer):
    """Zero-pad to the next power of two, FFT, keep the real parts of the first
    half (reference: nodes/stats/PaddedFFT.scala:13-21).

    The input is real, and only Re(bins 0..p/2) survive — the shared
    :func:`rfft_real_half` epilogue: at the MNIST bench geometry that
    halves both the FFT flops and the c64 round-trip bytes of the
    featurize phase (the HBM-bound piece of the row's roofline)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        p = padded_pow2(X.shape[-1])
        padded = jnp.pad(X, [(0, 0)] * (X.ndim - 1) + [(0, p - X.shape[-1])])
        return rfft_real_half(padded, p)


def packed_fft_gather_fn(branches, combiner):
    """Recognize the MnistRandomFFT gather shape — every branch
    [RandomSignNode → PaddedFFT → LinearRectifier] over one input, merged
    by a VectorCombiner — and return the packed-pair batch program in
    operand form, ``(static_key, params)`` for :func:`packed_fft_gather_apply`
    (the packing geometry and the rectifiers' settings in the key, the
    branches' sign vectors as params), or None when the shape doesn't
    match (the caller falls back to per-branch composition).

    Branch members may arrive wrapped in a FusedBatchTransformer (stage
    fusion runs before gather fusion) — those are unwrapped by their
    ``members`` list.
    """
    from keystone_tpu.ops.util import VectorCombiner

    if not isinstance(combiner, VectorCombiner) or len(branches) < 2:
        return None
    flat = []
    for br in branches:
        members = []
        for m in br:
            sub = getattr(m, "members", None)
            members.extend(sub if sub is not None else [m])
        if len(members) != 3:
            return None
        sign, fft, rect = members
        if not (
            isinstance(sign, RandomSignNode)
            and isinstance(fft, PaddedFFT)
            and isinstance(rect, LinearRectifier)
        ):
            return None
        flat.append(members)
    widths = {int(m[0].signs.shape[0]) for m in flat}
    if len(widths) != 1:
        return None
    rectifiers = tuple((float(m[2].max_val), float(m[2].alpha)) for m in flat)
    return (widths.pop(), rectifiers), tuple(m[0].signs for m in flat)


def packed_fft_gather_apply(static_key, params, X):
    """The packed program of :func:`packed_fft_gather_fn`.

    The per-branch composition reads X once PER BRANCH and runs nb real
    FFTs of width p. The packed program:

      - reads X once, applies the stacked sign flips as one broadcast
        multiply (the gather's input reads become one contiguous read);
      - packs branch pairs as real/imag of ONE width-p complex FFT —
        nb real transforms become ⌈nb/2⌉ complex ones — and unpacks
        Re(bins 0..p/2) by conjugate symmetry:

            Re A(k) = (Re Z(k) + Re Z((p−k) mod p)) / 2
            Re B(k) = (Im Z(k) + Im Z((p−k) mod p)) / 2

        (the scale-and-reversed-phase multiply of the classic two-real-
        FFTs-in-one-complex-FFT identity, folded into the FFT epilogue
        as two adds + one scale per bin);
      - applies the per-branch rectifiers and writes the concatenated
        output once, in the exact branch order the combiner produced.
    """
    d_in, rectifiers = static_key
    nb, p, npairs = len(rectifiers), padded_pow2(d_in), len(rectifiers) // 2
    signs = jnp.stack(params)  # (nb, d_in)
    maxvals = jnp.asarray([r[0] for r in rectifiers], jnp.float32)
    alphas = jnp.asarray([r[1] for r in rectifiers], jnp.float32)
    n = X.shape[0]
    Z = X[:, None, :] * signs  # ONE read of X for all branches
    Zp = jnp.pad(Z, ((0, 0), (0, 0), (0, p - d_in)))
    outs = []
    if npairs:
        pairs = Zp[:, : 2 * npairs].reshape(n, npairs, 2, p)
        F = jnp.fft.fft(
            jax.lax.complex(pairs[:, :, 0], pairs[:, :, 1]), axis=-1
        )
        re, im = jnp.real(F), jnp.imag(F)

        def rev(a):  # a[..., (p − k) mod p]
            return jnp.roll(a[..., ::-1], 1, axis=-1)

        reA = (0.5 * (re + rev(re)))[..., : p // 2]
        reB = (0.5 * (im + rev(im)))[..., : p // 2]
        outs.append(
            jnp.stack([reA, reB], axis=2).reshape(n, 2 * npairs, p // 2)
        )
    if nb % 2:
        tail = rfft_real_half(Zp[:, -1], p)
        outs.append(tail[:, None, :])
    halves = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out = jnp.maximum(halves - alphas[None, :, None], maxvals[None, :, None])
    return out.reshape(n, nb * (p // 2))


class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed random ±1 vector
    (reference: nodes/stats/RandomSignNode.scala:11-24)."""

    def __init__(self, signs):
        self.signs = jnp.asarray(signs)

    @staticmethod
    def create(num_features: int, seed: int = 0) -> "RandomSignNode":
        signs = jax.random.rademacher(
            jax.random.key(seed), (num_features,), dtype=jnp.float32
        )
        return RandomSignNode(signs)

    def device_operands(self):
        return (), (self.signs,)

    @staticmethod
    def device_apply(static_key, params, X):
        return X * params[0]


# ---------------------------------------------------------------------------
# Elementwise stats nodes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LinearRectifier(Transformer):
    """max(maxVal, x - alpha) (reference: nodes/stats/LinearRectifier.scala:12-17)."""

    max_val: float = 0.0
    alpha: float = 0.0

    def device_operands(self):
        return (float(self.max_val), float(self.alpha)), ()

    @staticmethod
    def device_apply(static_key, params, X):
        max_val, alpha = static_key
        return jnp.maximum(X - alpha, max_val)


@dataclass(frozen=True)
class SignedHellingerMapper(Transformer):
    """sign(x)·√|x| (reference: nodes/stats/SignedHellingerMapper.scala:11-22)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return jnp.sign(X) * jnp.sqrt(jnp.abs(X))


@dataclass(frozen=True)
class NormalizeRows(Transformer):
    """Divide by L2 norm, eps-floored (reference: nodes/stats/NormalizeRows.scala:10-14)."""

    eps: float = 2.2e-16

    def device_operands(self):
        return (float(self.eps),), ()

    @staticmethod
    def device_apply(static_key, params, X):
        norm = jnp.maximum(
            jnp.linalg.norm(X, axis=-1, keepdims=True), static_key[0]
        )
        return X / norm


@dataclass(frozen=True)
class TermFrequency(Transformer):
    """Seq of items -> {item: weighting(count)} (host-side;
    reference: nodes/stats/TermFrequency.scala:18-20)."""

    weighting: Callable = field(default=lambda x: x)

    def apply(self, items):
        counts = {}
        for item in items:
            counts[item] = counts.get(item, 0) + 1
        return {k: self.weighting(v) for k, v in counts.items()}

    def batch_apply(self, data: Dataset) -> Dataset:
        return Dataset.of([self.apply(x) for x in data.to_list()])

    def output_signature(self, sig):
        """Verifier declaration (host op): item sequences in, feature→
        weight dicts out. A bare string input is rejected — counting its
        CHARACTERS as terms is virtually always a missing-Tokenizer bug."""
        from keystone_tpu.workflow.verify import HostSig, expect_host

        sig = expect_host(sig, ("tokens", "ngrams", "int_tokens"), self)
        return HostSig("tf_dict", n=sig.n, datum=sig.datum)


class ColumnSampler(Transformer):
    """Sample columns of per-item (d, cols) matrices
    (reference: nodes/stats/Sampling.scala:12-25)."""

    def __init__(self, num_samples: int, seed: int = 0):
        self.num_samples = num_samples
        self.seed = seed

    def apply(self, x):
        x = jnp.asarray(x)
        idx = jax.random.randint(
            jax.random.key(self.seed), (self.num_samples,), 0, x.shape[1]
        )
        return x[:, idx]


def sample_dataset(data: Dataset, num_items: int, seed: int = 0) -> Dataset:
    """Random row sample (the RDD.takeSample FunctionNode analog,
    reference: nodes/stats/Sampling.scala:27-32)."""
    k = min(num_items, data.n)
    if data.is_host:
        rng = np.random.default_rng(seed)
        items = data.to_list()
        idx = rng.choice(len(items), size=k, replace=False)
        return Dataset.of([items[i] for i in idx])
    idx = jax.random.choice(jax.random.key(seed), data.n, (k,), replace=False)
    return Dataset(jnp.asarray(data.array)[: data.n][idx], n=k)


class Sampler(FunctionNode):
    """Dataset-level row sampler (FunctionNode, operates outside graph
    tracking like the reference's — reference: nodes/stats/Sampling.scala:27-32)."""

    def __init__(self, size: int, seed: int = 0):
        self.size = size
        self.seed = seed

    def apply(self, data: Dataset) -> Dataset:
        return sample_dataset(data, self.size, self.seed)

"""TimitPipeline: cosine random features + block least squares on TIMIT
(reference: pipelines/speech/TimitPipeline.scala:37-130).

Composition: gather(numCosines × CosineRandomFeatures(440→4096, γ,
gaussian|cauchy)) → VectorCombiner → BlockLeastSquares(4096, numEpochs, λ)
→ MaxClassifier.
"""

from __future__ import annotations

import argparse
import logging
import time
from dataclasses import dataclass

from keystone_tpu import obs
from keystone_tpu.data.loaders import TimitFeaturesDataLoader, synthetic_timit
from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.stats import (
    CosineRandomFeatures,
    cosine_draw_key,
    shared_bank,
)
from keystone_tpu.ops.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
    VectorCombiner,
)
from keystone_tpu.utils.profiling import follow_profiler
from keystone_tpu.workflow import Pipeline

logger = logging.getLogger("keystone_tpu.pipelines.timit")

NUM_CLASSES = TimitFeaturesDataLoader.num_classes  # 147
NUM_INPUT_FEATURES = TimitFeaturesDataLoader.num_features  # 440


@dataclass
class TimitConfig:
    train_data_location: str = ""
    train_labels_location: str = ""
    test_data_location: str = ""
    test_labels_location: str = ""
    num_parts: int = 512  # kept for flag parity; sharding is mesh-driven
    num_cosines: int = 50
    gamma: float = 0.05555
    rf_type: str = "gaussian"  # or "cauchy" (TimitPipeline.scala Distributions)
    block_size: int = 4096
    num_epochs: int = 5
    lam: float = 0.0
    seed: int = 123
    synthetic_n: int = 4096
    # Solver selection:
    #   "auto"      — cost-model-driven (LeastSquaresEstimator): the
    #                 optimizer picks among resident solvers and the
    #                 out-of-core streaming tier by analytic cost under an
    #                 HBM feasibility cut; past the memory wall the
    #                 StreamedFitFusionRule binds the cosine featurizer
    #                 into the fit with NO flag (the reference's defining
    #                 behavior, LeastSquaresEstimator.scala:59-84).
    #   "block"     — force BlockLeastSquares(block_size, epochs, λ), the
    #                 reference TimitPipeline's literal composition.
    #   "streaming" — force the out-of-core tier (the old --streaming).
    # All three fit the same centered model (streaming_ls centering).
    solver: str = "auto"
    # Back-compat alias: streaming=True == solver="streaming".
    streaming: bool = False


def _branch_draw(config: TimitConfig, i: int) -> tuple:
    """Branch ``i``'s arguments to ``CosineRandomFeatures`` (and to the key
    its draw is shared under): its own seed, everything else the config's."""
    return (NUM_INPUT_FEATURES, config.block_size, config.gamma,
            config.seed + i, config.rf_type == "cauchy")


def _draw_branches(config: TimitConfig) -> list:
    """One cosine bank a branch."""
    return [CosineRandomFeatures(*_branch_draw(config, i))
            for i in range(config.num_cosines)]


def _note_banks(span, branches: int, shared: int) -> None:
    """What ``pipeline.build`` says of its banks: how many branches' arrays
    were drawn anew and how many are an earlier equal draw's buffers (a
    sweep's every fit after its first shares them all)."""
    span.set(banks_drawn=branches - shared, banks_shared=shared)
    obs.counter_track("bank.shared", shared)


def build_featurizer(config: TimitConfig) -> Pipeline:
    """numCosines branches of 4096 random features each
    (TimitPipeline.scala:61-78: numCosineFeatures = 4096 per batch)."""
    follow_profiler()
    # Drawing the banks and building the graph: what every new fit pays
    # before ``pipeline.fit`` opens.
    with obs.span("pipeline.build", entry="featurizer",
                  branches=config.num_cosines) as span:
        rfs = _draw_branches(config)
        _note_banks(span, len(rfs), sum(rf.shared_draw for rf in rfs))
        return Pipeline.gather(
            [rf.to_pipeline() for rf in rfs]
        ).and_then(VectorCombiner())


def streaming_estimator(config: TimitConfig):
    """The out-of-core tier for this configuration: the cosine bank lives
    INSIDE the estimator, so the fit featurizes per row tile and the
    feature matrix never materializes."""
    import jax.numpy as jnp

    from keystone_tpu.ops.learning.streaming_ls import (
        StreamingFeaturizedLeastSquares,
        cosine_bank_featurize,
    )

    follow_profiler()
    with obs.span("pipeline.build", entry="streaming",
                  branches=config.num_cosines) as span:
        rfs = []

        def join():
            rfs.extend(_draw_branches(config))
            return (jnp.concatenate([rf.W for rf in rfs]),
                    jnp.concatenate([rf.b for rf in rfs]))

        # The joined bank is shared by its branches' keys: where an
        # earlier estimator's is still held, no branch is drawn at all.
        Wrf, brf, joined_shared = shared_bank(
            ("joined",) + tuple(cosine_draw_key(*_branch_draw(config, i))
                                for i in range(config.num_cosines)),
            join,
        )
        _note_banks(
            span, config.num_cosines,
            config.num_cosines if joined_shared
            else sum(rf.shared_draw for rf in rfs),
        )
        return StreamingFeaturizedLeastSquares(
            cosine_bank_featurize(Wrf, brf),
            d_feat=config.num_cosines * config.block_size,
            block_size=config.block_size, num_iter=config.num_epochs,
            lam=config.lam,
        )


def run(config: TimitConfig):
    start = time.time()
    if config.train_data_location:
        train = TimitFeaturesDataLoader(
            config.train_data_location, config.train_labels_location
        ).labeled
        test = TimitFeaturesDataLoader(
            config.test_data_location, config.test_labels_location
        ).labeled
    else:
        train = synthetic_timit(config.synthetic_n, seed=config.seed)
        test = synthetic_timit(max(config.synthetic_n // 4, 256), seed=config.seed + 1)
        # The reference default (numCosines=50 -> 204,800 features) is a
        # 2.2M-row cluster shape (TimitPipeline.scala:30); at the synthetic
        # demo's row count it is absurdly overparametrized and overflows a
        # single chip's HBM. Cap the demo's feature width at 8n; explicit
        # real-data runs keep whatever was asked for.
        max_branches = max(
            1, (8 * config.synthetic_n) // max(config.block_size, 1)
        )
        if config.num_cosines > max_branches:
            from dataclasses import replace

            logger.info(
                "synthetic demo: capping numCosines %d -> %d (d <= 8n)",
                config.num_cosines, max_branches,
            )
            config = replace(config, num_cosines=max_branches)

    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)

    solver = "streaming" if config.streaming else config.solver
    if solver == "streaming":
        est = streaming_estimator(config)
        pipeline = est.with_data(train.data, labels).and_then(MaxClassifier())
    elif solver == "auto":
        # Cost-model-driven selection: at resident-friendly geometry this
        # picks a resident solver (BlockLS at the reference's shape); past
        # the HBM wall the streaming choice wins and the optimizer fuses
        # the cosine featurizer into the fit — no flag.
        from keystone_tpu.ops.learning.cost import LeastSquaresEstimator

        est = LeastSquaresEstimator(
            lam=config.lam,
            block_size=config.block_size,
            block_iters=config.num_epochs,
        )
        pipeline = build_featurizer(config).and_then(
            est, train.data, labels,
        ).and_then(MaxClassifier())
    else:
        pipeline = build_featurizer(config).and_then(
            BlockLeastSquaresEstimator(config.block_size, config.num_epochs, config.lam),
            train.data,
            labels,
        ).and_then(MaxClassifier())

    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline.apply(train.data), train.labels)
    logger.info("TRAIN Error is %.2f%%", 100 * train_eval.total_error)
    test_eval = evaluator.evaluate(pipeline.apply(test.data), test.labels)
    logger.info("TEST Error is %.2f%%", 100 * test_eval.total_error)
    logger.info("Pipeline took %.1f s", time.time() - start)
    return pipeline, train_eval, test_eval


def main(argv=None):
    parser = argparse.ArgumentParser("Timit")
    parser.add_argument("--trainDataLocation", default="")
    parser.add_argument("--trainLabelsLocation", default="")
    parser.add_argument("--testDataLocation", default="")
    parser.add_argument("--testLabelsLocation", default="")
    parser.add_argument("--numParts", type=int, default=512)
    parser.add_argument("--numCosines", type=int, default=50)
    parser.add_argument("--gamma", type=float, default=0.05555)
    parser.add_argument("--rfType", default="gaussian", choices=["gaussian", "cauchy"])
    parser.add_argument("--blockSize", type=int, default=4096)
    parser.add_argument("--numEpochs", type=int, default=5)
    parser.add_argument("--lambda", dest="lam", type=float, default=0.0)
    parser.add_argument("--seed", type=int, default=123)
    parser.add_argument(
        "--streaming", action="store_true",
        help="force the out-of-core fit (equivalent to --solver streaming)",
    )
    parser.add_argument(
        "--solver", default="auto", choices=["auto", "block", "streaming"],
        help="auto = cost-model selection with HBM feasibility (default); "
        "block = reference-literal BlockLeastSquares",
    )
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    config = TimitConfig(
        train_data_location=args.trainDataLocation,
        train_labels_location=args.trainLabelsLocation,
        test_data_location=args.testDataLocation,
        test_labels_location=args.testLabelsLocation,
        num_parts=args.numParts,
        num_cosines=args.numCosines,
        gamma=args.gamma,
        rf_type=args.rfType,
        block_size=args.blockSize,
        num_epochs=args.numEpochs,
        lam=args.lam,
        seed=args.seed,
        solver=args.solver,
        streaming=args.streaming,
    )
    _, train_eval, test_eval = run(config)
    print(f"TRAIN Error is {100 * train_eval.total_error:.2f}%")
    print(f"TEST Error is {100 * test_eval.total_error:.2f}%")


if __name__ == "__main__":
    main()

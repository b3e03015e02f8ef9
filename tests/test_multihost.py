"""Multi-host (multi-process) bring-up exercised for real.

Two OS processes join one JAX distributed runtime over localhost (the DCN
analog of the reference's driver/executor bring-up, bin/run-pipeline.sh) and
run a sharded normal-equations solve whose Gramian reduction crosses the
process boundary. Each process forces 2 CPU devices, so the global mesh is
2 hosts × 2 devices = 4 — the smallest topology where `make_hybrid_mesh`'s
ICI-within/DCN-across layout is distinguishable.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # golden/e2e/multihost tier

_WORKER = textwrap.dedent(
    """
    import sys
    import numpy as np
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    jax.config.update("jax_enable_x64", True)

    coord, pid = sys.argv[1], int(sys.argv[2])

    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel import mesh as mesh_lib

    mesh_lib.init_distributed(
        coordinator_address=coord, num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, len(jax.devices())

    # data axis across hosts (DCN), model axis within a host (ICI).
    mesh = mesh_lib.make_hybrid_mesh(
        ici_shape=(1, 2), dcn_shape=(2, 1),
        axis_names=(mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
    )
    assert dict(mesh.shape) == {"data": 2, "model": 2}, dict(mesh.shape)

    # Deterministic data on every process; rows sharded over `data`.
    rng = np.random.default_rng(0)
    A = rng.normal(size=(32, 6))
    B = rng.normal(size=(32, 3))
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    # Build the global sharded array from per-process local shards (the
    # multi-host ingestion path: each host holds its own rows).
    sharding = NamedSharding(mesh, P("data", None))
    def put(x):
        return jax.make_array_from_process_local_data(sharding, x[pid * 16 : (pid + 1) * 16])
    A_sh, B_sh = put(A), put(B)

    W = linalg.normal_equations_solve(A_sh, B_sh, lam=1e-3)
    W_local = np.linalg.solve(A.T @ A + 1e-3 * np.eye(6), A.T @ B)
    # Replicated solve: every process's copy must equal the local solve.
    np.testing.assert_allclose(
        np.asarray(W.addressable_data(0)), W_local, atol=1e-9
    )
    print(f"proc {pid} OK")
    """
)


_LM_WORKER = textwrap.dedent(
    """
    import sys
    import numpy as np
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    # The packed n-gram ids use up to 62 bits: without x64 the device
    # all_gather would silently truncate them to int32 garbage.
    jax.config.update("jax_enable_x64", True)

    coord, pid = sys.argv[1], int(sys.argv[2])

    from keystone_tpu.parallel import mesh as mesh_lib

    mesh_lib.init_distributed(
        coordinator_address=coord, num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2

    from jax.experimental import multihost_utils

    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.nlp import (
        NGram,
        NGramsFeaturizer,
        StupidBackoffEstimator,
        initial_bigram_partition,
        pack_ngram_pairs,
        partition_ngram_pairs,
        unpack_ngram_pairs,
        ShardedStupidBackoffModel,
    )

    # Deterministic corpus of int word-ids; each process HOLDS only half of
    # the raw (ngram, count) stream (the per-host data slice).
    rng = np.random.default_rng(7)
    sents = [rng.integers(1, 40, size=12).tolist() for _ in range(30)]
    feats = NGramsFeaturizer([2, 3])
    all_pairs = []
    unigrams = {}
    for s in sents:
        for w in s:
            unigrams[w] = unigrams.get(w, 0) + 1
        for g in feats.apply(s):
            all_pairs.append((NGram(g), 1))
    local_pairs = all_pairs[pid::2]

    # Exchange: pack local counts into ONE int64 device array and
    # all_gather across the two processes (counts ride DCN as arrays, not
    # pickled host objects).
    packed = pack_ngram_pairs(local_pairs)
    # Ragged halves: pad to a common length with an invalid row (count 0).
    m = (len(all_pairs) + 1) // 2
    if packed.shape[0] < m:
        pad = np.zeros((m - packed.shape[0], 2), dtype=np.int64)
        packed = np.vstack([packed, pad])
    gathered = multihost_utils.process_allgather(packed)  # (2, m, 2)
    pairs_all = []
    for part in gathered:
        part = part[part[:, 1] > 0]
        pairs_all.extend(unpack_ngram_pairs(part))

    # reduceByKey + InitialBigramPartitioner; this process fits ONLY its
    # own partition (StupidBackoff.scala:152-176 mapPartitions analog).
    parts = partition_ngram_pairs(pairs_all, 2)
    est = StupidBackoffEstimator(unigrams)
    my_model = est.fit(Dataset.of(parts[pid]))

    # Single-host reference fit over the full data: the partition-local
    # scores must EQUAL the global fit's scores on this partition.
    full_model = est.fit(Dataset.of(all_pairs))
    assert len(my_model.scores) == len(parts[pid])
    for ngram, score in my_model.scores.items():
        ref = full_model.scores[ngram]
        assert abs(score - ref) < 1e-12, (ngram, score, ref)

    # Coverage: the two partitions tile the global table exactly.
    sizes = multihost_utils.process_allgather(
        np.array([len(my_model.scores)])
    )
    assert int(sizes.sum()) == len(full_model.scores), (
        sizes, len(full_model.scores)
    )

    # Serving side: a sharded model routing by the partitioner agrees with
    # the single-host model on every observed ngram.
    shards = [est.fit(Dataset.of(p)) for p in parts]
    sharded = ShardedStupidBackoffModel(shards)
    for ngram in list(full_model.scores)[:50]:
        assert abs(sharded.score(ngram) - full_model.score(ngram)) < 1e-12

    print(f"lm proc {pid} OK: partition size {len(my_model.scores)}")
    """
)


_KRR_WORKER = textwrap.dedent(
    """
    import sys
    import numpy as np
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    coord, pid = sys.argv[1], int(sys.argv[2])

    from keystone_tpu.parallel import mesh as mesh_lib

    mesh_lib.init_distributed(
        coordinator_address=coord, num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.kernel import (
        GaussianKernelGenerator,
        KernelRidgeRegression,
        _krr_fit_fused,
    )

    # data axis spans 2 hosts x 2 devices: the fused shard_map sweep's
    # all_gather(X) and psum(residual) must cross the process (DCN)
    # boundary, not just ICI.
    mesh = mesh_lib.make_hybrid_mesh(
        ici_shape=(2,), dcn_shape=(2,), axis_names=(mesh_lib.DATA_AXIS,)
    )
    assert dict(mesh.shape) == {"data": 4}

    n, d, k, bs, epochs = 256, 8, 3, 64, 2
    rng = np.random.default_rng(3)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)

    sharding = NamedSharding(mesh, P("data", None))
    def put(x):
        return jax.make_array_from_process_local_data(
            sharding, x[pid * (n // 2) : (pid + 1) * (n // 2)]
        )

    data = Dataset(put(X), n=n, mesh=mesh)
    labels = Dataset(put(Y), n=n, mesh=mesh)
    krr = KernelRidgeRegression(GaussianKernelGenerator(0.05), 0.2, bs, epochs)
    model = krr.fit(data, labels)

    # Reference: the single-device fused sweep on the full local copy.
    order = jnp.asarray(np.tile(np.arange(n // bs, dtype=np.int32), epochs))
    _, ref_stack = _krr_fit_fused(
        jnp.asarray(X), jnp.asarray(Y), order, 0.05, 0.2, bs, n, n // bs,
        False,
    )
    for b in range(n // bs):
        got = np.asarray(model.w_locals[b].addressable_data(0))
        want = np.asarray(ref_stack[b])
        np.testing.assert_allclose(got, want, atol=2e-4)
    print(f"krr proc {pid} OK: {n // bs} blocks match single-device fit")
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two_workers(tmp_path, source: str, ok_marker: str):
    coord = f"localhost:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(source)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker configures its own device count
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            cwd="/root/repo",
        )
        for pid in range(2)
    ]
    outputs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outputs.append(out.decode())
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert ok_marker.format(pid=pid) in out


def test_two_process_distributed_solve(tmp_path):
    _run_two_workers(tmp_path, _WORKER, "proc {pid} OK")


def test_two_process_stupid_backoff_counts(tmp_path):
    """The LM count/score tables shard by initial_bigram_partition across
    two OS processes: counts exchanged as packed int64 device arrays, each
    process fits only its partition, scores equal the single-host fit, the
    partitions tile the table, and the sharded model serves correctly."""
    _run_two_workers(tmp_path, _LM_WORKER, "lm proc {pid} OK")


def test_two_process_fused_krr_fit(tmp_path):
    """The fused KRR shard_map sweep runs with its data axis spanning two
    OS processes (all_gather + psum over the DCN boundary) and matches the
    single-device fused fit block for block."""
    _run_two_workers(tmp_path, _KRR_WORKER, "krr proc {pid} OK")

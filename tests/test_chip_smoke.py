"""Start-up plumbing and the chip smoke's control flow, on the CPU.

``chip_smoke.py`` proves the system on the TPU and nowhere else; what runs
here is a REHEARSAL of its control flow — the phase functions at toy size
with interpret mode requested explicitly (``interpret=True`` /
``KEYSTONE_PALLAS=1``) and the platform they expect named as ``"cpu"`` —
never a pass. The same file pins the start-up helpers every entry point
shares (``keystone_tpu/utils/startup.py``).
"""

import os
import subprocess
import sys

import jax
import pytest

from keystone_tpu.utils import startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (repo-root script, not a package module)


class TestCompileCacheHelper:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record ``jax.config.update`` calls instead of applying them, so
        the suite's own cache setting is left alone."""
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda name, value: calls.append((name, value))
        )
        return calls

    def test_env_set_sets_no_directory_in_code(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert startup.enable_compile_cache() == "/some/dir"
        assert updates == []  # jax reads the variable itself

    def test_unset_uses_the_fixed_in_checkout_path(
        self, monkeypatch, updates, tmp_path
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # Fixed, inside the checkout, no temp name / pid / time in it.
        assert startup.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        monkeypatch.setattr(
            startup, "DEFAULT_CACHE_DIR", str(tmp_path / ".jax_cache")
        )
        assert startup.enable_compile_cache() == startup.DEFAULT_CACHE_DIR
        assert os.path.isdir(startup.DEFAULT_CACHE_DIR)
        assert updates == [
            ("jax_compilation_cache_dir", startup.DEFAULT_CACHE_DIR)
        ]

    def test_uncreatable_directory_raises(self, monkeypatch, updates, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "a_file"
        blocker.write_text("not a directory")
        monkeypatch.setattr(
            startup, "DEFAULT_CACHE_DIR", str(blocker / ".jax_cache")
        )
        with pytest.raises(OSError):
            startup.enable_compile_cache()
        assert updates == []


class TestDeviceFacts:
    def test_summary_names_the_device(self):
        d = startup.device_summary()
        assert d == {
            "backend": "cpu",
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        }

    def test_cpu_reports_no_limit_and_tpu_must(self, monkeypatch):
        # The CPU test mesh reports no memory statistics: None, and the
        # callers' CPU constants apply.
        assert startup.device_memory_limit() is None

        class FakeTpu:
            platform = "tpu"
            device_kind = "TPU v5 lite"

            def memory_stats(self):
                return {"bytes_in_use": 0}

        monkeypatch.setattr(jax, "local_devices", lambda: [FakeTpu()])
        with pytest.raises(RuntimeError, match="no bytes_limit"):
            startup.device_memory_limit()


class TestChipSmoke:
    def test_script_refuses_the_cpu_and_prints_no_result(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode not in (0, None)
        assert '"ok"' not in proc.stdout
        assert "not 'tpu'" in proc.stderr

    def test_a_failed_phase_fails_the_run_and_later_phases_still_run(
        self, monkeypatch
    ):
        ran = []

        def failing(clock):
            ran.append("kernels")
            raise chip_smoke.CheckFailed("a deliberately wrong tolerance")

        def passing(clock):
            ran.append("serve")
            return {"ok": True}

        monkeypatch.setattr(chip_smoke, "phase_kernels", failing)
        monkeypatch.setattr(chip_smoke, "phase_serve", passing)
        ok, results = chip_smoke.run_phases(
            ["kernels", "serve"], chip_smoke.CompileClock()
        )
        assert ran == ["kernels", "serve"]
        assert ok is False
        assert results["kernels"]["ok"] is False
        assert "deliberately wrong" in results["kernels"]["error"]
        assert results["serve"]["ok"] is True


class TestPhaseRehearsal:
    """Toy sizes, interpret requested explicitly, platform named "cpu"."""

    @pytest.fixture(scope="class")
    def clock(self):
        return chip_smoke.CompileClock()

    TOY_TIMIT = chip_smoke.TimitSizes(
        rows=2048, cosines=2, block=256, probe_rows=256, export_max_batch=4
    )

    def test_kernel_roll_call(self, clock):
        toy = chip_smoke.KernelSizes(
            rows=256, krr_dim=256, krr_block=128, block=256, sketch_rows=128,
            sketch_nnz=4, sketch_m=64, sketch_d1=70, conv_filters=16,
        )
        report = chip_smoke.phase_kernels(
            clock, toy, interpret=True, platform="cpu"
        )
        assert report["ok"], report["failed"]
        kernels = {name.split("[")[0] for name in report["kernels"]}
        assert kernels == {
            "gaussian_kernel_block", "gaussian_resid_block",
            "cosine_features", "gram_corr", "gram_corr_sym",
            "block_gram_sym", "gram_sym_acc", "gram_corr_sym_acc",
            "block_corr", "block_residual_update", "countsketch_scatter",
            "conv_pool",
        }
        # Mosaic was asked for on a backend that has none: recorded as a
        # failure of that kernel, never a fall-through to anything else.
        refused = chip_smoke.phase_kernels(
            clock, toy, interpret=False, platform="cpu"
        )
        assert not refused["ok"]
        assert len(refused["failed"]) == len(refused["kernels"])
        # A run that landed on another device than it says is a failed run.
        elsewhere = chip_smoke.phase_kernels(
            clock, toy, interpret=True, platform="tpu"
        )
        assert not elsewhere["ok"]
        assert "ran on ['cpu'], not tpu" in \
            elsewhere["kernels"]["cosine_features"]["error"]

    def test_timit_auto_and_streaming(self, clock, monkeypatch):
        monkeypatch.setenv("KEYSTONE_PALLAS", "1")  # kernels on, interpreted
        # At toy width the two fits leave near-ties (10% test error) that
        # the full-width smoke does not, so the agreement bound is sized
        # for the toy (the production bound fails here).
        report = chip_smoke.phase_timit(
            clock, self.TOY_TIMIT, platform="cpu", min_agreement=0.95
        )
        assert report["ok"]
        for solver in ("auto", "streaming"):
            entry = report[solver]
            assert entry["train_error"] <= chip_smoke.TIMIT_TRAIN_ERROR_BOUND
            # Interpreted dispatches are reported as such, and nothing
            # claims a Mosaic custom call on this backend. The streamed
            # fit featurizes through the kernel; the resident fit's one
            # dispatch of it was the optimizer's few sample rows, which
            # take XLA's shared programs since PR 32 (ops/stats.py).
            assert ("cosine_features" in entry["kernels_interpreted"]) == (
                solver == "streaming")
            assert entry["kernels_dispatched"] == []
            assert entry["mosaic_custom_calls_in_lowered_text"] == {}
            assert entry["plan_compiled"] in (True, False)
        assert report["streaming"]["gram_sym_acc_dispatched"] is False

    def test_serve_cli_and_rows(self, clock):
        report = chip_smoke.phase_serve(
            clock,
            ("serve", "--pipeline", "MnistRandomFFT", "--rate", "50",
             "--duration-s", "0.5", "--fit-n", "256", "--input-dim", "64",
             "--blockSize", "64", "--numFFTs", "2"),
            platform="cpu",
        )
        assert report["ok"]
        assert report["summary"]["backend"] == "cpu"
        assert report["summary"]["device_count"] == len(jax.devices())
        assert report["summary"]["plan_compiled"] is True
        assert report["rows"]["served_classes"] == \
            report["rows"]["offline_classes"]

    def test_mesh_leg(self, clock, capsys):
        report = chip_smoke.phase_mesh(clock, self.TOY_TIMIT, platform="cpu")
        assert report["ok"] and not report.get("skipped")
        assert len(set(report["shard_devices"])) == 4
        assert report["rel_score_err"] <= chip_smoke.MESH_MAX_REL_SCORE_ERR
        # Fewer devices than the leg needs: skipped, and it says so.
        skipped = chip_smoke.phase_mesh(
            clock, self.TOY_TIMIT, platform="cpu",
            num_devices=len(jax.devices()) + 1,
        )
        assert skipped["skipped"] is True
        assert f"mesh_leg: skipped ({len(jax.devices())} devices)" in \
            capsys.readouterr().out

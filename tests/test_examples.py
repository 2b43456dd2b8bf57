"""Example-script smoke tests (reference: apex has no CI for examples —
its L0 test philosophy applied here: every shipped entry point must run
end-to-end, on the 8-virtual-device CPU mesh so the GSPMD/DDP paths are
real multi-device executions)."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(rel_path, argv, timeout=600):
    """Run an example on the CPU platform with 8 virtual devices (a
    child that never asks for a chip)."""
    code = (
        "import sys, runpy; sys.argv = [sys.argv[0]] + %r;"
        "runpy.run_path(%r, run_name='__main__')"
        % (argv, os.path.join(_ROOT, rel_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def _check(res):
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DONE" in res.stdout, res.stdout[-2000:]
    return res.stdout


class TestExamples:
    def test_simple_ddp(self):
        out = _check(_run_example(
            "examples/simple/distributed/distributed_data_parallel.py", []))
        assert "devices=8" in out

    @pytest.mark.parametrize("opt_level", ["O0", "O1", "O2"])
    @pytest.mark.slow
    def test_imagenet(self, opt_level):
        out = _check(_run_example(
            "examples/imagenet/main_amp.py",
            ["--arch", "resnet18", "--batch-size", "16", "--image-size",
             "32", "--num-classes", "10", "--steps", "2", "--print-freq",
             "1", "--opt-level", opt_level]))
        assert "devices=8" in out

    @pytest.mark.slow
    def test_dcgan(self):
        _check(_run_example(
            "examples/dcgan/main_amp.py",
            ["--batch-size", "8", "--image-size", "64", "--steps", "2",
             "--print-freq", "1", "--ngf", "8", "--ndf", "8",
             "--nz", "16"]))

    @pytest.mark.parametrize(
        "top_k", [1, pytest.param(2, marks=pytest.mark.slow)])
    def test_switch_gpt(self, top_k):
        out = _check(_run_example(
            "examples/moe/train_switch_gpt.py",
            ["--n-experts", "8", "--batch-per-device", "2",
             "--seq-len", "32", "--hidden", "32", "--layers", "1",
             "--heads", "4", "--vocab", "64", "--steps", "2",
             "--print-freq", "1", "--top-k", str(top_k)]))
        assert "devices=8" in out

    @pytest.mark.parametrize(
        "mechanism", ["ring", pytest.param("ulysses",
                                           marks=pytest.mark.slow)])
    def test_long_context(self, mechanism):
        out = _check(_run_example(
            "examples/long_context/train_long_gpt.py",
            ["--seq-len", "64", "--hidden", "32", "--layers", "1",
             "--heads", "8", "--vocab", "64", "--steps", "2",
             "--print-freq", "1", "--mechanism", mechanism]))
        assert "devices=8" in out

    @pytest.mark.slow
    def test_conformer_rnnt(self):
        _check(_run_example(
            "examples/conformer/train_rnnt.py",
            ["--steps", "2", "--print-freq", "1", "--batch-size", "2",
             "--layers", "1", "--hidden", "32", "--heads", "2",
             "--audio-len", "40", "--target-len", "6", "--vocab", "16",
             "--pred-hidden", "32", "--n-mels", "8"]))

    @pytest.mark.parametrize(
        "opt_level", [pytest.param("O0", marks=pytest.mark.slow), "O2"])
    def test_bert_pretrain(self, opt_level):
        out = _check(_run_example(
            "examples/bert/pretrain_bert.py",
            ["--config", "tiny", "--batch-size", "8", "--seq-len", "64",
             "--steps", "2", "--print-freq", "1",
             "--opt-level", opt_level]))
        assert "devices=8" in out

    @pytest.mark.slow
    def test_serving_engine(self):
        """The inference subsystem end-to-end: continuous batching over
        2 cache slots with a mixed greedy/top-k workload."""
        out = _check(_run_example(
            "examples/serving/generate_gpt.py",
            ["--requests", "4", "--max-slots", "2", "--hidden", "32",
             "--layers", "1", "--heads", "2", "--vocab", "64",
             "--max-seq", "32", "--max-new-tokens", "6",
             "--temperature", "0.7"]))
        assert "served 4 requests" in out

    @pytest.mark.slow
    def test_gpt7b_recipe_smoke(self):
        """BASELINE row 2's runnable artifact: the 7B TP x PP recipe at
        --smoke keeps the full tp=2 x pp=2 x dp=2 topology and every
        collective family, shrinking only shapes."""
        out = _check(_run_example(
            "examples/gpt7b/pretrain_gpt7b.py", ["--smoke", "--steps", "2"]))
        assert "mesh=(dp=2, pp=2, tp=2)" in out

    @pytest.mark.slow
    def test_checkpoint_resume_bitwise(self, tmp_path):
        """SURVEY §5 checkpoint/resume: the resumed process continues the
        EXACT trajectory of the uninterrupted run — full state (params,
        packed optimizer buckets, dynamic scaler, step) round-trips
        through the framework's own parallel-IO runtime."""
        import re
        ck = str(tmp_path / "ck.bin")
        full = _check(_run_example(
            "examples/checkpoint/train_resume.py",
            ["--steps", "6", "--save-at", "3", "--ckpt", ck]))
        resumed = _check(_run_example(
            "examples/checkpoint/train_resume.py",
            ["--steps", "6", "--resume", "--ckpt", ck]))

        def losses(out):
            return {int(m[0]): m[1] for m in
                    re.findall(r"step (\d+): loss=([0-9.]+)", out)}

        lf, lr = losses(full), losses(resumed)
        assert set(lr) == {3, 4, 5}, resumed
        for s in lr:
            assert lf[s] == lr[s], (s, lf[s], lr[s])  # bitwise identical

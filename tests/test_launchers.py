"""Launcher smoke tests: train (+resume), serve, search CLIs end to end."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, devices=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-m"] + args,
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    return out.stdout


def test_train_launcher_and_resume(tmp_path):
    ckpt = str(tmp_path / "ck")
    out = _run(["repro.launch.train", "--arch", "qwen2p5_3b", "--smoke",
                "--steps", "24", "--batch", "2", "--seq", "32", "--f32",
                "--ckpt-dir", ckpt, "--ckpt-every", "12",
                "--log-every", "12"])
    first = json.loads(out.strip().splitlines()[-1])
    assert first["final_loss"] < first["first_loss"]
    # Resume continues from the saved step.
    out2 = _run(["repro.launch.train", "--arch", "qwen2p5_3b", "--smoke",
                 "--steps", "30", "--batch", "2", "--seq", "32", "--f32",
                 "--ckpt-dir", ckpt, "--resume", "--log-every", "6"])
    assert "resumed from step 24" in out2


def test_train_launcher_sharded():
    out = _run(["repro.launch.train", "--arch", "qwen1p5_0p5b", "--smoke",
                "--steps", "30", "--batch", "4", "--seq", "32", "--f32",
                "--mesh", "2x2", "--log-every", "10"], devices=4)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["final_loss"] < rec["first_loss"]


def test_serve_launcher():
    out = _run(["repro.launch.serve", "--arch", "qwen1p5_0p5b", "--smoke",
                "--f32", "--requests", "4", "--max-new", "4"])
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["requests"] == 4 and stats["tokens"] == 16


def test_search_launcher(tmp_path):
    out_file = str(tmp_path / "res.json")
    _run(["repro.launch.search", "--workload", "ncf", "--epochs", "150",
          "--ga-generations", "50", "--platform", "iot",
          "--out", out_file])
    rec = json.load(open(out_file))
    assert rec["best_value"] <= rec["stage1_value"]
    assert len(rec["assignment"]["pe"]) == len(rec["assignment"]["layers"])


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and no code overrides it; unset, the
    cache goes to the one fixed path in the checkout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_persistent_cache\n"
            "print(enable_persistent_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]


def test_search_launcher_arch_target(tmp_path):
    out_file = str(tmp_path / "res.json")
    _run(["repro.launch.search", "--arch", "qwen1.5-0.5b", "--tokens", "64",
          "--epochs", "120", "--no-finetune", "--platform", "cloud",
          "--out", out_file])
    rec = json.load(open(out_file))
    assert rec["best_value"] < float("inf")

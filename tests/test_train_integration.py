"""End-to-end training integration: the launcher's verification gate, loss
decrease on the synthetic stream, checkpoint/kill/resume fault tolerance,
and elastic resume onto a different mesh layout."""
import os
import re
import subprocess
import sys
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _train(args: list[str], devices: int = 8, timeout: int = 800):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *args],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def _losses(stdout: str) -> list[float]:
    return [float(m) for m in re.findall(r"loss (\d+\.\d+)", stdout)]


def test_train_verify_gate_and_loss_decreases(tmp_path):
    out = _train(["--arch", "qwen3_4b", "--smoke", "--steps", "40",
                  "--tp", "2", "--dp", "4", "--seq", "64", "--batch", "8",
                  "--lr", "3e-3"])
    assert "VERIFIED" in out
    losses = _losses(out)
    assert losses[0] - losses[-1] > 0.3, f"no learning: {losses}"


def test_kill_and_resume_continues(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    # phase 1: 20 steps, checkpoint every 10
    out1 = _train(["--arch", "mamba2_130m", "--smoke", "--steps", "20",
                   "--tp", "1", "--dp", "2", "--seq", "32", "--batch", "4",
                   "--ckpt-dir", ckpt, "--ckpt-every", "10", "--skip-verify"],
                  devices=2)
    assert "saved step 20" in out1
    # phase 2: "restart after failure" — resumes from step 20
    out2 = _train(["--arch", "mamba2_130m", "--smoke", "--steps", "30",
                   "--tp", "1", "--dp", "2", "--seq", "32", "--batch", "4",
                   "--ckpt-dir", ckpt, "--ckpt-every", "10", "--resume",
                   "--skip-verify"], devices=2)
    assert "resumed" in out2 and "step 20" in out2
    losses1, losses2 = _losses(out1), _losses(out2)
    # resumed loss continues from (not above) where phase 1 ended
    assert losses2[0] <= losses1[0], (losses1, losses2)


def test_elastic_resume_different_mesh(tmp_path):
    """A checkpoint written under dp=2 restores under tp=2 x dp=2 (elastic
    re-sharding happens at restore; the data stream replays its position)."""
    ckpt = str(tmp_path / "ckpt")
    _train(["--arch", "qwen3_4b", "--smoke", "--steps", "10",
            "--tp", "1", "--dp", "2", "--seq", "32", "--batch", "8",
            "--ckpt-dir", ckpt, "--ckpt-every", "10", "--skip-verify"],
           devices=2)
    out = _train(["--arch", "qwen3_4b", "--smoke", "--steps", "14",
                  "--tp", "2", "--dp", "2", "--seq", "32", "--batch", "8",
                  "--ckpt-dir", ckpt, "--ckpt-every", "10", "--resume",
                  "--skip-verify"], devices=4)
    assert "resumed" in out
    assert _losses(out), out


def test_launcher_aborts_on_nonfinite_loss(monkeypatch, tmp_path, capsys):
    """A step whose loss is NaN makes the launcher exit nonzero at the next
    logged step instead of reporting success."""
    import jax.numpy as jnp

    from repro.launch import train

    make = train.make_step_fn

    def nan_loss_step(model, tcfg):
        step = make(model, tcfg)

        def wrapped(params, opt, batch):
            params, opt, metrics = step(params, opt, batch)
            return params, opt, {**metrics, "loss": metrics["loss"] * jnp.nan}

        return wrapped

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(train, "make_step_fn", nan_loss_step)
    rc = train.main(["--arch", "mamba2_130m", "--smoke", "--steps", "2",
                     "--seq", "32", "--batch", "2"])
    assert rc == 3
    assert "[abort] non-finite loss by step 0" in capsys.readouterr().out

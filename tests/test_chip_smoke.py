"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases pass on the CPU at a small size with the TPU-only checks (the
device and the ``tpu_custom_call`` in compiled programs) stepped past —
the mesh phase on four virtual CPU devices, where session carries that
migrate between shards must move between devices."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(args, cwd, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    env.pop("REPRO_KERNEL_IMPL", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_tpu(tmp_path, alone):
    """No chip, or no repository beside the script: non-zero exit and
    no result line."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        with open(script) as f:
            (tmp_path / "chip_smoke.py").write_text(f.read())
        script = str(tmp_path / "chip_smoke.py")
    proc = _run([script], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_phases_on_cpu(monkeypatch, tmp_path):
    kernels = []
    monkeypatch.setattr(chip_smoke, "require_kernel",
                        lambda text, what: kernels.append(what))
    ckpt = str(tmp_path / "paper_lstm.npz")
    chip_smoke.train_phase(ckpt, seed=0, iterations=40, days=1430)
    chip_smoke.serve_phase(ckpt, seed=0, n_clients=12, decode_slots=8,
                           requests=64)
    assert kernels == ["training round", "slots generate"]


def test_mesh_phase_on_four_cpu_devices():
    code = ("import chip_smoke; "
            "chip_smoke.mesh_phase(0, n_chips=4, n_clients=16)")
    proc = _run(["-c", code], cwd=REPO,
                PYTHONPATH=os.path.join(REPO, "src"),
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "carries moved" in proc.stdout

"""chip_smoke.py, rehearsed without the chip.

The script itself has no CPU branch: run as a program it needs a TPU.
Its phases are functions of a ``Size``, so the control flow of the
train / generate / serve phases and of the ``--chips 4`` comparison is
driven here at a tiny size on the virtual CPU mesh (``on_chip=False``
skips only what a CPU cannot show: kernel names in the lowering, the
compiled q8 path, HBM statistics).  The two ways the driver expects the
program to FAIL are pinned too."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Size(
    vocab_size=512, d_model=128, n_heads=4, d_ff=256, n_layers=2, seq=128,
    batch=8, steps_per_epoch=3, flash_block=128, loss_chunk=128,
    gen_prompt=16, gen_new=8, serve_system=32, serve_family=(8, 8, 20, 20),
    serve_others=(12, 28, 40, 28), serve_new=8)


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    return tmp_path


def test_one_chip_phases_rehearsal(out_dir, capsys):
    chip_smoke.phase_train(TINY, 0, on_chip=False)
    model, params = chip_smoke.phase_generate(TINY, 0, on_chip=False)
    chip_smoke.phase_serve(TINY, 0, model, params, on_chip=False)
    phases = [line.split('"')[3] for line
              in capsys.readouterr().out.splitlines()
              if line.startswith('{"phase"')]
    assert phases == ["train", "generate", "generate", "serve"]


def test_lfm2_cell_shapes_phase_rehearsal(capsys):
    chip_smoke.phase_lfm2_cell_shapes(
        0, batch=2, seq=64, d_model=64, expert_width=48, experts=8, held=4,
        top_k=2, iters=1, on_chip=False)
    out = capsys.readouterr().out
    assert '"op": "dropless_moe"' in out and '"rows_in": 128' in out
    assert '"op": "dropless_moe_overflow"' in out
    assert '"op": "gated_short_conv"' in out


def test_nemotron_cell_shapes_phase_rehearsal(capsys):
    chip_smoke.phase_nemotron_cell_shapes(
        0, batch=2, seq=64, d_model=64, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, chunk=16, latent=32, expert_width=24, shared_width=48,
        experts=16, held=4, top_k=4, iters=1, on_chip=False)
    out = capsys.readouterr().out
    assert '"op": "mamba2_mixer"' in out
    assert '"op": "latent_moe"' in out and '"rows_in": 128' in out


def test_ouro_cell_shapes_phase_rehearsal(capsys):
    chip_smoke.phase_ouro_cell_shapes(
        0, batch=2, seq=64, d_model=64, heads=4, head_dim=24, d_ff=160,
        layers=2, passes=3, block=32, iters=1, on_chip=False)
    out = capsys.readouterr().out
    assert '"op": "looped_stack"' in out
    assert '"layer_applications": 6' in out and "['layers']['ln1_post']" in out


def test_four_chip_phase_rehearsal(out_dir, capsys):
    chip_smoke.phase_four_chip(TINY, 0)
    out = capsys.readouterr().out
    for run in ("one_device", "dp4", "fsdp4", "fsdp4_int8_scan"):
        assert f'"run": "{run}"' in out
    assert (out_dir / "four_chip.stderr").exists()


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_an_accelerator_it_fails_and_prints_no_result():
    proc = _run(_REPO, "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = _run(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

"""``chip_smoke.py`` refuses to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_cpu():
    out = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not 'tpu'" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_kernel_phase_runs_interpreted_at_short_lengths():
    """The kernel phase's inputs and comparisons, at Qwen2.5-3B's heads
    but short sequences and a small bank, with the kernels interpreted."""
    import chip_smoke
    from repro.configs import get_config
    errs = chip_smoke.kernels(get_config("qwen2.5-3b"), 4096, seq=64,
                              cache_len=256, compiled=False)
    assert sorted(errs) == sorted([
        "cosine_topk", "cosine_topk_gather", "decode_attention",
        "decode_attention_block", "paged_attention",
        "paged_attention_block", "flash_attention"])

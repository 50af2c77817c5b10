"""``chip_smoke.py`` rehearsed on the CPU at a test size.

The script itself refuses anything but a TPU; these tests drive its phase
functions directly (kernels in interpret mode), run its four-chip phase on
forced host devices, and check that it fails closed without a chip and
without the rest of the repository.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest
from _subproc import REPO, child_env, run_python

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

_TINY = """
from repro.configs.registry import get_config
import chip_smoke
TINY = chip_smoke.SmokeShape(
    cfg=get_config("qwen2-57b-a14b", reduced=True).with_overrides(
        num_heads=8, num_kv_heads=4),
    draft_cfg=get_config("qwen2-0.5b", reduced=True),
    requests=6, max_batch=4, max_new=8, page_size=16,
    reduced="test shape")
"""


def _tiny():
    ns = {}
    exec(_TINY, ns)
    return ns["TINY"]


def _result_line(stdout: str):
    lines = stdout.strip().splitlines()
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        return None


def test_one_chip_phases_at_test_size(capsys):
    """Phases (b)-(e): kernel parity, SD and AR streams with mid-stream
    admission, and a warm replay that compiles and transfers nothing."""
    chip_smoke.run_one_chip(_tiny(), interpret=True)
    out = capsys.readouterr().out
    assert "(c) SD stream" in out and "(d) AR stream" in out
    assert "(e) warm replay: 0 compile(s), 0 implicit transfer(s), " \
        "tokens identical to (c): True" in out


def test_four_chip_phase_on_host_devices():
    """The expert-parallel path (1x4 mesh, heads sharded under shard_map)
    against the single-device run, on four forced host devices."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, os.getcwd())
        import jax
    """) + _TINY + textwrap.dedent("""
        chip_smoke.run_four_chips(TINY, jax.devices()[:4])
        print("OK")
    """)
    proc = run_python(["-c", script], timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout
    assert "expert weights (2, 4, 256, 128) on 4 device(s)" in proc.stdout


def test_refuses_to_run_without_a_tpu():
    proc = run_python(["chip_smoke.py"], timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _result_line(proc.stdout) is None
    assert "(b) kernels" not in proc.stdout


def test_fails_alone_without_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, timeout=300,
                          env=child_env(), cwd=tmp_path)
    assert proc.returncode != 0
    assert _result_line(proc.stdout) is None


def test_compile_cache_directory(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is configured;
    without it the cache is the fixed, git-ignored .jax_cache/."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

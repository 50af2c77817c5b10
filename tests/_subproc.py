"""Run a Python child from the repository root on the CPU backend.

Tests that need forced host devices (``--xla_force_host_platform_device_count``)
or a fresh backend run their script in a child process; it inherits only
what it needs: the repo's ``src`` on the path, ``HOME``/``TMPDIR`` and
``JAX_PLATFORMS=cpu``, so it never looks for an accelerator.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child_env() -> dict:
    env = {"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    for k in ("HOME", "TMPDIR"):
        if k in os.environ:
            env[k] = os.environ[k]
    return env


def run_python(args, *, timeout: int) -> subprocess.CompletedProcess:
    """``python <args>`` in the repo root; returns the completed process."""
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=child_env(),
                          cwd=REPO)

"""Importing the service and the core starts no JAX backend, so a process
that only submits jobs over HTTP never takes the chip that the serving
process holds."""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_starts_no_backend():
    code = ("import repro.service, repro.core\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_span_starts_no_backend():
    """A span enters a profiler annotation only once JAX is imported, and
    neither imports JAX nor starts a backend."""
    code = ("import sys\n"
            "from repro import obs\n"
            "with obs.span('unit.light'): pass\n"
            "assert 'jax' not in sys.modules\n"
            "import repro.service, repro.core\n"
            "with obs.span('unit.light'): pass\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr

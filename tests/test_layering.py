"""The serving half does not import the planner.

``runtime/``, ``models/`` and ``kernels/`` serve a model on the chip;
``core/`` is the host-side PipeOrgan planner.  Each serving module is
imported alone in a fresh interpreter, which must leave no ``repro.core``
module loaded.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.mark.parametrize("module", [
    "repro.runtime.serve_loop",
    "repro.runtime.steps",
    "repro.runtime.telemetry",
    "repro.models.transformer",
    "repro.models.layers",
])
def test_serving_module_does_not_import_planner(module):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   filter(None, [str(SRC), os.environ.get("PYTHONPATH")])))
    code = (f"import sys, {module}\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m == 'repro.core' or m.startswith('repro.core.')))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]", out.stdout

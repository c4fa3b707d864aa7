"""Every example the README points at runs to its end with exit code
0 (an assertion inside one fails it)."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
EXAMPLES = ["quickstart", "serving_quickstart", "epidemic_case_study",
            "distributed_reachability"]


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    env = dict(os.environ)
    # distributed_reachability sets its own host device count
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = (str(ROOT / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, str(ROOT / "examples" / f"{name}.py")],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"

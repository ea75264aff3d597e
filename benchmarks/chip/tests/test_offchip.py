"""Off a TPU the benchmark exits non-zero and prints no result line."""

import json
import os
import subprocess
import sys

RUN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "run.py")


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "minicpm-2b.serve.chat",
         "--seed", str(2 ** 32 + 11), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj
    assert "TPU" in p.stderr

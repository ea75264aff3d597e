"""Hierarchical multi-pod collectives — correctness on an 8-device host mesh
(subprocess so the main test process keeps 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_PROG = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import (hierarchical_psum,
                                               hierarchical_psum_int8)
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((2, 4), ("pod", "data"))
    x = jnp.arange(512, dtype=jnp.float32).reshape(64, 8) / 7.0

    def flat_sum(v):
        return jax.lax.psum(v, ("pod", "data"))

    def hier_sum(v):
        return hierarchical_psum(v, intra_axis="data", inter_axis="pod")

    sm = lambda f: jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=P(("pod", "data")),
                                 check_vma=False)
    a = jax.jit(sm(flat_sum))(x)
    b = jax.jit(sm(hier_sum))(x)
    exact = float(jnp.max(jnp.abs(a - b)))

    # int8 EF variant: approximate, residual carries the error.
    # residual lives on the SCATTERED shard: (rows_per_device/|data|, cols)
    def hier_int8(v):
        r = jnp.zeros((v.shape[0] // 4, *v.shape[1:]), jnp.float32)
        out, new_r = hierarchical_psum_int8(v, r, intra_axis="data",
                                            inter_axis="pod")
        return out

    c = jax.jit(sm(hier_int8))(x)
    rel = float(jnp.max(jnp.abs(a - c)) / (jnp.max(jnp.abs(a)) + 1e-9))
    print(json.dumps({"exact_err": exact, "int8_rel_err": rel}))
""")


def test_hierarchical_psum_matches_flat():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _PROG], capture_output=True, text=True,
        env=env, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # reduction ORDER differs from flat psum (RS→AR→AG) → f32 rounding noise
    assert rec["exact_err"] < 1e-4
    assert rec["int8_rel_err"] < 0.02       # int8 quantization error bound

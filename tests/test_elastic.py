"""Elastic scaling: a checkpoint saved under one topology restores onto a
DIFFERENT mesh (the node-failure / pod-resize story).  Checkpoints are
layout-free logical arrays + named sharding rules, so restore = device_put
with whatever mesh is alive."""

import json
import os
import subprocess
import sys
import textwrap

_PROG = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.checkpoint import restore, save
    from repro.configs import smoke_config
    from repro.core import Lake
    from repro.distributed import param_specs, named
    from repro.launch.mesh import auto_mesh
    from repro.models import init_params, forward

    cfg = smoke_config("paper-demo")
    params = init_params(cfg, jax.random.PRNGKey(0))
    lake = Lake("{lake_dir}")
    if "t.run" not in lake.catalog.branches():
        lake.catalog.create_branch("t.run", "main", author="t")

    # "train" on an 8-device (4 data × 2 model) mesh and checkpoint
    mesh_a = auto_mesh((4, 2), ("data", "model"))
    specs_a = param_specs(cfg, mesh_a)
    p_a = jax.tree.map(jax.device_put, params, named(mesh_a, specs_a))
    commit = save(lake, "t.run", step=1, params=p_a, author="t")

    # cluster shrinks: restore onto a 2-device mesh (2 data × 1 model)
    from jax.sharding import Mesh
    mesh_b = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                  ("data", "model"))
    specs_b = param_specs(cfg, mesh_b)
    p_b, _, meta = restore(lake, commit, mesh=mesh_b, param_specs=specs_b)

    # same logical values, new physical layout; forward output identical
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    with mesh_a:
        la, _, _ = forward(cfg, p_a, tok, remat=False)
    with mesh_b:
        lb, _, _ = forward(cfg, p_b, tok, remat=False)
    err = float(np.max(np.abs(np.asarray(la) - np.asarray(lb))))
    n_shards_b = len(p_b["embed"].sharding.device_set)
    print(json.dumps({"err": err, "step": meta["step"],
                      "n_devices_b": n_shards_b}))
""")


def test_restore_onto_different_mesh(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    prog = _PROG.replace("{lake_dir}", str(tmp_path / "lake"))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5
    assert rec["step"] == 1
    assert rec["n_devices_b"] == 2

"""Sharding rules + multi-device pjit correctness (8 fake CPU devices in
a subprocess so the main test process keeps its single real device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.distributed.sharding import logical_to_mesh_spec, set_rules


class _FakeMesh:
    def __init__(self, names):
        self.axis_names = tuple(names)


def test_logical_mapping_drops_missing_axes():
    spec = logical_to_mesh_spec(("batch", None, "d_ff"),
                                _FakeMesh(["data", "model"]))
    assert spec == __import__("jax").sharding.PartitionSpec(
        ("data",), None, "model")


def test_logical_mapping_multi_axis_batch():
    spec = logical_to_mesh_spec(("batch", "d_ff"),
                                _FakeMesh(["pod", "data", "model"]))
    assert spec[0] == ("pod", "data")
    assert spec[1] == "model"


def test_rules_override_scoped():
    mesh = _FakeMesh(["data", "model"])
    with set_rules({"seq": "model"}):
        spec = logical_to_mesh_spec(("batch", "seq"), mesh)
        assert spec[1] == "model"
    spec2 = logical_to_mesh_spec(("batch", "seq"), mesh)
    assert spec2[1] is None


def test_no_duplicate_mesh_axes():
    """The same mesh axis must never appear twice in one spec."""
    mesh = _FakeMesh(["data", "model"])
    with set_rules({"seq": "data"}):   # batch also wants data
        spec = logical_to_mesh_spec(("batch", "seq"), mesh)
    used = []
    for s in spec:
        if s is None:
            continue
        used.extend([s] if isinstance(s, str) else list(s))
    assert len(used) == len(set(used))


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import (make_train_step, params_shardings,
                                    opt_state_shardings, batch_shardings)
    from repro.models import model as M
    from repro.optimizer.adamw import AdamWConfig, adamw_init

    cfg = get_config("smollm_360m", smoke=True)
    opt_cfg = AdamWConfig(lr=1e-3)
    mesh = make_mesh((4, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "mask": jnp.ones((8, 32), jnp.float32),
    }
    # single-device reference
    params = M.init_params(cfg, key)
    opt_state = adamw_init(params, opt_cfg)
    step = make_train_step(cfg, opt_cfg)
    p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

    # sharded execution on the 4x2 mesh
    with mesh:
        p_sh = params_shardings(cfg, mesh)
        o_sh = opt_state_shardings(cfg, mesh)
        b_sh = batch_shardings(cfg, mesh, 8, False)
        sharded = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None))
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put(opt_state, o_sh)
        batch_s = jax.device_put(batch, b_sh)
        p2, o2, m2 = sharded(params_s, opt_s, batch_s)

    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                     b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(p1),
                               jax.tree_util.tree_leaves(p2)))
    print(json.dumps({
        "loss_single": float(m1["loss"]),
        "loss_sharded": float(m2["loss"]),
        "max_param_diff": diff,
        "n_devices": jax.device_count(),
    }))
""")


@pytest.mark.slow
def test_pjit_matches_single_device():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 8
    assert abs(res["loss_single"] - res["loss_sharded"]) < 2e-2
    assert res["max_param_diff"] < 2e-2

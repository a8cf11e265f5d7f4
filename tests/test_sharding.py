"""Sharding rules engine + multi-device pjit smoke (subprocess with forced
host device count, since the main test process has already initialized the
single-device backend)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro import sharding


class _FakeMesh:
    """Duck-typed mesh: only .shape (dict) is consulted by spec_for."""
    def __init__(self, shape):
        self.shape = shape


MESH = _FakeMesh({"data": 16, "model": 16})
MESH3 = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_spec_basic():
    spec = sharding.spec_for(("w_embed", "w_mlp"), (1024, 8192), MESH,
                             sharding.RULES_BASELINE)
    assert spec == PartitionSpec(None, "model")


def test_divisibility_fallback():
    # 9 heads over a 16-way axis: must drop to replicated, not crash
    spec = sharding.spec_for(("act_heads",), (9,), MESH,
                             {"act_heads": "model"})
    assert spec == PartitionSpec()


def test_axis_reuse_guard():
    # two dims both wanting `model`: the second must be dropped
    spec = sharding.spec_for(("w_mlp", "w_vocab"), (256, 256), MESH,
                             sharding.RULES_BASELINE)
    assert spec == PartitionSpec("model")


def test_multi_axis_batch():
    spec = sharding.spec_for(("act_batch", "act_seq"), (256, 4096), MESH3,
                             sharding.RULES_BASELINE)
    assert spec == PartitionSpec(("pod", "data"))
    # single-pod mesh: the pod name is filtered out
    spec2 = sharding.spec_for(("act_batch",), (256,), MESH,
                              sharding.RULES_BASELINE)
    assert spec2 == PartitionSpec("data")


def test_fsdp_rules_shard_contraction_dims():
    spec = sharding.spec_for(("w_embed", "w_mlp"), (1024, 8192), MESH,
                             sharding.RULES_FSDP)
    assert spec == PartitionSpec("data", "model")


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert sharding.constrain(x, "act_batch", "act_seq") is x


@pytest.mark.slow
def test_pjit_train_step_8_devices():
    """Real pjit on 8 forced host devices (2x4 data x model) - a miniature
    of the production dry-run, executed (not just compiled)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses, json
        from repro import configs, sharding
        from repro.configs.base import TrainConfig, ShapeConfig
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.optim import adamw_init

        cfg = dataclasses.replace(configs.smoke("llama3.2-1b"),
                                  d_model=64, d_ff=128)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeConfig("t", "train", 32, 4)
        with jax.set_mesh(mesh), sharding.use(mesh, "fsdp"):
            in_sh, out_sh, args, _ = steps.shardings_for_cell(
                cfg, shape, mesh, "fsdp")
            fn = steps.make_train_step(cfg, TrainConfig(warmup_steps=1))
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            params, _ = T.init(cfg, jax.random.PRNGKey(0))
            opt = adamw_init(params)
            batch = {
                "tokens": jnp.zeros((4, 32), jnp.int32),
                "labels": jnp.ones((4, 32), jnp.int32),
                "mask": jnp.ones((4, 32), jnp.float32),
            }
            params = jax.device_put(params, in_sh[0])
            opt = jax.device_put(opt, in_sh[1])
            batch = jax.device_put(batch, in_sh[2])
            p2, o2, metrics = jitted(params, opt, batch)
            print(json.dumps({"loss": float(metrics["loss"]),
                              "devices": len(jax.devices())}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["devices"] == 8
    assert result["loss"] > 0


@pytest.mark.slow
def test_train_honours_mesh_4_devices(tmp_path):
    """train(mesh=...) places parameters, optimizer state and batches by the
    rule table and matches the one-device run's losses."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
        import dataclasses, json
        from repro import configs
        from repro.configs.base import TrainConfig
        from repro.launch import steps
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import train

        meshes = []
        cell = steps.shardings_for_cell
        steps.shardings_for_cell = lambda c, s, mesh, rules: (
            meshes.append(dict(mesh.shape)) or cell(c, s, mesh, rules))
        cfg = dataclasses.replace(configs.smoke("smollm-135m"), d_model=64,
                                  d_ff=128, n_heads=4, n_kv_heads=2)
        out = {{}}
        for name, mesh in (("one", None), ("mesh", make_host_mesh())):
            tc = TrainConfig(ckpt_dir={str(tmp_path)!r} + "/" + name,
                             ckpt_policy="none", warmup_steps=2,
                             total_steps=4)
            out[name] = train(cfg, tc, total_steps=4, seq_len=32,
                              global_batch=8, mesh=mesh,
                              verbose=False).losses
        out["meshes"] = meshes
        print(json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["meshes"] == [{"data": 4, "model": 1}]
    assert len(res["mesh"]) == len(res["one"]) == 4
    # f32 reductions split over 4 devices reorder sums
    np.testing.assert_allclose(res["mesh"], res["one"], rtol=1e-4)

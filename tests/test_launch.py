"""Launch-layer tests: abstract specs, analytics, HLO collective parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analytics, configs
from repro.configs import SHAPES
from repro.configs.base import ShapeConfig, TrainConfig
from repro.launch import hlo_stats, steps


def test_abstract_init_no_allocation():
    """abstract_init on a 33B config must be instant (pure eval_shape)."""
    cfg = configs.get("deepseek-coder-33b")
    shapes, axes = steps.abstract_init(cfg)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    total = sum(int(np.prod(l.shape)) for l in leaves)
    assert total == cfg.param_count()
    # axes tree mirrors params
    ax_leaves = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    assert len(ax_leaves) == len(leaves)


@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_cover_all_archs(shape_name):
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        spec = steps.input_specs(cfg, SHAPES[shape_name])
        assert "batch" in spec
        b = spec["batch"]
        if cfg.embeds_input:
            assert "embeds" in b and b["embeds"].shape[-1] == cfg.d_model
        else:
            assert "tokens" in b
        if cfg.pos_type == "mrope":
            assert b["positions"].shape[0] == 3
        if SHAPES[shape_name].kind != "train":
            assert "cache" in spec


def test_train_step_grad_accum_equivalence():
    """accum=2 must give (numerically) the same update as accum=1."""
    import dataclasses
    cfg = dataclasses.replace(configs.smoke("llama3.2-1b"),
                              compute_dtype="float32")
    from repro.models import transformer as T
    from repro.optim import adamw_init
    params, _ = T.init(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    B, S = 4, 16
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                     cfg.vocab_size),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    f1 = steps.make_train_step(cfg, TrainConfig(warmup_steps=1, grad_accum=1))
    f2 = steps.make_train_step(cfg, TrainConfig(warmup_steps=1, grad_accum=2))
    p1, _, m1 = jax.jit(f1)(params, opt, batch)
    p2, _, m2 = jax.jit(f2)(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_analytics_train_flops_scale():
    """Analytic FLOPs/chip x chips ~ 4 x forward; 6ND ratio sane."""
    cfg = configs.get("llama3.2-1b")
    shape = SHAPES["train_4k"]
    cost = analytics.cell_cost(cfg, shape, chips=256, rules="fsdp")
    roof = analytics.roofline(cost, chips=256)
    assert 0.05 < roof["model_flops_ratio"] <= 1.0
    assert roof["step_time_est"] > 0
    # total model flops across chips == 6*N*D
    total_useful = cost.model_flops * 256
    np.testing.assert_allclose(
        total_useful, 6 * cfg.active_param_count() * shape.global_batch
        * shape.seq_len, rtol=1e-6)


def test_analytics_decode_memory_bound():
    """32k-cache decode must be memory/collective bound, never compute."""
    cfg = configs.get("yi-34b")
    cost = analytics.cell_cost(cfg, SHAPES["decode_32k"], chips=256,
                               rules="fsdp")
    roof = analytics.roofline(cost, chips=256)
    assert roof["dominant"] in ("memory", "collective")


def test_hlo_collective_parser_on_real_module():
    """Parse a real partitioned module with a known all-reduce."""
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec

    @jax.jit
    def f(x):
        return jax.lax.with_sharding_constraint(
            x.sum(0, keepdims=True), NamedSharding(mesh, PartitionSpec()))

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    hlo = jax.jit(lambda x: x @ x.T).lower(x).compile().as_text()
    stats = hlo_stats.collective_bytes(hlo)
    assert stats.total_bytes >= 0  # parser must not crash on any module


def test_hlo_parser_trip_counts():
    """Collectives inside a scanned body must be multiplied by trip count."""
    hlo = """
HloModule test

%cond.1 (p: (s32[], f32[16])) -> pred[] {
  %p = (s32[], f32[16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (p: (s32[], f32[16])) -> (s32[], f32[16]) {
  %p = (s32[], f32[16]) parameter(0)
  %x = f32[16] get-tuple-element(%p), index=1
  %ar = f32[16]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[16]) tuple(%i, %ar)
}

ENTRY %main (a: f32[16]) -> f32[16] {
  %a = f32[16] parameter(0)
  %init = (s32[], f32[16]) tuple(s32[] constant(0), %a)
  %w = (s32[], f32[16]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[16] get-tuple-element(%w), index=1
}
"""
    stats = hlo_stats.collective_bytes(hlo)
    assert stats.count_by_kind.get("all-reduce") == 1
    # 16 floats * 4 bytes * 12 trips
    np.testing.assert_allclose(stats.bytes_by_kind["all-reduce"],
                               16 * 4 * 12)


@pytest.mark.parametrize("env", [None, "given"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The entry points' cache goes to $JAX_COMPILATION_CACHE_DIR when set
    (left for JAX to read), else to a fixed <checkout>/.jax_cache."""
    from repro import compile_cache
    old = jax.config.jax_compilation_cache_dir
    want = str(tmp_path / "cache") if env else None
    if env:
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable()
        if env:
            assert got == want
            assert jax.config.jax_compilation_cache_dir == old
        else:
            assert got == str(compile_cache.CHECKOUT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_train_calls_share_one_step_function():
    """train() calls that differ only in the run's own fields (seed,
    checkpoints, fleet) get one step function, so jax.jit finds the program
    it loaded; any field the step may read, known or new, splits them."""
    import dataclasses
    from repro.launch import train as TR
    cfg = configs.smoke("llama3.2-1b")
    tc = TrainConfig(warmup_steps=1)
    run = dataclasses.replace(tc, seed=9, ckpt_dir="elsewhere",
                              ckpt_policy="none", async_checkpoint=False)
    assert TR._train_step(cfg, tc) is TR._train_step(cfg, run)
    for field, value in (("learning_rate", 1e-3), ("grad_accum", 2),
                         ("moe_bias_rate", 0.0)):
        other = dataclasses.replace(tc, **{field: value})
        assert TR._train_step(cfg, other) is not TR._train_step(cfg, tc)
    from repro.models import transformer as T
    _, axes = T.init(cfg, jax.random.PRNGKey(0))
    assert TR._train_step(cfg, tc, param_axes=axes) \
        is TR._train_step(cfg, run, param_axes=axes) \
        is not TR._train_step(cfg, tc)

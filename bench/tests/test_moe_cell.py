"""The ``moonlight-train`` cell: it resolves to its files, a CPU run at test
sizes reads ``correct: true``, the control fails a limit, and runs with the
expert layer, the routing-bias update or the restore broken underneath read
``correct: false``."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import common, run
from bench.tests import small_moe

CELL = "moonlight-train"


def _run(capsys, seed=6000000003):
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "1", "--trace", "0"], spec=small_moe.spec(),
                    require_tpu=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fails_a_limit(checks: dict, limits: dict) -> bool:
    return any(not checks[k] <= v for k, v in limits.items() if k in checks)


def test_cell_resolves_to_its_files():
    s = common.resolve(CELL)
    assert s["config_entry"]["name"] == "moonlight-16b-a3b-ep8"
    assert s["reference"].name == "moonlight-16b-a3b-ep8_reference.py"
    assert s["generator"].name == "train_moe.py"
    assert set(s["limits"]) == {"loss_gap", "grad_norm_gap",
                                "update_norm_gap", "rows_gap",
                                "bias_step_gap", "dropped_rows",
                                "restore_mismatch"}
    names = {m["name"] for m in s["per_layer"]}
    assert {"train_mfu", "idle_share.train", "expert_gmm_roofline"} <= names
    assert s["config"]["reduced"] == s["config_entry"]["reduced"]


def test_sound_run_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] and out["failed"] == 0, out


def test_control_fails():
    s = small_moe.spec()
    drv = common.load_module(s["generator"])
    ref = common.load_module(s["reference"])
    kw = dict(n_steps=s["mix"]["check_steps"],
              total_steps=s["mix"]["total_steps"])
    f32 = ref.train(s["config"], s["mix"]["data"], 17, **kw)
    fp8 = ref.train(s["config"], s["mix"]["data"], 17, mode="fp8", **kw)
    assert _fails_a_limit(drv.compare(fp8, f32), s["limits"])


def _expert_dropped(monkeypatch):
    """The first held expert's rows come back zero from every product."""
    from repro.kernels import moe_gmm
    real = moe_gmm.gmm

    def gmm(lhs, rhs, sizes, **kw):
        out = real(lhs, rhs, sizes, **kw)
        return jnp.where((jnp.arange(out.shape[0]) < sizes[0])[:, None], 0,
                         out)

    monkeypatch.setattr(moe_gmm, "gmm", gmm)


def _biased_weights(monkeypatch):
    """Selection weights from the biased scores."""
    from repro.models import moe as M

    def route(cfg, p, xt):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        choose = scores + p[M.BIAS][None, :]
        _, idx = jax.lax.top_k(choose, cfg.top_k)
        w = jnp.take_along_axis(choose, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * cfg.routed_scale, scores

    monkeypatch.setattr(M, "route", route)


def _token_dropped(monkeypatch):
    """The first token with a held expert is left out of the expert layer
    (its pairs pointed at an expert not held), as a capacity cut would."""
    from repro.models import moe as M
    real = M.held_experts

    def held_experts(cfg, p, xt, idx, w):
        first, H = cfg.held
        held = (idx >= first) & (idx < first + H)
        t = jnp.argmax(jnp.any(held, axis=-1))
        other = (first + H) % cfg.n_experts
        return real(cfg, p, xt, idx.at[t].set(other), w)

    monkeypatch.setattr(M, "held_experts", held_experts)


def _bias_not_updated(monkeypatch):
    """The aux-loss-free update skipped: every routing bias stays put."""
    from repro.models import moe as M
    monkeypatch.setattr(M, "update_bias", lambda b, load, rate: b)


def _restore_alters_bias(monkeypatch):
    from repro.checkpoint import manager
    real = manager.restore_latest

    def altered(directory, template):
        out = real(directory, template)
        if out is None:
            return out
        (params, opt), step, meta = out
        g = dict(params["groups"][0])
        g["moe"] = dict(g["moe"], e_score_correction_bias=g["moe"]
                        ["e_score_correction_bias"] + 1e-3)
        params = dict(params, groups=[g])
        return (params, opt), step, meta

    monkeypatch.setattr(manager, "restore_latest", altered)


@pytest.mark.parametrize("fault", [_expert_dropped, _biased_weights,
                                   _token_dropped, _bias_not_updated,
                                   _restore_alters_bias],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_run_fails(fault, capsys, monkeypatch):
    fault(monkeypatch)
    out = _run(capsys)
    assert not out["correct"], out["checks"]

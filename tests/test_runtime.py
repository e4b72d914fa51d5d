"""Runtime layer: hetero async executor, fault tolerance, elastic resharding,
gradient compression."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import Compressor, MethodConfig, init_train_state, make_method
from repro.data import PipelineConfig, TokenPipeline
from repro.models import build_model
from repro.runtime import (AsyncSamExecutor, ExecutorConfig, InjectedFailure,
                           ResilienceConfig, run_resilient)
from repro.utils import trees


def _mlp_loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"])
    logits = h @ params["w2"]
    onehot = jax.nn.one_hot(batch["y"], logits.shape[-1])
    loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
    return loss, {"logits": logits}


def _mlp_params(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w1": jax.random.normal(k, (8, 32)) * 0.3,
            "w2": jax.random.normal(jax.random.fold_in(k, 1), (32, 4)) * 0.3}


def _batch(seed=0, n=64):
    k = jax.random.PRNGKey(100 + seed)
    return {"x": jax.random.normal(k, (n, 8)),
            "y": jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, 4)}


# ---------------------------------------------------------------------------
# async executor (paper Form B)
# ---------------------------------------------------------------------------

def test_executor_steady_state_tau_is_one():
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.1, momentum=0.9)
    method = make_method(mcfg)
    state = init_train_state(_mlp_params(), opt, method, jax.random.PRNGKey(1))
    with AsyncSamExecutor(_mlp_loss, mcfg, opt) as ex:
        first_loss = None
        for i in range(25):
            state, m = ex.step(state, _batch(i))
            if first_loss is None:
                first_loss = float(m["loss"])
        summary = ex.ledger.summary()
    assert summary["tau"] == 1
    assert summary["refreshes"] >= 20
    assert summary["sgd_fallbacks"] == 0
    assert float(m["loss"]) < first_loss


def test_executor_straggler_grows_tau_then_falls_back_to_sgd():
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.05)
    method = make_method(mcfg)
    state = init_train_state(_mlp_params(), opt, method, jax.random.PRNGKey(1))
    # calibrate the injected straggle against THIS machine's step time so the
    # test stays deterministic under CPU contention: the helper must be far
    # slower than the descent lane
    probe = AsyncSamExecutor(_mlp_loss, mcfg, opt)
    t0 = time.perf_counter()
    state, _ = probe.step(state, _batch(0))
    state, _ = probe.step(state, _batch(1))
    step_s = (time.perf_counter() - t0) / 2
    probe.close()
    xcfg = ExecutorConfig(max_staleness=2,
                          ascent_delay_s=max(0.5, 10.0 * step_s))
    with AsyncSamExecutor(_mlp_loss, mcfg, opt, xcfg) as ex:
        fallbacks = 0
        for i in range(12):
            state, m = ex.step(state, _batch(i))
            fallbacks += m["perturbed"] == 0.0
        summary = ex.ledger.summary()
    # helper ~10x slower than a step: reuse crosses max_staleness => SGD steps
    assert summary["stale_reuses"] > 0 or summary["sgd_fallbacks"] > 0 \
        or fallbacks > 0
    assert np.isfinite(float(m["loss"]))


def test_executor_calibration_returns_sane_fraction():
    mcfg = MethodConfig(name="async_sam", ascent_fraction=0.5)
    opt = optim.sgd(0.05)
    method = make_method(mcfg)
    state = init_train_state(_mlp_params(), opt, method, jax.random.PRNGKey(1))
    with AsyncSamExecutor(_mlp_loss, mcfg, opt) as ex:
        frac = ex.calibrate(state, _batch(0))
    assert 0.05 <= frac <= 1.0


# ---------------------------------------------------------------------------
# fault tolerance: crash-restart equivalence
# ---------------------------------------------------------------------------

def _make_lm_run(tmp_path, n_steps, injector=None, subdir="a"):
    cfg = get_config("olmo-1b", reduced=True)
    bundle = build_model(cfg)
    mcfg = MethodConfig(name="async_sam", rho=0.02, ascent_fraction=0.5)
    method = make_method(mcfg)
    opt = optim.adamw(1e-3)
    params = bundle.init(jax.random.PRNGKey(0))
    state = init_train_state(params, opt, method, jax.random.PRNGKey(1))
    step = jax.jit(method.make_step(bundle.loss_fn, opt))
    pipe = TokenPipeline(cfg, PipelineConfig(global_batch=4, seq_len=16,
                                             ascent_fraction=0.5, prefetch=0))
    mgr = CheckpointManager(tmp_path / subdir, keep=3)
    return run_resilient(step, state, pipe, mgr, n_steps,
                         ResilienceConfig(save_every=5, async_save=False),
                         failure_injector=injector)


def test_crash_restart_reaches_identical_state(tmp_path):
    clean = _make_lm_run(tmp_path, 20, subdir="clean")

    crashed = {"done": False}

    def injector(step):
        if step == 12 and not crashed["done"]:
            crashed["done"] = True
            raise InjectedFailure("simulated node loss at step 12")

    faulty = _make_lm_run(tmp_path, 20, injector=injector, subdir="faulty")
    assert faulty.restarts == 1
    assert faulty.steps_done == clean.steps_done == 20
    # deterministic pipeline + step => bitwise identical final parameters
    assert jax.tree.all(jax.tree.map(
        lambda a, b: jnp.array_equal(a, b),
        clean.final_state.params, faulty.final_state.params))


def test_restart_budget_exhaustion_raises(tmp_path):
    def always_fail(step):
        raise InjectedFailure("dead node")

    with pytest.raises(RuntimeError, match="restart budget"):
        cfg = get_config("olmo-1b", reduced=True)
        bundle = build_model(cfg)
        mcfg = MethodConfig(name="sgd")
        method = make_method(mcfg)
        opt = optim.sgd(0.01)
        params = bundle.init(jax.random.PRNGKey(0))
        state = init_train_state(params, opt, method, jax.random.PRNGKey(1))
        step = jax.jit(method.make_step(bundle.loss_fn, opt))
        pipe = TokenPipeline(cfg, PipelineConfig(global_batch=2, seq_len=8,
                                                 prefetch=0))
        mgr = CheckpointManager(tmp_path / "x", keep=1)
        run_resilient(step, state, pipe, mgr, 10,
                      ResilienceConfig(save_every=5, max_restarts=2,
                                       async_save=False),
                      failure_injector=always_fail)


# ---------------------------------------------------------------------------
# elastic resharding across meshes (subprocess: needs >1 device)
# ---------------------------------------------------------------------------

def test_elastic_reshard_roundtrip(subprocess_py):
    out = subprocess_py("""
        import jax, jax.numpy as jnp
        AUTO = (jax.sharding.AxisType.Auto,) * 2
        from repro.configs import get_config
        from repro.models import build_model
        from repro.runtime import reshard_state
        from repro.core import MethodConfig, make_method, init_train_state
        from repro import optim

        cfg = get_config('olmo-1b', reduced=True)
        bundle = build_model(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        method = make_method(MethodConfig(name='async_sam'))
        opt = optim.adamw(1e-3)
        state = init_train_state(params, opt, method, jax.random.PRNGKey(1))

        mesh_a = jax.make_mesh((4, 2), ('data', 'model'), axis_types=AUTO)
        mesh_b = jax.make_mesh((2, 4), ('data', 'model'), axis_types=AUTO)
        on_a = reshard_state(state, cfg, mesh_a)
        on_b = reshard_state(on_a, cfg, mesh_b)
        back = jax.device_get(on_b)
        orig = jax.device_get(state)
        ok = jax.tree.all(jax.tree.map(
            lambda x, y: jnp.array_equal(x, y), orig.params, back.params))
        print('RESHARD_OK', bool(ok))
    """, devices=8)
    assert "RESHARD_OK True" in out


# ---------------------------------------------------------------------------
# gradient compression with error feedback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_compressor_error_feedback_preserves_signal(kind):
    comp = Compressor(kind=kind, topk_fraction=0.25)
    key = jax.random.PRNGKey(0)
    g = {"w": jax.random.normal(key, (256,))}
    state = comp.init(g)
    # accumulated quantized signal tracks accumulated true signal (EF property)
    acc_q = jnp.zeros(256)
    acc_g = jnp.zeros(256)
    for i in range(30):
        gi = {"w": jax.random.normal(jax.random.fold_in(key, i), (256,))}
        q, state = comp.compress(gi, state)
        acc_q += q["w"]
        acc_g += gi["w"]
    # residual is bounded, so mean error -> 0 over time
    err = float(jnp.linalg.norm(acc_q - acc_g) / jnp.linalg.norm(acc_g))
    assert err < 0.25, err


def test_compressor_wire_bytes_ordering():
    g = {"w": jnp.zeros((1000,))}
    none_b = Compressor("none").wire_bytes(g)
    int8_b = Compressor("int8").wire_bytes(g)
    topk_b = Compressor("topk", topk_fraction=0.01).wire_bytes(g)
    assert topk_b < int8_b < none_b


def test_executor_with_compressed_ascent_exchange():
    """int8 ascent hand-off: training still descends, wire bytes ~1/4 of fp32."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5,
                        compressor="int8")
    opt = optim.sgd(0.1, momentum=0.9)
    method = make_method(mcfg)
    state = init_train_state(_mlp_params(), opt, method, jax.random.PRNGKey(1))
    with AsyncSamExecutor(_mlp_loss, mcfg, opt) as ex:
        first = None
        for i in range(20):
            state, m = ex.step(state, _batch(i))
            if first is None:
                first = float(m["loss"])
        wire = ex.wire_bytes_per_exchange
    n_params = sum(x.size for x in jax.tree.leaves(_mlp_params()))
    assert wire < 0.3 * 4 * n_params      # ~int8 payload vs fp32
    assert float(m["loss"]) < first

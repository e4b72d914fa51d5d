"""Fused flat-buffer weight-space path ≡ per-leaf path.

The fused path (utils.buckets + optim.fused + the single-pass kernels) must be
a drop-in for the per-leaf chain: same opt_state layout, same numbers (exact
summation-order tolerance for fp32 params; bf16 params differ only by the
per-leaf path's intermediate bf16 round-trips, which the fp32 kernels skip).
On CPU the kernels dispatch to the jnp oracles (ops._resolve), so these tests
exercise the full bucketing + chain-recognition + state-rebuild machinery.

The bucket-RESIDENT tests additionally pin the PR-4 invariants: a resident
step traces with ZERO gather/scatter conversion copies, steps allocate no
extra device buffers, and pytree-shaped checkpoints round-trip through
resident executors bitwise (per-leaf save -> resident restore and back).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.core import MethodConfig, init_train_state, make_method
from repro.core.perturb import perturb
from repro.engine import Engine, FusedExecutor, StalenessTelemetry
from repro.optim import configure_fused
from repro.optim.fused import epilogue_hbm_bytes, fused_apply
from repro.utils import buckets, trees

KEY = jax.random.PRNGKey(0)

F32_TOL = dict(rtol=5e-5, atol=5e-6)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _params(dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    return {"w": jax.random.normal(ks[0], (37, 5)).astype(dtype) * 0.3,
            "b": jnp.zeros((5,), dtype),
            "emb": jax.random.normal(ks[1], (11, 3)).astype(dtype)}


def _grads(params, seed=1):
    k = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(k, x.size),
                                    x.shape).astype(x.dtype), params)


def _loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _batch(seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"x": jax.random.normal(ks[0], (16, 37)),
            "y": jax.random.normal(ks[1], (16, 5))}


def _allclose_trees(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


# ---------------------------------------------------------------------------
# buckets: layout + roundtrip
# ---------------------------------------------------------------------------

def test_bucket_roundtrip_mixed_dtypes():
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.arange(5, dtype=jnp.bfloat16),
            "c": {"d": jnp.ones((2, 2), jnp.float32)}}
    layout = buckets.bucket_layout(tree)
    assert len(layout.groups) == 2          # one bucket per dtype
    bufs = buckets.tree_to_buckets(tree, layout)
    assert sum(b.shape[0] for b in bufs) == trees.tree_size(tree)
    back = buckets.buckets_to_tree(bufs, layout, tree)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_bucket_layout_is_cached():
    tree = _params()
    assert buckets.bucket_layout(tree) is buckets.bucket_layout(
        jax.tree.map(lambda x: x + 1, tree))


def test_congruent_tree_buckets_by_param_layout():
    """An all-fp32 gradient tree follows a mixed-dtype param grouping."""
    params = {"w": jnp.ones((4, 4), jnp.bfloat16), "b": jnp.ones((3,))}
    grads = jax.tree.map(lambda x: jnp.full(x.shape, 2.0, jnp.float32), params)
    layout = buckets.bucket_layout(params)
    gb = buckets.tree_to_buckets(grads, layout)
    assert [b.dtype for b in gb] == [jnp.float32] * len(gb)
    assert sorted(b.shape[0] for b in gb) == [3, 16]


def test_bucketed_reductions_match_tree_ops():
    a, b = _params(), _grads(_params())
    np.testing.assert_allclose(float(buckets.bucketed_sq_norm(a)),
                               float(trees.tree_sq_norm(a)), rtol=1e-6)
    dot, sa, sb = buckets.bucketed_dot_norms(a, b)
    np.testing.assert_allclose(float(dot), float(trees.tree_dot(a, b)),
                               rtol=1e-5)
    np.testing.assert_allclose(float(sa), float(trees.tree_sq_norm(a)),
                               rtol=1e-6)
    np.testing.assert_allclose(float(sb), float(trees.tree_sq_norm(b)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# perturb: fused vs per-leaf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_perturb_fused_matches_per_leaf(dtype, tol):
    params = _params(dtype)
    grad = trees.tree_cast(_grads(_params()), jnp.float32)
    ref = perturb(params, grad, 0.1, fused=False)
    got = perturb(params, grad, 0.1, fused=True)
    assert all(x.dtype == dtype for x in jax.tree.leaves(got))
    _allclose_trees(ref, got, **tol)
    # carried-norm variant (the AsyncSAM call shape)
    norm = trees.global_norm(grad)
    _allclose_trees(perturb(params, grad, 0.1, grad_norm=norm, fused=False),
                    perturb(params, grad, 0.1, grad_norm=norm, fused=True),
                    **tol)


# ---------------------------------------------------------------------------
# optimizer epilogue: fused_apply vs per-leaf chain
# ---------------------------------------------------------------------------

OPTIMIZERS = {
    "sgd_plain": lambda: optim.sgd(0.1),
    "sgd_full": lambda: optim.sgd(0.1, momentum=0.9, nesterov=True,
                                  weight_decay=1e-4, clip_norm=1.0),
    "sgd_mom_wd": lambda: optim.sgd(optim.cosine_schedule(0.1, 50),
                                    momentum=0.9, weight_decay=5e-4),
    "adamw": lambda: optim.adamw(0.01, clip_norm=0.5),
    "adamw_nowd": lambda: optim.adamw(0.01, weight_decay=0.0),
}


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_fused_apply_matches_per_leaf_chain(name):
    params = _params()
    grads = _grads(params)
    opt = OPTIMIZERS[name]()
    st1 = st2 = opt.init(params)
    p1 = p2 = params
    for _ in range(4):
        upd, st1 = opt.update(grads, st1, p1)
        p1 = optim.apply_updates(p1, upd)
        out = fused_apply(configure_fused(opt, True), grads, st2, p2)
        assert out is not None
        p2, st2, gnorm = out
    assert jax.tree.structure(st1) == jax.tree.structure(st2)
    _allclose_trees(p1, p2, **F32_TOL)
    _allclose_trees(st1, st2, **F32_TOL)
    np.testing.assert_allclose(float(gnorm), float(trees.global_norm(grads)),
                               rtol=1e-6)


def test_fused_apply_declines_unrecognized_chains():
    params = _params()
    grads = _grads(params)
    hand_built = optim.chain(optim.scale_by_adam(),
                             optim.scale_by_learning_rate(0.01))
    assert fused_apply(configure_fused(hand_built, True), grads,
                       hand_built.init(params), params) is None
    masked = optim.adamw(0.01, decay_mask=lambda path: "w" in path)
    assert masked.fused_spec is None
    # disabled spec declines too
    opt = optim.adamw(0.01)
    assert fused_apply(configure_fused(opt, False), grads,
                       opt.init(params), params) is None


def test_fused_default_is_off_on_cpu():
    assert not buckets.fused_path_enabled(None)
    assert buckets.fused_path_enabled(True)


# ---------------------------------------------------------------------------
# end-to-end: method steps, fused vs per-leaf (sgd/adamw x sam/async_sam)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sam", "async_sam"])
@pytest.mark.parametrize("opt_name,opt_kw", [
    ("sgd", dict(momentum=0.9, weight_decay=1e-4, clip_norm=1.0)),
    ("adamw", dict(clip_norm=1.0)),
])
def test_method_steps_fused_matches_per_leaf(method, opt_name, opt_kw):
    params = _params()
    batch = _batch()
    results = []
    for fused in (False, True):
        mcfg = MethodConfig(name=method, rho=0.05, fused_update=fused)
        opt = configure_fused(optim.make_optimizer(opt_name, 0.05, **opt_kw),
                              fused)
        m = make_method(mcfg)
        state = init_train_state(params, opt, m, jax.random.PRNGKey(3))
        step = jax.jit(m.make_step(_loss_fn, opt))
        metrics = None
        for _ in range(5):
            state, metrics = step(state, batch)
        results.append((state, metrics))
    (s1, m1), (s2, m2) = results
    assert jax.tree.structure(s1) == jax.tree.structure(s2)
    _allclose_trees(s1, s2, **F32_TOL)
    for k in ("loss", "grad_norm"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    if method == "async_sam":
        for k in ("ascent_norm", "ascent_cosine"):
            np.testing.assert_allclose(float(m1[k]), float(m2[k]),
                                       rtol=1e-4, atol=1e-5)


def test_fused_executor_flag_resolution_and_fit():
    """fused_update=True on the executor drives the loss down like False.

    A forced-fused executor goes bucket-RESIDENT by default (the buffers are
    the source of truth); its final params are viewed back to the pytree
    shape for the comparison.
    """
    params = _params()
    batches = [_batch(seed=s) for s in range(20)]
    finals = {}
    for fused in (False, True):
        ex = FusedExecutor(_loss_fn, MethodConfig(name="async_sam", rho=0.05),
                           optim.adamw(0.01, clip_norm=1.0),
                           donate=False, fused_update=fused)
        assert ex.fused_update is fused
        assert ex.resident is fused     # resident follows the resolved switch
        with ex:
            state = ex.init_state(params, jax.random.PRNGKey(0))
            assert buckets.is_resident(state.params) is fused
            report = Engine(ex, batches).fit(state, 20)
        assert report.metrics_history[-1]["loss"] < report.metrics_history[0]["loss"]
        finals[fused] = report.final_state
    _allclose_trees(finals[False].params,
                    buckets.to_portable(finals[True].params), **F32_TOL)


def test_fused_executor_default_off_on_cpu():
    ex = FusedExecutor(_loss_fn, MethodConfig(name="sgd"), optim.sgd(0.1))
    assert ex.fused_update is False
    ex.close()


# ---------------------------------------------------------------------------
# telemetry jsonl sink
# ---------------------------------------------------------------------------

def test_staleness_telemetry_jsonl_sink(tmp_path):
    path = tmp_path / "telemetry" / "run.jsonl"
    tele = StalenessTelemetry(print_summary=False, jsonl_path=path)
    ex = FusedExecutor(_loss_fn, MethodConfig(name="async_sam", rho=0.05),
                       optim.sgd(0.05, momentum=0.9), donate=False)
    with ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(0))
        Engine(ex, [_batch(seed=s) for s in range(6)], [tele]).fit(state, 6)
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(records) == 6
    assert [r["step"] for r in records] == list(range(1, 7))
    for r in records:
        assert set(r) == {"step", "tau", "perturbed", "step_time_s", "loss"}
        assert r["loss"] is not None
    # steady state: tau=1 from the second step on (first step has no ascent)
    assert records[-1]["tau"] == 1


# ---------------------------------------------------------------------------
# bucket-resident state: buffer-to-buffer steps, no conversions, interop
# ---------------------------------------------------------------------------

def _resident_executor(method="async_sam", **kw):
    return FusedExecutor(_loss_fn, MethodConfig(name=method, rho=0.05),
                         optim.adamw(0.01, clip_norm=1.0),
                         fused_update=True, resident=True, **kw)


def test_resident_state_representation():
    ex = _resident_executor(donate=False)
    state = ex.init_state(_params(), jax.random.PRNGKey(0))
    assert buckets.is_bucketed(state.params)
    adam = state.opt_state[1]
    assert buckets.is_bucketed(adam.mu) and buckets.is_bucketed(adam.nu)
    assert buckets.is_bucketed(state.method_state.ascent_grad)
    # the view reproduces the exact pytree contract (structure/shape/dtype)
    view = buckets.to_portable(state.params)
    ref = _params()
    assert jax.tree.structure(view) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(view), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
    ex.close()


@pytest.mark.parametrize("method", ["sam", "async_sam"])
@pytest.mark.parametrize("opt_name,opt_kw", [
    ("sgd", dict(momentum=0.9, weight_decay=1e-4, clip_norm=1.0)),
    ("adamw", dict(clip_norm=1.0)),
])
def test_resident_matches_per_leaf(method, opt_name, opt_kw):
    """Bucket-resident fit == per-leaf fit across sgd/adamw x sam/async_sam."""
    params = _params()
    batches = [_batch(seed=s) for s in range(6)]
    finals, metrics = {}, {}
    for resident in (False, True):
        ex = FusedExecutor(_loss_fn, MethodConfig(name=method, rho=0.05),
                           optim.make_optimizer(opt_name, 0.05, **opt_kw),
                           donate=False, fused_update=resident,
                           resident=resident)
        with ex:
            state = ex.init_state(params, jax.random.PRNGKey(0))
            report = Engine(ex, batches).fit(state, 6)
        finals[resident] = buckets.to_portable(report.final_state)
        metrics[resident] = report.metrics_history[-1]
    assert jax.tree.structure(finals[False]) == jax.tree.structure(finals[True])
    _allclose_trees(finals[False], finals[True], **F32_TOL)
    np.testing.assert_allclose(metrics[False]["loss"], metrics[True]["loss"],
                               rtol=1e-5)


def test_resident_step_traces_with_zero_conversion_copies():
    """The whole resident step is buffer -> buffer: tracing it performs no
    tree_to_buckets/buckets_to_tree copies, while the same step over plain
    pytree state re-gathers buckets around every kernel call."""
    batch = _batch()
    realized = {}
    for resident in (False, True):
        ex = FusedExecutor(_loss_fn, MethodConfig(name="async_sam", rho=0.05),
                           optim.adamw(0.01, clip_norm=1.0), donate=False,
                           fused_update=True, resident=resident)
        sds = ex.abstract_state(_params, jax.random.PRNGKey(0))
        with buckets.track_copies() as stats:
            jax.eval_shape(ex._step_raw, sds, batch)
        realized[resident] = stats
        ex.close()
    assert realized[True].total_bytes == 0, realized[True]
    assert realized[True].gathers == realized[True].scatters == 0
    assert realized[False].gathers >= 4 and realized[False].scatters >= 2
    # the modeled resident=False overhead and the trace agree on the sign
    # and rough size of the gap (the model folds the fp32 ascent-grad gather
    # to param dtype, so exact equality is not expected)
    n = trees.tree_size(_params())
    modeled_gap = (epilogue_hbm_bytes(n, 4 * n, fused=True, resident=False)
                   - epilogue_hbm_bytes(n, 4 * n, fused=True, resident=True))
    assert 0.5 * modeled_gap <= realized[False].total_bytes <= 2.0 * modeled_gap


def test_resident_steps_allocate_no_extra_buffers():
    """Donated resident steps are allocation-neutral: after warmup, the count
    of live device arrays is identical from step to step (buffer in, buffer
    out — no gather/scatter temporaries survive, nothing accumulates)."""
    ex = _resident_executor(donate=True, block=True)
    state = ex.init_state(_params(), jax.random.PRNGKey(0))
    batches = [_batch(seed=s) for s in range(6)]
    metrics = None
    with ex:
        for b in batches[:2]:          # warmup: compile + constant caches
            state, metrics = ex.step(state, b)
        baseline = len(jax.live_arrays())
        for b in batches[2:]:
            state, metrics = ex.step(state, b)
            assert len(jax.live_arrays()) == baseline
    del metrics


def test_checkpoint_interop_per_leaf_and_resident(tmp_path):
    """Pytree checkpoints are the interchange format: a per-leaf (PR 1-3-era)
    save restores into a bucket-resident executor and resumes bitwise-equal
    to the directly-converted state; a resident save restores back into a
    per-leaf executor unchanged."""
    params = _params()
    batches = [_batch(seed=s) for s in range(8)]
    mcfg = MethodConfig(name="async_sam", rho=0.05)
    opt = lambda: optim.adamw(0.01, clip_norm=1.0)  # noqa: E731

    # --- per-leaf run to step 3, saved pytree-shaped (the PR 1-3 format)
    ex_pl = FusedExecutor(_loss_fn, mcfg, opt(), donate=False,
                          fused_update=False, resident=False)
    st_pl = ex_pl.init_state(params, jax.random.PRNGKey(0))
    for b in batches[:3]:
        st_pl, _ = ex_pl.step(st_pl, b)
    mgr = CheckpointManager(tmp_path / "ck", keep=3)
    mgr.save(3, st_pl)

    # --- restore into a bucket-resident executor via the portable edge
    ex_r = _resident_executor(donate=False)
    template = ex_r.init_state(params, jax.random.PRNGKey(0))
    like = jax.eval_shape(lambda: buckets.to_portable(template))
    restored, _ = mgr.restore(like, step=3)
    st_restored = buckets.residentize(restored, like=template)
    st_direct = buckets.residentize(st_pl, like=template)

    losses = {}
    finals = {}
    for tag, st in [("restored", st_restored), ("direct", st_direct)]:
        cur, ls = st, []
        for b in batches[3:6]:
            cur, m = ex_r.step(cur, b)
            ls.append(np.asarray(m["loss"]))
        losses[tag] = ls
        finals[tag] = cur
    # bitwise: restore went through .npy files but the values are identical,
    # and the resident steps are deterministic
    for a, b in zip(losses["restored"], losses["direct"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(buckets.to_portable(finals["restored"])),
                    jax.tree.leaves(buckets.to_portable(finals["direct"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # --- and back: resident state saves pytree-shaped, restores per-leaf
    mgr.save(6, buckets.to_portable(finals["restored"]))
    back, _ = mgr.restore(jax.eval_shape(lambda: st_pl), step=6)
    assert jax.tree.structure(back) == jax.tree.structure(st_pl)
    st_after, m = ex_pl.step(back, batches[6])
    assert np.isfinite(float(m["loss"]))
    ex_pl.close()
    ex_r.close()


def test_run_resilient_converts_resident_state_at_the_edge(tmp_path):
    """Engine.fit + CheckpointCallback on a resident executor writes pytree
    checkpoints (layout-stamped) and survives an injected crash by
    re-residentizing the restored state."""
    from repro.engine import CheckpointCallback
    from repro.runtime import ResilienceConfig

    class ListPipe(list):
        def state(self):
            return {"cursor": 0}

        def restore(self, s):
            pass

    batches = ListPipe([_batch(seed=s) for s in range(8)])
    boom = {"armed": True}

    def injector(step):
        if step == 5 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected")

    ex = _resident_executor(donate=False)
    state = ex.init_state(_params(), jax.random.PRNGKey(0))
    mgr = CheckpointManager(tmp_path / "ck", keep=5)
    cb = CheckpointCallback(mgr, ResilienceConfig(save_every=4,
                                                  async_save=False))
    with ex:
        report = Engine(ex, batches, [cb]).fit(state, 8,
                                               failure_injector=injector)
    assert report.steps_done == 8 and report.restarts == 1
    assert buckets.is_resident(report.final_state.params)
    # on-disk: pytree-shaped arrays + the layout stamp in the manifest
    d = mgr.root / "step_00000008"
    manifest = json.loads((d / "manifest.json").read_text())
    paths = [rec["path"] for rec in manifest["leaves"]]
    plain = FusedExecutor(_loss_fn, MethodConfig(name="async_sam", rho=0.05),
                          optim.adamw(0.01, clip_norm=1.0), donate=False,
                          fused_update=False)
    plain_paths = trees.tree_paths(
        plain.init_state(_params(), jax.random.PRNGKey(0)))
    plain.close()
    assert paths == plain_paths
    assert manifest["extras"]["bucket_layout"], "resident saves are stamped"


# ---------------------------------------------------------------------------
# modeled epilogue bytes (perf_cell artifact contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,param_bytes_per", [("adamw", 4),
                                                    ("adamw", 2),
                                                    ("sgd", 4)])
def test_modeled_epilogue_reduction_at_least_2x(family, param_bytes_per):
    n = 1_000_000
    kw = dict(family=family, clip=True, weight_decay=True,
              carried_norm=True)
    unfused = epilogue_hbm_bytes(n, param_bytes_per * n, fused=False, **kw)
    fused = epilogue_hbm_bytes(n, param_bytes_per * n, fused=True, **kw)
    assert unfused / fused >= 2.0, (family, param_bytes_per, unfused / fused)


@pytest.mark.parametrize("family", ["adamw", "sgd"])
@pytest.mark.parametrize("carried_norm", [True, False])
def test_modeled_nonresident_fused_forfeits_the_win(family, carried_norm):
    """resident=False models the gather/scatter-per-call regime: the kernels'
    reduction is eaten by conversion copies (~1x unfused or worse) — exactly
    the gap bucket residency closes."""
    n = 1_000_000
    kw = dict(family=family, clip=True, weight_decay=True, momentum=True,
              carried_norm=carried_norm)
    unfused = epilogue_hbm_bytes(n, 4 * n, fused=False, **kw)
    ceiling = epilogue_hbm_bytes(n, 4 * n, fused=True, resident=True, **kw)
    realized = epilogue_hbm_bytes(n, 4 * n, fused=True, resident=False, **kw)
    assert ceiling < unfused
    assert realized > ceiling
    # the non-resident "win" is no better than ~1.1x of per-leaf
    assert unfused / realized < 1.1, (family, carried_norm, unfused / realized)


def test_bucketed_primitives_accept_threaded_layout_and_resident_operands():
    a, b = _params(), _grads(_params())
    layout = buckets.bucket_layout(a)
    # threading the cached layout changes nothing numerically
    np.testing.assert_allclose(
        float(buckets.bucketed_sq_norm(a, layout)),
        float(buckets.bucketed_sq_norm(a)), rtol=1e-6)
    d1 = buckets.bucketed_dot_norms(a, b, layout=layout)
    d2 = buckets.bucketed_dot_norms(a, b)
    for x, y in zip(d1, d2):
        np.testing.assert_allclose(float(x), float(y), rtol=1e-6)
    # resident operands use their own buffers — same numbers, zero gathers
    ra = buckets.BucketedState.from_tree(a, layout)
    rb = buckets.BucketedState.from_tree(b, layout)
    with buckets.track_copies() as stats:
        d3 = buckets.bucketed_dot_norms(ra, rb)
        sq = buckets.bucketed_sq_norm(ra)
    assert stats.gathers == 0
    for x, y in zip(d3, d2):
        np.testing.assert_allclose(float(x), float(y), rtol=1e-6)
    np.testing.assert_allclose(float(sq), float(trees.tree_sq_norm(a)),
                               rtol=1e-6)
    # resident axpy stays resident
    out = buckets.bucketed_axpy(jnp.float32(0.5), rb, ra)
    assert buckets.is_bucketed(out)
    _allclose_trees(out.to_tree(),
                    jax.tree.map(lambda x, y: 0.5 * y + x, a, b), **F32_TOL)


def test_chip_kernel_selection_trains_on_cpu(monkeypatch):
    """Rehearsal of what a TPU selects: the fused bucket-resident Form A step
    with every kernel a Pallas kernel (interpret mode here), AdamW with a
    clip, on a transformer whose sequence fills whole attention blocks.
    Differentiating through the attention kernel is what used to fail."""
    from repro.configs import get_config
    from repro.data import PipelineConfig, TokenPipeline
    from repro.kernels import ops
    from repro.models import build_model

    monkeypatch.setattr(ops, "_FORCED_IMPL", "pallas_interpret")
    cfg = get_config("olmo-1b", reduced=True)
    bundle = build_model(cfg)
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.25)
    pipe = TokenPipeline(cfg, PipelineConfig(
        global_batch=2, seq_len=128, ascent_fraction=0.25, prefetch=0))
    with FusedExecutor(bundle.loss_fn, mcfg, optim.adamw(1e-3, clip_norm=1.0),
                       fused_update=True, resident=True) as ex:
        assert ex.fused_update and ex.resident
        state = ex.init_state(bundle.init(KEY), jax.random.PRNGKey(1))
        report = Engine(ex, pipe).fit(state, 2)
    hist = report.metrics_history
    assert report.steps_done == 2
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(h["grad_norm"] > 0 for h in hist)
    assert [h["perturbed"] for h in hist] == [0.0, 1.0]

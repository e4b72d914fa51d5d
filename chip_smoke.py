"""Chip smoke: OLMo-1B at its published widths trains on a TPU via Engine.fit.

    python chip_smoke.py              # one chip: Form A AsyncSAM (default)
    python chip_smoke.py --chips 4    # four chips: data parallel vs one chip,
                                      # then Form B across tpu:0 / tpu:1

Only depth is cut (`n_layers` 16 -> LAYERS) so that parameters, AdamW
moments and the carried ascent gradient fit one v5e's 16 GB; every width is
the published one and the weights are random from --seed. The one-chip run:

  1. refuses anything but a TPU (no CPU fallback, no result printed);
  2. compares the Pallas kernels with the jnp path on the chip: attention at
     the model's widths, the mamba2 and rwkv6 scans at published head widths,
     the whole forward (logits and loss) on one batch at the initial params,
     and the weight-space reductions over the real parameter buckets;
  3. builds TokenPipeline -> FusedExecutor (host mesh) -> Engine.fit, the way
     `repro.launch.train` does, with async_sam + AdamW on a cosine schedule;
  4. checks finite losses, that the ascent ran (perturbed == 1 after step 1),
     that the fused bucket-resident update path was chosen, and that the
     compiled step holds Pallas kernels (tpu_custom_call).

With --chips 4 it trains data parallel on a (4, 1) mesh and on one chip from
the same params and batches, and compares the pre-clip gradient norm of each
step and each parameter leaf's Adam first moment; then Form B runs.

Times printed here are smoke timings of a few steps, not a benchmark. The
last line of stdout is the JSON verdict; any failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import gc
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "olmo-1b"
LAYERS = 3               # n_layers of the cut (published: 16)
BATCH, SEQ = 2, 2048     # global batch; sequence = the published context
STEPS = 8
LR = 3e-4
ASCENT_FRACTION = 0.25
# data parallel: one sequence per chip, as many tokens per step as the cut
DP_BATCH, DP_SEQ, DP_STEPS = 4, 1024, 4

# Pallas vs jnp on one chip (bf16 compute). Relative L2 errors, except the
# loss; each limit is 3-10x the gap measured on a v5e. The bf16 logits move
# about 10x the attention's own error, so a wrong attention output (zeroed,
# doubled or noise) moves them by 0.7-1.4 and the loss by 7.6e-4 or more.
ATTN_RTOL = 5e-3         # attention output
SCAN_RTOL = 1e-2         # mamba2 / rwkv6 scan outputs and final states
LOGITS_RTOL = 5e-2       # logits of the whole forward
LOSS_RTOL = 2e-4         # forward loss
REDUCE_RTOL = 1e-5       # fp32 sq_norm / dot_norms over the param buckets
# 4-chip data parallel vs one chip. A per-device cotangent scaled by the
# mesh size moves the grad norm 5x and each leaf's first moment by 0.72-0.78.
DP_LOSS_RTOL = 1e-4      # per-step loss
DP_GNORM_RTOL = 1e-2     # per-step pre-clip gradient norm
# Each leaf's Adam first moment (its clipped gradients, decay-summed) and
# not its parameter change: Adam steps by about sign(g) * lr, which hides a
# gradient's scale. bf16 compute alone puts the two meshes' gradients 1.5e-2
# apart, in every leaf alike (so their norms agree to about 1e-4).
DP_MOMENT_RTOL = 1e-1


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel and Form B phases")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


class StepLog:
    """Engine callback: per-step loss, grad norm, perturbed flag, wall time."""

    def __init__(self):
        self.rows: list[dict] = []

    def on_fit_start(self, engine, state):
        pass

    def on_step(self, engine, state, metrics, step_time_s):
        row = {"step": int(state.step), "wall_s": step_time_s, **{
            k: float(metrics[k])
            for k in ("loss", "grad_norm", "perturbed", "tau")}}
        self.rows.append(row)
        print(f"  step {row['step']}: loss {row['loss']:.6f} grad_norm "
              f"{row['grad_norm']:.6f} perturbed {row['perturbed']:.0f} tau "
              f"{row['tau']:.0f} wall {row['wall_s']:.4f}s (smoke timing)")

    def on_fit_end(self, engine, report):
        pass


def main() -> int:
    args = parse_args()
    import jax

    from repro.launch.compile_cache import use_checkout_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); refusing to "
              "fall back", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cache_dir = use_checkout_compile_cache()
    cache = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache.update(
            [event.rsplit("/", 1)[-1]] if "compilation_cache" in event else []))
    print(f"compile cache: {cache_dir}")

    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    print(f"config: {ARCH} n_layers 16 -> {cfg.n_layers} (depth only), "
          f"d_model {cfg.d_model}, heads {cfg.n_heads}x"
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"tied {cfg.tie_embeddings}, remat {cfg.remat}, "
          f"{cfg.compute_dtype} compute / {cfg.param_dtype} params; "
          f"{cfg.param_count() / 1e9:.3f} B params")
    try:
        if args.chips == 1:
            one_chip(args, cfg)
        else:
            four_chips(args, cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"compile cache events: {dict(cache)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def _train_parts(args, cfg, *, batch: int, seq: int, steps: int):
    from repro.core import MethodConfig
    from repro.data import PipelineConfig, TokenPipeline
    from repro.optim import cosine_schedule, make_optimizer

    mcfg = MethodConfig(name="async_sam", rho=0.05,
                        ascent_fraction=ASCENT_FRACTION)
    optimizer = make_optimizer(
        "adamw", cosine_schedule(LR, steps, warmup_steps=1), clip_norm=1.0)
    pipe = TokenPipeline(cfg, PipelineConfig(
        global_batch=batch, seq_len=seq, seed=args.seed,
        ascent_fraction=ASCENT_FRACTION))
    return mcfg, optimizer, pipe


def _fit(executor, pipe, state, steps: int):
    """Engine.fit for `steps`; returns (per-step rows, final state)."""
    from repro.engine import Engine
    log = StepLog()
    with Engine(executor, pipe, [log]) as eng:
        report = eng.fit(state, steps)
    if report.steps_done != steps:
        raise SmokeFailure(f"fit stopped at step {report.steps_done}/{steps}")
    return log.rows, report.final_state


def _rel(a, b) -> float:
    """Relative L2 error ||a - b|| / ||b||, in fp32 on the device."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _compare(name: str, fn, inputs: tuple, rtol: float) -> None:
    """`fn(*inputs, impl=...)` with the Pallas kernel and the jnp path: every
    output must agree within `rtol` (relative L2)."""
    import jax
    got, want = (jax.tree.leaves(jax.jit(functools.partial(fn, impl=i))(
        *inputs)) for i in ("pallas", "jnp"))
    errs = [_rel(a, b) for a, b in zip(got, want)]
    print(f"  {name}: rel L2 " + ", ".join(f"{e:.3e}" for e in errs))
    check(max(errs) <= rtol, f"{name} agrees within rel {rtol}")


def reference(args, cfg, bundle, params, batch) -> None:
    """Pallas kernels vs the jnp path, on the chip."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.utils import buckets
    impls = ("pallas", "jnp")

    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed + 3), 16))

    def normal(shape, scale=1.0, dtype=jnp.float32):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    hd = cfg.resolved_head_dim
    _compare(f"attention {(BATCH, SEQ, cfg.n_heads, hd)} causal",
             ops.flash_attention,
             (normal((BATCH, SEQ, cfg.n_heads, hd), dtype=jnp.bfloat16),
              normal((BATCH, SEQ, cfg.n_kv_heads, hd), dtype=jnp.bfloat16),
              normal((BATCH, SEQ, cfg.n_kv_heads, hd), dtype=jnp.bfloat16)),
             ATTN_RTOL)
    # the scans at published head widths (zamba2-1.2b, rwkv6-7b): not on
    # OLMo's path, and compared on a chip nowhere else
    h = p = n = 64
    _compare(f"mamba2 scan (1, {SEQ}, {h}, {p}) y, final state",
             ops.mamba2_mix,
             (normal((1, SEQ, h, p), 0.5, jnp.bfloat16),
              jax.nn.softplus(normal((1, SEQ, h))),
              -jnp.exp(jnp.linspace(-1.0, 1.0, h)),
              normal((1, SEQ, 1, n), 0.3, jnp.bfloat16),
              normal((1, SEQ, 1, n), 0.3, jnp.bfloat16),
              jnp.full((h,), 0.5)),
             SCAN_RTOL)
    _compare(f"rwkv6 scan (1, {SEQ}, {h}, {p}) y, final state", ops.rwkv6_mix,
             (normal((1, SEQ, h, p), 0.5, jnp.bfloat16),
              normal((1, SEQ, h, p), 0.5, jnp.bfloat16),
              normal((1, SEQ, h, p), 0.5, jnp.bfloat16),
              -jnp.exp(normal((1, SEQ, h, p), 0.5) - 2.0),
              normal((h, p), 0.1)),
             SCAN_RTOL)

    fwd = {}
    rng = jax.random.PRNGKey(args.seed + 2)
    for impl in impls:
        ops.set_default_impl(impl)
        try:
            fwd[impl] = jax.jit(
                lambda p, b: (lambda loss, aux: (loss, aux["logits"]))(
                    *bundle.loss_fn(p, b, rng)))(params, batch)
        finally:
            ops.set_default_impl(None)
    (lp, zp), (lj, zj) = fwd["pallas"], fwd["jnp"]
    lp, lj = float(lp), float(lj)
    err = _rel(zp, zj)
    print(f"  forward loss: pallas {lp:.6f} jnp {lj:.6f} rel diff "
          f"{abs(lp - lj) / abs(lj):.3e}; logits {tuple(zp.shape)} rel L2 "
          f"{err:.3e}")
    del fwd, zp, zj
    check(math.isfinite(lp) and math.isfinite(lj),
          "forward losses are finite")
    check(math.isclose(lp, lj, rel_tol=LOSS_RTOL),
          f"forward losses agree within rel {LOSS_RTOL}")
    check(err <= LOGITS_RTOL, f"logits agree within rel {LOGITS_RTOL}")

    # the AdamW clip and SAM perturb norm (sq_norm) and the AsyncSAM refresh
    # (dot_norms) over the parameter buckets, against a second real-sized tree
    key = next(keys)
    other = jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(key, x.shape, x.dtype), params)
    red = {i: [float(x) for x in jax.jit(lambda p, o, i=i: (
        buckets.bucketed_sq_norm(p, impl=i),
        *buckets.bucketed_dot_norms(p, o, impl=i)))(params, other)]
        for i in impls}
    names = ("sq_norm", "dot", "dot_norms |a|^2", "dot_norms |b|^2")
    errs = [abs(a - b) / abs(b) for a, b in zip(red["pallas"], red["jnp"])]
    for n, a, b, e in zip(names, red["pallas"], red["jnp"], errs):
        print(f"  {n}: pallas {a!r} jnp {b!r} rel diff {e:.3e}")
    check(max(errs) <= REDUCE_RTOL,
          f"bucket reductions agree within rel {REDUCE_RTOL}")


def one_chip(args, cfg) -> None:
    import jax
    from repro.engine import FusedExecutor
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model

    print(f"cut: n_layers 16 -> {cfg.n_layers}, global batch {BATCH}, "
          f"seq {SEQ}, ascent batch b'/b {ASCENT_FRACTION}")
    bundle = build_model(cfg)
    mcfg, optimizer, pipe = _train_parts(args, cfg, batch=BATCH, seq=SEQ,
                                         steps=STEPS)
    params = bundle.init(jax.random.PRNGKey(args.seed))
    batch = pipe.peek()

    print("[reference] Pallas kernels vs the jnp path")
    reference(args, cfg, bundle, params, batch)
    gc.collect()

    print("[train] FusedExecutor (Form A) async_sam + adamw via Engine.fit")
    mesh = make_host_mesh()
    executor = FusedExecutor(bundle.loss_fn, mcfg, optimizer, mesh=mesh,
                             model_cfg=cfg)
    print(f"  mesh {dict(mesh.shape)}, fused_update {executor.fused_update}, "
          f"resident {executor.resident}")
    check(executor.fused_update and executor.resident,
          "executor resolved fused_update=True and resident=True")
    state = executor.init_state(params, jax.random.PRNGKey(args.seed + 1))
    del params
    t0 = time.perf_counter()
    compiled = executor.compile_step(state, batch)
    print(f"  step compile {time.perf_counter() - t0:.2f}s (set-up, not a "
          "speed)")
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels > 0,
          f"compiled step holds Pallas kernels ({n_kernels} tpu_custom_call)")
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"  compiled step memory: args {mem.argument_size_in_bytes} "
              f"temp {mem.temp_size_in_bytes} bytes")
    del compiled

    rows, _ = _fit(executor, pipe, state, STEPS)
    check(all(math.isfinite(r["loss"]) for r in rows), "every loss is finite")
    check(rows[0]["perturbed"] == 0.0 and
          all(r["perturbed"] == 1.0 for r in rows[1:]),
          "perturbed == 1 on every step after the first (the ascent ran)")
    walls = [r["wall_s"] for r in rows[1:]]
    print(f"  smoke timing, not a benchmark: step 1 {rows[0]['wall_s']:.4f}s, "
          f"steps 2-{len(rows)} mean {sum(walls) / len(walls):.4f}s")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}")


def _adam_mu(opt_state):
    """The first moment (a tree like the params) of the AdamW state."""
    import jax
    from repro.optim.base import AdamState
    adam, = (s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, AdamState))
        if isinstance(s, AdamState))
    return adam.mu


def four_chips(args, cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import FusedExecutor, HeteroExecutor
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime import ExecutorConfig
    from repro.runtime.elastic import make_sized_mesh

    devices = jax.devices()
    bundle = build_model(cfg)
    print(f"cut: n_layers 16 -> {cfg.n_layers}, data parallel global batch "
          f"{DP_BATCH}, seq {DP_SEQ}; Form B batch {BATCH}, seq {SEQ}")
    init = jax.tree_util.tree_flatten_with_path(
        jax.device_get(bundle.init(jax.random.PRNGKey(args.seed))))
    paths = [jax.tree_util.keystr(p) for p, _ in init[0]]
    params0 = [np.asarray(x, np.float32) for _, x in init[0]]

    def fresh_params():
        return jax.tree.unflatten(init[1], [jnp.asarray(x) for x in params0])

    def dp_run(mesh):
        mcfg, optimizer, pipe = _train_parts(args, cfg, batch=DP_BATCH,
                                             seq=DP_SEQ, steps=DP_STEPS)
        # the per-leaf update path on both meshes, so the mesh is the only
        # difference (bucket-resident state needs an unsharded step)
        executor = FusedExecutor(bundle.loss_fn, mcfg, optimizer, mesh=mesh,
                                 model_cfg=cfg, fused_update=False,
                                 resident=False)
        state = executor.init_state(fresh_params(),
                                    jax.random.PRNGKey(args.seed + 1))
        sizes = collections.Counter(
            len(x.sharding.device_set) for x in jax.tree.leaves(state))
        print(f"  mesh {dict(mesh.shape)}: state leaves by device-set size "
              f"{dict(sizes)}")
        rows, final = _fit(executor, pipe, state, DP_STEPS)
        mu = jax.tree.leaves(jax.device_get(_adam_mu(final.opt_state)))
        del state, final
        gc.collect()
        return rows, mu, sizes

    print("[dp] FusedExecutor on a (4, 1) host mesh")
    mesh4 = make_host_mesh()
    check(mesh4.size == 4, f"host mesh spans 4 chips ({dict(mesh4.shape)})")
    rows4, mu4, sizes4 = dp_run(mesh4)
    check(sizes4.get(4, 0) == sum(sizes4.values()),
          "every state leaf is placed across all 4 chips")
    print("[dp] the same params and global batches on a one-device mesh of "
          "chip 0")
    rows1, mu1, _ = dp_run(make_sized_mesh(1))

    def rel_steps(key):
        return max(abs(a[key] - b[key]) / abs(b[key])
                   for a, b in zip(rows4, rows1))

    loss_err, gnorm_err = rel_steps("loss"), rel_steps("grad_norm")
    mom = sorted(((float(np.linalg.norm(a - b) / np.linalg.norm(b)), p)
                  for p, a, b in zip(paths, mu4, mu1)), reverse=True)
    print(f"  per-step max rel diff: loss {loss_err:.3e}, pre-clip grad_norm "
          f"{gnorm_err:.3e}")
    print(f"  Adam first moment after {DP_STEPS} steps, rel L2 per leaf: "
          "worst " + ", ".join(f"{p} {e:.3e}" for e, p in mom[:3])
          + f"; median {mom[len(mom) // 2][0]:.3e}")
    check(all(math.isfinite(r["loss"]) for r in rows4 + rows1),
          "losses are finite")
    check(loss_err <= DP_LOSS_RTOL,
          f"4-chip losses match the one-chip run within rel {DP_LOSS_RTOL}")
    check(gnorm_err <= DP_GNORM_RTOL,
          f"4-chip gradient norms match within rel {DP_GNORM_RTOL}")
    check(mom[0][0] <= DP_MOMENT_RTOL,
          f"every leaf's Adam first moment matches within rel {DP_MOMENT_RTOL}")
    del mu4, mu1

    print(f"[form B] HeteroExecutor: descent on {devices[0]}, ascent on "
          f"{devices[1]}")
    mcfg, optimizer, pipe = _train_parts(args, cfg, batch=BATCH, seq=SEQ,
                                         steps=DP_STEPS)
    xcfg = ExecutorConfig(descent_device=devices[0], ascent_device=devices[1],
                          lockstep=True)
    executor = HeteroExecutor(bundle.loss_fn, mcfg, optimizer, exec_cfg=xcfg)
    params = jax.device_put(fresh_params(), devices[0])
    state = executor.init_state(params, jax.random.PRNGKey(args.seed + 1))
    del params
    rows, _ = _fit(executor, pipe, state, DP_STEPS)
    perturbed = sum(r["perturbed"] for r in rows)
    check(all(math.isfinite(r["loss"]) for r in rows), "losses are finite")
    check(perturbed > 0, f"Form B produced {perturbed:.0f} perturbed steps")
    for d in devices[:2]:
        stats = d.memory_stats() or {}
        print(f"  {d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


if __name__ == "__main__":
    sys.exit(main())

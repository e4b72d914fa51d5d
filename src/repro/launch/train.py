"""Training launcher: --arch x --method x mesh -> fault-tolerant Engine run.

CPU-runnable end-to-end (reduced configs); the same launcher drives pod runs —
mesh construction, sharding, checkpointing and the resilient loop are the
production code paths exercised by the dry-run at full scale. Both executors
go through the same `Engine.fit`:

  --executor fused   one jitted SPMD step (Form A, pod-scale default)
  --executor hetero  two-lane heterogeneous executor (Form B, paper §3.3/§3.4);
                     add --calibrate for the system-aware b' pre-fit probe
  --executor remote  the hetero lanes across processes/hosts: ascent runs in a
                     `repro.service.ascent_server`; point --ascent-addr at a
                     running server, or pass --serve-ascent to spawn one as a
                     localhost subprocess (loopback smoke mode)

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 100 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 20 --executor hetero --calibrate
  # multi-host: on the helper host
  PYTHONPATH=src python -m repro.service.ascent_server \
      --loss arch:olmo-1b:reduced --bind 0.0.0.0:7431
  # ... and on the descent host
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 20 --executor remote --ascent-addr helper:7431
  # single-host loopback (server spawned as a subprocess)
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 20 --executor remote --serve-ascent
  # delta-encoded JOB payloads: ship int8 deltas against the server's params
  # shadow instead of full fp32 snapshots (~4x less wire out)
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 20 --executor remote --serve-ascent \
      --job-compress int8
  # elastic chaos run: shrink the mesh to 4 devices at step 40, grow back to
  # 8 at step 80, hard-preempt down to 2 at step 120 (restores from --ckpt-dir)
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 200 --elastic --chaos 40:4,80:8,120:2:crash \
      --ckpt-dir /tmp/ckpt --telemetry-jsonl /tmp/elastic.jsonl
  # fleet mode: several descent hosts sharing one multi-client ascent pool,
  # perturbing coherently via a `global` sync group (run per descent host)
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --method async_sam --steps 20 --executor remote \
      --ascent-addr pool-host:7431 --sync-group dp0 --auth-token "$TOKEN"
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core import MethodConfig
from repro.checkpoint import CheckpointManager
from repro.data import PipelineConfig, TokenPipeline
from repro.engine import (CheckpointCallback, Engine, FusedExecutor,
                          HeteroExecutor, LoggingCallback, RemoteExecutor,
                          StalenessTelemetry, ThroughputMeter)
from repro.launch.compile_cache import use_checkout_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import cosine_schedule, make_optimizer
from repro.runtime import ExecutorConfig, ResilienceConfig


def _parse_device(spec: str):
    """'cpu', 'cpu:1', 'tpu:0' ... -> the jax.Device (None for '')."""
    if not spec:
        return None
    platform, _, idx = spec.partition(":")
    devices = jax.devices(platform)
    return devices[int(idx) if idx else 0]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-trainable)")
    ap.add_argument("--method", default="async_sam")
    ap.add_argument("--executor", choices=("fused", "hetero", "remote"),
                    default="fused",
                    help="fused: one SPMD step; hetero: two-lane async_sam; "
                         "remote: ascent lane behind repro.service")
    ap.add_argument("--calibrate", action="store_true",
                    help="hetero/remote: measure the system-aware b'/b pre-fit")
    ap.add_argument("--ascent-addr", default="",
                    help="remote only: address of a running ascent server "
                         "('host:port' or 'unix:/path')")
    ap.add_argument("--serve-ascent", action="store_true",
                    help="remote only: spawn the ascent server as a localhost "
                         "subprocess (loopback mode; --ascent-addr optional)")
    ap.add_argument("--job-compress", choices=("none", "int8", "topk"),
                    default="none",
                    help="remote only: JOB-direction (params out) encoding. "
                         "'none' ships full fp32 snapshots (bitwise parity "
                         "with --executor hetero under lockstep); int8/topk "
                         "quantize the delta against the server's shadow of "
                         "the last-synced params (~4x less wire for int8)")
    ap.add_argument("--job-delta", choices=("on", "off"), default="on",
                    help="remote only: delta-encode JOB payloads against the "
                         "server's params shadow (off: every exchange ships "
                         "a full snapshot even with --job-compress set)")
    ap.add_argument("--pool-workers", type=int, default=0,
                    help="remote + --serve-ascent only: ascent workers in the "
                         "spawned pool server (0 = server default; a shared "
                         "pool serving several descent hosts wants >= 2)")
    ap.add_argument("--sync-group", default="",
                    help="remote only: `global` ascent-sync group name — "
                         "clients declaring the same group receive the "
                         "pool's shared LSAM-smoothed ascent gradient per "
                         "(generation, step), so data-parallel replicas "
                         "perturb coherently")
    ap.add_argument("--auth-token", default="",
                    help="remote only: shared secret presented in HELLO "
                         "(must match the pool server's --auth-token; "
                         "required for non-loopback deployments)")
    ap.add_argument("--netchaos", default="",
                    help="remote only: interpose service.netchaos.ChaosProxy "
                         "between the client and the ascent server and drive "
                         "it with this fault schedule — comma-separated "
                         "'action[:FRAME][:key=val...]', e.g. "
                         "'corrupt:GRAD:every=5,drop:JOB_DELTA:nth=7,"
                         "blackhole:GRAD:nth=9:duration_s=0.5' (actions: "
                         "corrupt, truncate, drop, delay, stall, blackhole, "
                         "duplicate). Local soak harness for the wire "
                         "hardening + the --lane-ladder response")
    ap.add_argument("--lane-ladder", action="store_true",
                    help="hetero/remote: health-driven degradation ladder — "
                         "an unhealthy/stalled ascent lane fails over one "
                         "rung (remote -> in-process thread -> ledger-only) "
                         "and recovers back up after a probationary cooldown; "
                         "transitions land in lane_state/lane_failovers/"
                         "lane_recoveries telemetry")
    ap.add_argument("--guard", action="store_true",
                    help="numerics guard (runtime.guard): in-step skip of "
                         "non-finite updates, loss-spike + stale-ascent "
                         "detection, a rho de-escalation ladder (halve rho "
                         "rung by rung down to plain descent, recover after "
                         "a probationary cooldown), and — with --ckpt-dir — "
                         "diverge-proof PoisonBatch rollback that restores "
                         "the model but advances the data cursor past the "
                         "poison window; telemetry lands in guard_state/"
                         "rho_scale/steps_skipped/poison_rollbacks")
    ap.add_argument("--numchaos", default="",
                    help="deterministic numerics-chaos injector over the "
                         "data stream: comma-separated 'kind[:key=val...]' "
                         "rules keyed on the batch cursor, e.g. "
                         "'nan_grad:nth=40:span=8,spike:prob=0.01:scale=1e4' "
                         "(kinds: nan_grad, inf_grad, spike). Poisons FLOAT "
                         "batch leaves only — token-only batches pass "
                         "through untouched. Soak harness for --guard")
    ap.add_argument("--watchdog", action="store_true",
                    help="remote + --serve-ascent only: STATS-scraping "
                         "server watchdog — restarts the loopback server "
                         "when it is dead or wedged (counters frozen with "
                         "work queued), under a bounded restart budget")
    ap.add_argument("--ascent-device", default="",
                    help="hetero only: device for the slow ascent lane, e.g. "
                         "'cpu:0' (paper's CPU helper on a CPU+accelerator host)")
    ap.add_argument("--descent-device", default="",
                    help="hetero only: device for the fast descent lane, e.g. "
                         "'tpu:0' or 'gpu:0'")
    ap.add_argument("--fused-update", choices=("auto", "on", "off"),
                    default="auto",
                    help="flat-buffer fused perturb + optimizer epilogue "
                         "(auto: on for TPU, off for CPU)")
    ap.add_argument("--resident", choices=("auto", "on", "off"),
                    default="auto",
                    help="bucket-resident training state: params/opt-state "
                         "persist as dtype buckets, the step runs buffer->"
                         "buffer (auto: follows the resolved fused path; "
                         "checkpoints stay pytree-shaped either way)")
    ap.add_argument("--elastic", action="store_true",
                    help="wrap the executor in ElasticExecutor: survive "
                         "mesh shrink/grow events mid-fit (graceful resizes "
                         "reshard the live state; crash events restore the "
                         "last checkpoint onto the survivors — those need "
                         "--ckpt-dir)")
    ap.add_argument("--chaos", default="",
                    help="elastic only: scripted MeshEvent schedule "
                         "'STEP:DEVICES[:crash],...' e.g. '40:4,80:8,"
                         "120:2:crash' (deterministic chaos harness; in "
                         "production a capacity watcher replaces this)")
    ap.add_argument("--resize-budget", type=int, default=8,
                    help="elastic only: resizes tolerated per window")
    ap.add_argument("--resize-window-s", type=float, default=0.0,
                    help="elastic only: rolling window for --resize-budget "
                         "(0 = lifetime)")
    ap.add_argument("--restart-window-s", type=float, default=0.0,
                    help="rolling window for the checkpoint-restart budget: "
                         "tolerate --max-restarts within this many seconds "
                         "instead of over the whole run (0 = lifetime; a "
                         "spot job wants e.g. 3600)")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="checkpoint-restart budget (per --restart-window-s "
                         "window when set)")
    ap.add_argument("--telemetry-jsonl", default="",
                    help="write per-step tau/perturbed/step-time records here")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace-event JSON here: "
                         "descent, ascent lane, pool workers, and elastic "
                         "resizes as named tracks (load at ui.perfetto.dev)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--rho", type=float, default=0.05)
    ap.add_argument("--ascent-fraction", type=float, default=0.25)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1,
                    help="TP width of the host mesh")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    lanes = args.executor in ("hetero", "remote")
    if lanes and args.model_axis != 1:
        ap.error("--model-axis applies to --executor fused only "
                 "(the hetero/remote lanes run meshless)")
    if args.calibrate and not lanes:
        ap.error("--calibrate requires --executor hetero or remote")
    if lanes and args.method != "async_sam":
        ap.error(f"--executor {args.executor} realizes async_sam only "
                 f"(got --method {args.method})")
    if (args.ascent_device or args.descent_device) and args.executor != "hetero":
        ap.error("--ascent-device/--descent-device apply to --executor hetero "
                 "only (the remote ascent device is the server's --device)")
    if (args.ascent_addr or args.serve_ascent) and args.executor != "remote":
        ap.error("--ascent-addr/--serve-ascent apply to --executor remote only")
    if ((args.job_compress != "none" or args.job_delta != "on")
            and args.executor != "remote"):
        ap.error("--job-compress/--job-delta apply to --executor remote only "
                 "(the JOB direction exists only on the wire)")
    if ((args.sync_group or args.auth_token or args.pool_workers)
            and args.executor != "remote"):
        ap.error("--pool-workers/--sync-group/--auth-token apply to "
                 "--executor remote only (they configure the ascent pool)")
    if args.pool_workers and not args.serve_ascent:
        ap.error("--pool-workers configures the spawned loopback server; "
                 "with --ascent-addr the pool size is the server's "
                 "--pool-workers")
    if args.executor == "remote" and not (args.ascent_addr or args.serve_ascent):
        ap.error("--executor remote needs --ascent-addr (a running "
                 "ascent server) or --serve-ascent (loopback subprocess)")
    if args.netchaos and args.executor != "remote":
        ap.error("--netchaos applies to --executor remote only (it attacks "
                 "the ascent wire)")
    if args.lane_ladder and args.executor not in ("hetero", "remote"):
        ap.error("--lane-ladder applies to --executor hetero or remote "
                 "(the fused executor has no ascent lane to degrade)")
    if args.watchdog and not args.serve_ascent:
        ap.error("--watchdog restarts the spawned loopback server; it needs "
                 "--serve-ascent (an external server is restarted by its "
                 "own supervisor)")
    if args.watchdog and args.netchaos:
        ap.error("--watchdog and --netchaos are mutually exclusive: under "
                 "--netchaos the launcher owns the server (behind the "
                 "proxy), so the executor's watchdog could not restart it")
    if args.chaos and not args.elastic:
        ap.error("--chaos needs --elastic (a non-elastic executor cannot "
                 "act on mesh resize events)")
    if args.elastic and args.chaos and not args.ckpt_dir:
        from repro.runtime import parse_schedule as _parse
        if any(e.kind == "crash" for e in _parse(args.chaos).pending):
            ap.error("crash-kind chaos events recover via checkpoint-restart "
                     "— add --ckpt-dir")

    use_checkout_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    bundle = build_model(cfg)
    mcfg = MethodConfig(name=args.method, rho=args.rho,
                        ascent_fraction=args.ascent_fraction,
                        n_microbatches=args.n_micro,
                        guard_update=args.guard)
    optimizer = make_optimizer(args.optimizer,
                               cosine_schedule(args.lr, args.steps,
                                               warmup_steps=args.steps // 20))

    pipe = TokenPipeline(cfg, PipelineConfig(
        global_batch=args.batch, seq_len=args.seq, seed=args.seed,
        ascent_fraction=(args.ascent_fraction
                         if args.method in ("async_sam",) else 0.0)))
    numchaos = None
    if args.numchaos:
        from repro.runtime import NumericChaosPipeline, parse_numchaos
        numchaos = parse_numchaos(args.numchaos, seed=args.seed)
        pipe = NumericChaosPipeline(pipe, numchaos)
        print(f"numchaos: {len(numchaos.rules)} rules over the batch stream")

    fused_update = {"auto": None, "on": True, "off": False}[args.fused_update]
    resident = {"auto": None, "on": True, "off": False}[args.resident]
    netchaos_proxy = netchaos_server = None
    if args.executor == "hetero":
        # two host lanes; hand-offs are host arrays, no mesh required.
        # --ascent-device/--descent-device place the lanes on real devices
        # (paper §3.3's CPU helper + accelerator on a two-device host).
        exec_cfg = ExecutorConfig(
            ascent_device=_parse_device(args.ascent_device),
            descent_device=_parse_device(args.descent_device),
            fused_update=fused_update, resident=resident,
            lane_ladder=args.lane_ladder)
        executor = HeteroExecutor(bundle.loss_fn, mcfg, optimizer,
                                  exec_cfg=exec_cfg,
                                  calibrate=args.calibrate)
    elif args.executor == "remote":
        # ascent lane behind repro.service: either a server the operator
        # already runs on another host, or a spawned loopback subprocess
        # holding the same arch/config (the wire carries params + b' batches
        # out and compressed ascent gradients back)
        loss_spec = f"arch:{args.arch}" + (":reduced" if args.reduced else "")
        upstream, serve = args.ascent_addr, args.serve_ascent
        if args.netchaos:
            # chaos soak: the client talks to the proxy, the proxy to the
            # real server — spawned here (not by RemoteExecutor) so the
            # proxy can interpose on the loopback path too
            from repro.service.ascent_server import spawn_server
            from repro.service.netchaos import ChaosProxy, parse_faults
            if serve:
                netchaos_server = spawn_server(
                    loss_spec, pool_workers=args.pool_workers,
                    auth_token=args.auth_token)
                upstream, serve = netchaos_server.addr, False
            netchaos_proxy = ChaosProxy(upstream,
                                        parse_faults(args.netchaos))
            upstream = netchaos_proxy.addr
            print(f"netchaos: proxy {netchaos_proxy.addr} -> "
                  f"{netchaos_proxy.upstream} "
                  f"({len(netchaos_proxy.schedule.rules)} fault rules)")
        exec_cfg = ExecutorConfig(ascent_addr=upstream,
                                  serve_ascent=serve,
                                  loss_spec=loss_spec,
                                  fused_update=fused_update,
                                  resident=resident,
                                  job_compress=args.job_compress,
                                  job_delta=(args.job_delta == "on"),
                                  pool_workers=args.pool_workers,
                                  sync_group=args.sync_group,
                                  auth_token=args.auth_token,
                                  lane_ladder=args.lane_ladder,
                                  watchdog=args.watchdog)
        executor = RemoteExecutor(bundle.loss_fn, mcfg, optimizer,
                                  exec_cfg=exec_cfg,
                                  calibrate=args.calibrate)
    else:
        mesh = make_host_mesh(model_axis=args.model_axis)
        executor = FusedExecutor(bundle.loss_fn, mcfg, optimizer,
                                 mesh=mesh, model_cfg=cfg,
                                 fused_update=fused_update,
                                 resident=resident)

    events = None
    if args.elastic:
        from repro.engine import ElasticExecutor
        from repro.runtime import parse_schedule
        executor = ElasticExecutor(
            executor, model_cfg=cfg, model_axis=args.model_axis,
            resize_budget=args.resize_budget,
            resize_window_s=args.resize_window_s or None)
        if args.chaos:
            events = parse_schedule(args.chaos)

    guard = None
    if args.guard:
        # outermost wrapper: the guard's verdict must cover everything below
        # (elastic resizes included); PoisonBatch rollback needs the
        # checkpoint-restart loop, so it arms only with --ckpt-dir
        from repro.engine import GuardConfig, GuardedExecutor
        guard = GuardedExecutor(executor,
                                GuardConfig(rollback=bool(args.ckpt_dir)))
        executor = guard

    # init_state shards/jits inside the executor's mesh scope (fused) so the
    # launcher never touches jit/sharding plumbing itself
    params = bundle.init(jax.random.PRNGKey(args.seed))
    state = executor.init_state(params, jax.random.PRNGKey(args.seed + 1))

    meter = ThroughputMeter(tokens_per_batch=args.batch * args.seq)
    callbacks = [LoggingCallback(every=args.log_every,
                                 total_steps=args.steps), meter]
    if args.executor in ("hetero", "remote") or args.telemetry_jsonl:
        callbacks.append(StalenessTelemetry(
            jsonl_path=args.telemetry_jsonl or None))
    if args.ckpt_dir:
        callbacks.append(CheckpointCallback(
            CheckpointManager(args.ckpt_dir, keep=3),
            ResilienceConfig(save_every=args.save_every,
                             max_restarts=args.max_restarts,
                             restart_window_s=args.restart_window_s or None,
                             require_finite_restore=args.guard)))

    tracker = None
    if args.trace:
        from repro.obs import TraceEventSink, Tracker
        tracker = Tracker([TraceEventSink(args.trace)])
    try:
        with Engine(executor, pipe, callbacks) as eng:
            report = eng.fit(state, args.steps, events=events,
                             tracker=tracker)
    finally:
        # launcher-owned netchaos plumbing (the executor tears down only
        # what it spawned itself)
        if netchaos_proxy is not None:
            netchaos_proxy.close()
        if netchaos_server is not None:
            netchaos_server.kill()
    if netchaos_proxy is not None:
        print(f"netchaos: {netchaos_proxy.connections} connections, "
              f"{netchaos_proxy.fault_count()} faults fired "
              f"{netchaos_proxy.schedule.fired_actions()}")
    if tracker is not None:
        tracker.close()
        print(f"trace written to {args.trace} (load at ui.perfetto.dev)")

    if report.pre_fit:
        pf = report.pre_fit
        print(f"calibration: configured b'/b="
              f"{pf['configured_ascent_fraction']:.3f}  system-aware b'/b="
              f"{pf['calibrated_ascent_fraction']:.3f}")
    if args.ckpt_dir:
        print(f"done: {report.steps_done} steps, {report.restarts} restarts, "
              f"{report.wall_time_s:.1f}s")
    if numchaos is not None:
        print(f"numchaos: fired {dict(numchaos.fired)}"
              + (f", {numchaos.skipped_no_float} no-float-leaf skips"
                 if numchaos.skipped_no_float else ""))
    if guard is not None:
        print(f"guard: rung {guard.ladder.level} "
              f"(rho_scale {guard.cfg.rho_scales[guard.ladder.level]}), "
              f"{guard.steps_skipped} updates skipped, "
              f"{guard.poison_rollbacks} poison rollbacks")
    summary = meter.summary()
    if summary:
        print(json.dumps({"arch": cfg.name, "method": args.method,
                          "executor": args.executor,
                          "steps": report.steps_done,
                          "mean_step_s": summary["mean_step_s"],
                          "tokens_per_s": summary.get("tokens_per_s")}))


if __name__ == "__main__":
    main()

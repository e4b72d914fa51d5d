"""Observability layer: typed metric-key registry, tracker/sinks, the
Chrome-trace exporter + overlap report, the STATS protocol frame, and the
jsonl byte-compatibility contract with the pre-registry StalenessTelemetry.

`scripts/tier1.sh --obs` runs this file (after the metric-registry lint)
under a hard timeout with interpret-mode kernels.
"""
import importlib.util
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.core import MethodConfig, slice_ascent_batch
from repro.data.synthetic import ClassificationTask
from repro.engine import (ElasticExecutor, Engine, FusedExecutor,
                          HeteroExecutor, RemoteExecutor, StalenessTelemetry)
from repro.obs import (ENGINE_METRIC_KEYS, ENGINE_OPTIONAL_METRIC_KEYS,
                       METRIC_KEYS, REGISTRY, JsonlSink, MemorySink, Tracker,
                       TraceEventSink, UnknownMetricError, current_tracker,
                       metric_key, registry_table, scalar_metrics,
                       use_tracker, validate_keys)
from repro.runtime import ChaosSchedule, ExecutorConfig, MeshEvent
from repro.service import protocol
from repro.service.ascent_server import AscentServer
from repro.service.client import fetch_pool_stats
from repro.service.protocol import (FrameType, ProtocolError,
                                    STATS_COUNTER_KEYS, decode_stats,
                                    encode_frame, encode_stats,
                                    stats_frame_bytes)
from repro.service.testing import mlp_init, mlp_loss

ROOT = pathlib.Path(__file__).resolve().parent.parent
TASK = ClassificationTask(n_classes=4, dim=8, seed=3)


def _loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"])
    logits = h @ params["w2"]
    onehot = jax.nn.one_hot(batch["y"], logits.shape[-1])
    loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
    return loss, {"logits": logits}


def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w1": jax.random.normal(k, (8, 32)) * 0.3,
            "w2": jax.random.normal(jax.random.fold_in(k, 1), (32, 4)) * 0.3}


def _batches(n, batch=64, frac=0.5):
    return [{**b, "ascent": slice_ascent_batch(b, frac)}
            for b in TASK.train_batches(batch, n)]


def _mcfg():
    return MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)


def _overlap_mod():
    """benchmarks/ is not a package: import overlap_report from its path."""
    spec = importlib.util.spec_from_file_location(
        "overlap_report", ROOT / "benchmarks" / "overlap_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# registry: derived contract tuples, lookups, strict validation
# ---------------------------------------------------------------------------

def test_contract_tuples_derive_to_historical_values():
    # byte-for-byte the tuples engine/api.py used to hard-code — order is
    # load-bearing for the jsonl schema and every downstream consumer
    assert ENGINE_METRIC_KEYS == ("loss", "grad_norm", "tau", "perturbed")
    assert ENGINE_OPTIONAL_METRIC_KEYS == (
        "wire_bytes", "job_bytes", "grad_bytes", "rtt_s", "pool_depth",
        "pool_wait_s", "client_id", "mesh_devices", "resize_events",
        "resize_time_s", "lane_state", "lane_failovers", "lane_recoveries",
        "guard_state", "rho_scale", "steps_skipped", "nonfinite_count",
        "poison_rollbacks")
    # the engine re-export keeps old imports working
    from repro.engine import ENGINE_METRIC_KEYS as legacy
    assert legacy is ENGINE_METRIC_KEYS


def test_registry_lookup_and_validation():
    assert metric_key("tau").required and metric_key("tau").source == "lane"
    with pytest.raises(UnknownMetricError):
        metric_key("nonesuch")
    validate_keys(["loss", "tau", "step_time_s"])
    with pytest.raises(UnknownMetricError, match="bogus"):
        validate_keys(["loss", "bogus"])
    table = registry_table()
    assert all(f"`{k.name}`" in table for k in METRIC_KEYS)


def test_strict_memory_sink_rejects_unregistered_key():
    strict = MemorySink(strict=True)
    strict.log({"loss": 1.0, "tau": 1}, step=0)           # registered: fine
    with pytest.raises(UnknownMetricError):
        strict.log({"loss": 1.0, "made_up_key": 2.0}, step=1)
    assert len(strict.steps) == 1
    relaxed = MemorySink(strict=False)
    relaxed.log({"made_up_key": 2.0}, step=0)             # tolerated
    assert relaxed.steps == [(0, {"made_up_key": 2.0})]


def test_lint_script_passes_on_tree():
    r = subprocess.run([sys.executable,
                        str(ROOT / "scripts" / "lint_metric_registry.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# tracker: global install, spans, and their profiler annotations
# ---------------------------------------------------------------------------

def test_use_tracker_scoped_install_and_null_default():
    base = current_tracker()
    assert base.log({"loss": 1.0}, step=0) is None        # null: cheap no-op
    trk = Tracker([MemorySink()])
    with use_tracker(trk) as active:
        assert current_tracker() is trk is active
    assert current_tracker() is base


def test_span_records_lane_args_and_survives_exceptions():
    sink = MemorySink()
    trk = Tracker([sink])
    with trk.span("descent_compute", lane="descent", step=7):
        pass
    with pytest.raises(RuntimeError):
        with trk.span("ascent_compute", lane="ascent-thread", gen=3):
            raise RuntimeError("boom")
    trk.span_at("ascent_exchange", lane="ascent-thread", t0=1.0, t1=1.5,
                tau=1)
    assert [s.name for s in sink.spans] == [
        "descent_compute", "ascent_compute", "ascent_exchange"]
    assert sink.spans_on("ascent")[0].args == {"gen": 3}
    assert sink.spans[2].duration_s == pytest.approx(0.5)
    assert sink.spans[0].args["step"] == 7


def _profiled(body) -> list:
    """Run `body` under a jax.profiler session; the host events named
    "repro.*" it recorded, as (name, stats, line index, start, end)."""
    import glob
    import tempfile
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        data = ProfileData.from_file(path)
    return [(e.name, dict(e.stats), i, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name == "/host:CPU"
            for i, line in enumerate(plane.lines) for e in line.events
            if e.name.startswith("repro.")]


@pytest.mark.parametrize("sinks", [0, 1], ids=["null", "memory"])
def test_span_lands_in_the_profiler_trace(sinks):
    sink = MemorySink()
    trk = Tracker([sink] * sinks)

    def body():
        with trk.span("ascent_rpc", lane="descent", wire_bytes=64, gen=3,
                      kind="job", tau=0.5, ok=True):
            pass

    events = _profiled(body)
    assert [(n, st) for n, st, *_ in events] == [
        ("repro.ascent_rpc", {"wire_bytes": 64, "gen": 3, "kind": "job",
                              "tau": 0.5, "ok": 1})]
    assert len(sink.spans) == sinks
    if sinks:
        assert sink.spans[0].args["wire_bytes"] == 64


def test_null_tracker_records_nothing_in_memory():
    null = current_tracker()
    assert null.sinks == []
    with null.span("train_step", lane="descent", step=1):
        pass
    null.span_at("ascent_exchange", lane="x", t0=0.0, t1=1.0)
    null.event("guard_skip", lane="guard")
    assert vars(null) == {"sinks": []}


def test_step_span_is_a_step_annotation():
    sink = MemorySink()
    trk = Tracker([sink])

    def body():
        with trk.span("step", lane="descent", step_num=7):
            with trk.span("readback", lane="descent", of="step", n=1):
                pass

    (step, st, line, s0, s1), (read, rt, line2, r0, r1) = _profiled(body)
    assert (step, read) == ("repro.step", "repro.readback")
    assert st["step_num"] == 7 and "_r" in st      # the profiler's step mark
    assert rt == {"of": "step", "n": 1}
    assert line == line2 and s0 <= r0 <= r1 <= s1
    assert [s.args for s in sink.spans] == [{"of": "step", "n": 1},
                                            {"step_num": 7}]


def test_span_annotation_reads_no_device_value():
    class Unprintable:
        def __str__(self):
            raise AssertionError("an annotation stat was formatted")

    sink = MemorySink()
    trk = Tracker([sink])
    value = jnp.ones(())

    def body():
        with trk.span("callbacks", lane="descent", loss=value,
                      other=Unprintable(), n=2):
            pass

    [(_, stats, *_)] = _profiled(body)
    assert stats == {"n": 2}
    assert sink.spans[0].args["loss"] is value      # sinks get every arg


def test_null_span_costs_microseconds_without_a_session():
    import time
    trk = current_tracker()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with trk.span("readback", lane="descent", of="step", n=1):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6


def test_fit_spans_the_loop_the_step_and_the_reads():
    from repro.data import PipelineConfig, TokenPipeline
    from repro.models import build_model
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                      act="silu", norm="nonparam_ln", tie_embeddings=True,
                      remat="none", compute_dtype="float32")
    bundle = build_model(cfg)
    sink = MemorySink()
    with FusedExecutor(bundle.loss_fn, _mcfg(), optim.sgd(0.1),
                       donate=False) as ex:
        state = ex.init_state(bundle.init(jax.random.PRNGKey(0)),
                              jax.random.PRNGKey(1))
        pipe = TokenPipeline(cfg, PipelineConfig(global_batch=2, seq_len=8,
                                                 ascent_fraction=0.5))
        Engine(ex, pipe).fit(state, 3, tracker=Tracker([sink]))
    names = [s.name for s in sink.spans]
    for name in ("data_next", "train_step", "dispatch", "device_wait",
                 "callbacks"):
        assert names.count(name) == 3, name
    assert [s.args["step_num"] for s in sink.spans if s.name == "step"] \
        == [0, 1, 2]
    reads = [s.args for s in sink.spans if s.name == "readback"]
    # the first test of the step counter, then per step: the step for the
    # train_step span, the metrics and the step for the log, the metrics
    # for the history, the step for the loop's next test
    assert len(reads) == 1 + 5 * 3
    assert {r["of"] for r in reads} == {"step", "metrics"}
    assert all(r["n"] == 1 for r in reads if r["of"] == "step")
    assert all(r["n"] >= 4 for r in reads if r["of"] == "metrics")
    # nested as the loop runs them: each train_step inside a step, and
    # dispatch and device_wait inside the train_step
    by = {n: [s for s in sink.spans if s.name == n] for n in set(names)}
    for loop, tstep, disp, wait in zip(by["step"], by["train_step"],
                                       by["dispatch"], by["device_wait"]):
        assert loop.t0 <= tstep.t0 <= disp.t0 <= disp.t1 <= wait.t0 \
            <= wait.t1 <= tstep.t1 <= loop.t1


# ---------------------------------------------------------------------------
# jsonl: sink byte-compatible with the pre-registry StalenessTelemetry
# ---------------------------------------------------------------------------

def _golden_record(step, metrics, step_time_s):
    """The record the pre-tracker StalenessTelemetry.on_step built inline."""
    loss = metrics.get("loss")
    rec = {"step": int(step),
           "tau": int(metrics.get("tau", 0)),
           "perturbed": float(metrics.get("perturbed", 0.0)),
           "step_time_s": step_time_s,
           "loss": float(loss) if loss is not None else None}
    for key in ("wire_bytes", "job_bytes", "grad_bytes", "rtt_s",
                "pool_depth", "pool_wait_s", "client_id", "mesh_devices",
                "resize_events", "resize_time_s"):
        if key in metrics:
            rec[key] = float(metrics[key])
    return json.dumps(rec)


def test_jsonl_sink_byte_compatible_with_historical_schema(tmp_path):
    rows = [
        (0, {"loss": 0.5, "tau": 0, "perturbed": 0.0, "grad_norm": 1.0},
         0.0123),
        (1, {"loss": 0.4, "tau": 1, "perturbed": 1.0, "grad_norm": 0.9,
             "wire_bytes": 4096.0, "job_bytes": 3072.0, "grad_bytes": 1024.0,
             "rtt_s": 0.002}, 0.011),
        (2, {"tau": 2, "perturbed": 1.0, "pool_depth": 3.0,
             "pool_wait_s": 0.001, "client_id": 7.0, "mesh_devices": 4.0,
             "resize_events": 1.0, "resize_time_s": 0.2}, 0.0105),
    ]
    path = tmp_path / "telemetry.jsonl"
    sink = JsonlSink(path)
    for step, metrics, dt in rows:
        sink.log({**metrics, "step_time_s": dt}, step=step)
    sink.close()
    got = path.read_text().splitlines()
    want = [_golden_record(step, m, dt) for step, m, dt in rows]
    assert got == want                      # bytes, field order included


def test_staleness_telemetry_streams_through_jsonl_sink(tmp_path):
    path = tmp_path / "tau.jsonl"
    tel = StalenessTelemetry(print_summary=False, jsonl_path=path)
    with FusedExecutor(_loss, _mcfg(), optim.sgd(0.1, momentum=0.9),
                       donate=False) as ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        Engine(ex, _batches(4), [tel]).fit(state, 4)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(recs) == 4
    assert list(recs[0])[:5] == ["step", "tau", "perturbed", "step_time_s",
                                 "loss"]
    assert [r["step"] for r in recs] == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# every executor logs registered keys through the engine's tracker route
# ---------------------------------------------------------------------------

def _fit_with_strict_tracker(ex, n, events=None):
    sink = MemorySink(strict=True)     # raises on any unregistered write
    with ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        Engine(ex, _batches(n)).fit(state, n, events=events,
                                    tracker=Tracker([sink]))
    return sink


@pytest.mark.parametrize("kind", ["fused", "hetero", "elastic"])
def test_executors_emit_registered_keys_every_step(kind):
    opt = optim.sgd(0.1, momentum=0.9)
    if kind == "fused":
        ex = FusedExecutor(_loss, _mcfg(), opt, donate=False)
    elif kind == "hetero":
        ex = HeteroExecutor(_loss, _mcfg(), opt,
                            exec_cfg=ExecutorConfig(lockstep=True))
    else:
        ex = ElasticExecutor(HeteroExecutor(_loss, _mcfg(), opt))
    events = (ChaosSchedule([MeshEvent(step=3, devices=4)])
              if kind == "elastic" else None)
    sink = _fit_with_strict_tracker(ex, 6, events=events)
    assert len(sink.steps) == 6
    for _, metrics in sink.steps:
        assert set(ENGINE_METRIC_KEYS) <= set(metrics)
        assert "step_time_s" in metrics
    if kind == "elastic":
        assert all(m["mesh_devices"] >= 1.0 for _, m in sink.steps)
        resizes = [s for s in sink.spans if s.name == "mesh_resize"]
        assert resizes and resizes[0].lane == "elastic"
        assert resizes[0].args["devices"] == 4
    if kind == "hetero":
        lanes = {s.lane for s in sink.spans}
        assert "descent" in lanes and "ascent-thread" in lanes


def test_remote_executor_registered_keys_and_live_stats_scrape():
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    try:
        xcfg = ExecutorConfig(lockstep=True, ascent_addr=server.address)
        sink = MemorySink(strict=True)
        with RemoteExecutor(mlp_loss, _mcfg(), optim.sgd(0.1, momentum=0.9),
                            exec_cfg=xcfg) as ex:
            state = ex.init_state(mlp_init(jax.random.PRNGKey(0)),
                                  jax.random.PRNGKey(1))
            Engine(ex, _batches(6)).fit(state, 6, tracker=Tracker([sink]))
            # scrape while the training client is still attached
            snap = fetch_pool_stats(server.address)
        for _, metrics in sink.steps:
            assert set(ENGINE_METRIC_KEYS) <= set(metrics)
        assert any("wire_bytes" in m for _, m in sink.steps)
        rpc = [s for s in sink.spans if s.name == "ascent_rpc"]
        assert rpc and all(s.args["wire_bytes"] > 0 for s in rpc)
        # the STATS snapshot saw the fit: exchanges counted, the training
        # client listed (the observer scrape itself excluded), one shadow
        assert snap["exchanges"] >= 5
        assert snap["workers"] >= 1 and snap["queue_capacity"] >= 1
        assert len(snap["clients_detail"]) == 1
        assert snap["clients_detail"][0]["exchanges"] >= 5
        # one canonical shadow for the client's attach scope (gen is the
        # *mesh* generation — 0 until a resize)
        assert len(snap["shadows_detail"]) == 1
        assert snap["shadows_detail"][0]["scope_uid"] > 0
        # exact wire accounting, measured == modeled like JOB/GRAD frames
        frame = encode_frame(FrameType.STATS, encode_stats(snap))
        assert len(frame) == stats_frame_bytes(len(snap["clients_detail"]),
                                               len(snap["shadows_detail"]))
    finally:
        server.close()


# ---------------------------------------------------------------------------
# STATS frame: exact bytes, roundtrip, hostile payloads
# ---------------------------------------------------------------------------

def test_stats_roundtrip_and_exact_modeled_bytes():
    snap = {"workers": 2, "queue_capacity": 32, "queue_depth": 5,
            **{k: i * 3 for i, k in enumerate(STATS_COUNTER_KEYS)},
            "clients_detail": [
                {"uid": 7, "group_uid": 9, "exchanges": 41,
                 "last_wait_s": 0.125},
                {"uid": 8, "group_uid": 0, "exchanges": 2,
                 "last_wait_s": 0.0}],
            "shadows_detail": [
                {"scope_uid": 9, "gen": 12, "sync": 3, "seq": 40,
                 "replays": 1}]}
    payload = encode_stats(snap)
    assert decode_stats(payload) == snap
    frame = encode_frame(FrameType.STATS, payload)
    assert len(frame) == stats_frame_bytes(2, 1)
    # empty pool: fixed layout only
    empty = decode_stats(encode_stats({}))
    assert empty["clients_detail"] == [] and empty["shadows_detail"] == []
    assert len(encode_frame(FrameType.STATS, encode_stats({}))) \
        == stats_frame_bytes(0, 0)


def test_stats_decode_rejects_hostile_payloads():
    good = encode_stats({})
    with pytest.raises(ProtocolError, match="version"):
        decode_stats(bytes([99]) + good[1:])
    with pytest.raises(ProtocolError, match="trailing"):
        decode_stats(good + b"\x00")
    with pytest.raises(ProtocolError, match="shorter"):
        decode_stats(good[:8])
    # announced client count overruns the actual bytes
    truncated = bytearray(good)
    truncated[-8:-4] = (5).to_bytes(4, "big")    # n_clients=5, no entries
    with pytest.raises(ProtocolError, match="overruns"):
        decode_stats(bytes(truncated))
    assert protocol.PROTO_REVISION >= protocol.STATS_REVISION == 4


# ---------------------------------------------------------------------------
# trace exporter + overlap report: the acceptance criterion end-to-end
# ---------------------------------------------------------------------------

def test_hetero_lockstep_trace_is_perfetto_loadable_with_overlap(tmp_path):
    trace_path = tmp_path / "overlap.json"
    sink = TraceEventSink(trace_path)
    with HeteroExecutor(_loss, _mcfg(), optim.sgd(0.1, momentum=0.9),
                        exec_cfg=ExecutorConfig(lockstep=True)) as ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        with Tracker([sink]) as trk:
            Engine(ex, _batches(12)).fit(state, 12, tracker=trk)
    trace = json.loads(trace_path.read_text())
    evs = trace["traceEvents"]
    # structure Perfetto needs: one pid, named tracks, X spans with ts/dur
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"descent", "ascent-thread"} <= lanes
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all(e["ts"] >= 0 and e["dur"] >= 0 for e in spans)
    assert {e["name"] for e in spans} >= {
        "train_step", "descent_compute", "ascent_compute", "ascent_exchange"}
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"loss", "tau"} <= counters
    # and the paper's claim: perturbation time hides under descent compute
    report = _overlap_mod().compute_overlap(trace)
    assert report["steps"] == 12
    assert report["ascent_busy_s"] > 0
    assert report["hidden_fraction"] > 0
    assert report["step_time_p95_s"] >= report["step_time_p50_s"] > 0


def test_overlap_math_on_synthetic_trace():
    mod = _overlap_mod()
    mk = lambda name, ts, dur: {"name": name, "ph": "X", "ts": ts,  # noqa
                                "dur": dur, "cat": "x", "pid": 1, "tid": 1}
    trace = {"traceEvents": [
        mk("descent_compute", 0, 100), mk("descent_compute", 200, 100),
        mk("ascent_compute", 50, 100),     # 50us under descent of 100us busy
        mk("ascent_compute", 400, 50),     # fully exposed
        mk("train_step", 0, 120), mk("train_step", 200, 110),
    ]}
    rep = mod.compute_overlap(trace)
    assert rep["ascent_busy_s"] == pytest.approx(150e-6)
    assert rep["hidden_s"] == pytest.approx(50e-6)
    assert rep["hidden_fraction"] == pytest.approx(50 / 150)
    assert rep["steps"] == 2
    assert rep["step_time_p50_s"] == pytest.approx(110e-6)
    # no ascent work at all -> fraction is 0, not a ZeroDivisionError
    assert mod.compute_overlap({"traceEvents": []})["hidden_fraction"] == 0.0


def test_scalar_metrics_filters_to_floatable():
    out = scalar_metrics({"loss": jnp.float32(0.5), "tau": 1,
                          "logits": jnp.zeros((4, 4)), "note": "skip"})
    assert out == {"loss": 0.5, "tau": 1.0}
    assert REGISTRY["loss"].trace_counter

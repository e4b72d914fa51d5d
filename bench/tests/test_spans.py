"""The program's spans and scopes, read from profiler traces (bench/spans.py).

`data/cpu_spans.xplane.pb` was recorded on the CPU by `record` below: a tiny
OLMo-shaped AsyncSAM model trained through `TokenPipeline` -> `FusedExecutor`
-> `Engine.fit`, behind the benchmark's own feed and step wrappers, with the
last steps inside a "bench.window" annotation. Re-record with
`python -m bench.tests.test_spans <path>` from the root of the repo.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

from bench import spans, trace
from bench.spans import Span
from bench.trace import Op, Trace

DATA = pathlib.Path(__file__).parent / "data"


def record(path: str, steps: int = 4, window_steps: int = 3) -> None:
    import glob
    import shutil
    import tempfile

    import jax

    from bench import system
    from repro import optim
    from repro.core import MethodConfig
    from repro.data import PipelineConfig, TokenPipeline
    from repro.engine import Engine, FusedExecutor
    from repro.models import build_model
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                      act="silu", norm="nonparam_ln", tie_embeddings=True,
                      remat="none", compute_dtype="float32")
    method = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    ex = system.TimedExecutor(FusedExecutor(
        build_model(cfg).loss_fn, method, optim.adamw(1e-3), donate=False))
    feed = system.Feed(TokenPipeline(cfg, PipelineConfig(
        global_batch=2, seq_len=16, ascent_fraction=0.5)))
    rec = system.Recorder(0.9)
    rec.checking = False
    engine = Engine(ex, feed, [rec])
    state = ex.init_state(build_model(cfg).init(jax.random.PRNGKey(0)),
                          jax.random.PRNGKey(1))
    state = engine.fit(state, steps - window_steps).final_state
    tmp = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        engine.fit(state, steps)
    jax.profiler.stop_trace()
    xplane = sorted(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb"))[-1]
    with open(xplane, "rb") as f:
        data = f.read()
    shutil.rmtree(tmp)
    with open(path, "wb") as f:      # without the programs' HLO protos
        f.write(_drop_plane(data, b"/host:metadata"))


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        n |= (b & 0x7F) << shift
        i, shift = i + 1, shift + 7
        if b < 0x80:
            return n, i


def _drop_plane(space: bytes, name: bytes) -> bytes:
    """An XSpace without its planes named `name` (protobuf wire format:
    XSpace.planes is field 1, XPlane.name field 2)."""
    out, i = [], 0
    while i < len(space):
        start = i
        key, i = _varint(space, i)
        assert key & 7 == 2, "XSpace holds only length-delimited fields"
        size, i = _varint(space, i)
        body, i = space[i:i + size], i + size
        if key >> 3 == 1 and _plane_name(body) == name:
            continue
        out.append(space[start:i])
    return b"".join(out)


def _plane_name(plane: bytes) -> bytes:
    i = 0
    while i < len(plane):
        key, i = _varint(plane, i)
        if key & 7 == 0:
            _, i = _varint(plane, i)
            continue
        size, i = _varint(plane, i)
        if key >> 3 == 2:
            return plane[i:i + size]
        i += size
    return b""


if __name__ == "__main__":
    record(sys.argv[1])


# --- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return spans.load(str(DATA / "cpu_spans.xplane.pb"))


def _ms(*pairs) -> float:
    """Milliseconds per step, over the recorded window's 3 steps."""
    return sum(b - a for a, b in pairs) * 1e-6 / 3


@pytest.mark.parametrize("name", ["cpu.xplane.pb", "cpu_spans.xplane.pb"])
def test_loading_spans_leaves_the_trace_reduction_as_it_is(name):
    path = str(DATA / name)
    got, _ = spans.load(path)
    want = trace.load(path)
    assert got.host == want.host and got.devices == want.devices
    assert got.window() == want.window()
    assert trace.busy_s(got) == trace.busy_s(want)
    assert trace.idle_gaps(got) == trace.idle_gaps(want)
    assert trace.top_ops(got) == trace.top_ops(want)


def test_the_older_trace_still_reads_as_it_did():
    t, found = spans.load(str(DATA / "cpu.xplane.pb"))
    assert found == []         # recorded before the program had spans
    assert t.window() == (18481.0, 7039422.0)
    assert {g[0] for g in trace.idle_gaps(t, 3)} == {"engine"}
    assert trace.top_ops(t, 1)[0][0] == "dot_general.1"
    for name in ("repro.readback", "repro.dispatch", "repro.data_next"):
        assert spans.span_ms(t, found, name) is None
    assert spans.loop_self_ms(t, found) is None
    assert spans.scope_ms(t, found, "ascent") is None


def test_recorded_spans_carry_their_args(recorded):
    t, found = recorded
    mine = spans.in_window(t, found)
    assert {s.thread for s in mine} == {1}        # the loop's thread
    loops = [s for s in mine if s.name == "repro.step"]
    assert [s.args["step_num"] for s in loops] == [1, 2, 3]
    assert spans.steps(t, found) == 3
    reads = [s for s in mine if s.name == "repro.readback"]
    # the loop's first test of the step counter, then five reads a step
    assert len(reads) == 1 + 5 * 3
    assert {s.args["of"] for s in reads} == {"step", "metrics"}
    assert {s.args["n"] for s in reads if s.args["of"] == "metrics"} == {10}
    assert [s.args["ready"] for s in mine
            if s.name == "repro.data_next"] == [0, 1, 1]


@pytest.mark.parametrize("name,want", [
    ("repro.dispatch", _ms((4914276, 6657210), (10100216, 11144208),
                           (15533461, 16201895))),
    ("repro.data_next", _ms((1128684, 4646568), (8912875, 8921405),
                            (14467151, 14477180))),
    ("repro.device_wait", _ms((6697670, 8137134), (11172031, 12787705),
                              (16224529, 17946849))),
    ("repro.readback", _ms(
        (92058, 171570),
        (4736096, 4858470), (8239560, 8624990), (8650245, 8674308),
        (8764954, 8860009), (8868259, 8877852),
        (10000863, 10065796), (12873880, 13244229), (13267444, 13293371),
        (14246313, 14390120), (14407372, 14421210),
        (15480670, 15509790), (18037043, 18393397), (18415495, 18440137),
        (19503021, 19673458), (19693267, 19708821))),
])
def test_span_time_per_step_by_hand(recorded, name, want):
    assert spans.span_ms(*recorded, name) == pytest.approx(want)


def test_loop_self_by_hand(recorded):
    # each repro.step less its data_next and train_step
    want = _ms((199989, 8881613), (8896623, 14426945), (14447122, 19715166)) \
        - _ms((1128684, 4646568), (8912875, 8921405), (14467151, 14477180)) \
        - _ms((4884954, 8187826), (10086934, 12823757),
              (15522471, 17984152))
    got = spans.loop_self_ms(*recorded)
    assert got == pytest.approx(want)
    assert spans.span_ms(*recorded, "repro.readback") <= got


def test_idle_time_is_put_down_whole_to_spans(recorded):
    t, found = recorded
    by_span = spans.idle_by_span(t, found)
    lo, hi = t.window()
    idle = (hi - lo) * trace.NS - trace.busy_s(t)[0]
    assert sum(by_span.values()) == pytest.approx(idle)
    assert by_span["repro.dispatch"] > 0 and by_span["repro.readback"] > 0
    gaps = spans.longest_gaps(t, found, 3)
    assert [g for g, _ in gaps] == pytest.approx(
        [s for _, s in trace.idle_gaps(t, 3)])
    for seconds, split in gaps:
        assert sum(split.values()) == pytest.approx(seconds)


# --- constructed traces ------------------------------------------------------

def _trace(ops, lo=0, hi=1000):
    return Trace({0: ops}, [Op("bench.window", lo, hi, "")])


def test_loop_self_subtracts_only_same_thread_children():
    t = _trace([Op("x", 0, 10, "")])
    found = [Span("repro.step", 100, 200, 0, {}),
             Span("repro.data_next", 110, 130, 0, {}),
             Span("repro.train_step", 140, 180, 0, {}),
             # another thread's spans inside the step's interval
             Span("repro.data_next", 120, 190, 1, {}),
             Span("repro.train_step", 150, 160, 1, {})]
    # 100 ns of step, 20 + 40 under its own children; two train_step spans
    assert spans.loop_self_ms(t, found) == pytest.approx(40e-6 / 2)


def test_idle_is_put_down_to_the_innermost_span():
    ops = [Op("a", 0, 100, ""), Op("b", 300, 400, ""), Op("c", 600, 1000, "")]
    found = [Span("repro.step", 50, 650, 0, {}),
             Span("repro.readback", 120, 180, 0, {}),
             Span("repro.dispatch", 250, 320, 0, {}),
             Span("repro.data_next", 450, 700, 0, {})]
    t = _trace(ops)
    assert spans.idle_intervals(t) == [(100, 300), (400, 600)]
    got = spans.idle_by_span(t, found)
    # gap 100-300: step 100-120, readback 120-180, step 180-250, dispatch
    # 250-300; gap 400-600: step 400-450, data_next 450-600
    assert got == pytest.approx({"repro.step": 1.4e-7,
                                 "repro.readback": 6e-8,
                                 "repro.dispatch": 5e-8,
                                 "repro.data_next": 1.5e-7})
    # time no span covers is put down to None
    assert spans.idle_by_span(t, found[1:2]) == pytest.approx(
        {"repro.readback": 6e-8, None: 3.4e-7})
    [[seconds, split]] = spans.longest_gaps(t, found, 1)
    assert seconds == pytest.approx(2e-7)
    assert split == pytest.approx({"repro.step": 9e-8,
                                   "repro.readback": 6e-8,
                                   "repro.dispatch": 5e-8})


SCOPED = [
    "jit(step)/jit(main)/ascent/jvp(cross_entropy)/dot_general",
    "jit(step)/ascent/transpose(jvp(ascent))/dot_general",
    "jit(step)/transpose(jvp(checkpoint(ascent)))/mul",
]
UNSCOPED = [
    "jit(step)/descent/jvp(cross_entropy)/dot_general",
    "jit(step)/update/adamw_update/pallas_call",
    "jit(step)/descent/ascent_loss/add",
    "/src/repro/core/ascent.py:120",
]


def test_scope_is_found_under_transformations_and_remat():
    for name in SCOPED:
        assert spans.has_scope(name, "ascent"), name
    for name in UNSCOPED:
        assert not spans.has_scope(name, "ascent"), name


def test_scope_ms_reads_op_text_or_an_hlo_map():
    ops = [Op(f"%f.{i} = f32[2] fusion()", 100 * i, 100 * i + 10 * (i + 1),
              f"%f.{i} = f32[2] fusion() {text}")
           for i, text in enumerate(SCOPED + UNSCOPED)]
    t = _trace(ops)
    two = [Span("repro.train_step", 0, 1, 0, {})] * 2
    # ops 0, 1, 2 last 10, 20, 30 ns; two steps
    assert spans.scope_ms(t, two, "ascent") == pytest.approx(60e-6 / 2)
    by_name = {f"f.{i}": text for i, text in enumerate(UNSCOPED + SCOPED)}
    bare = _trace([Op(o.name, o.start, o.end, o.name) for o in ops])
    assert spans.scope_ms(bare, two, "ascent") is None
    # with the map, ops 4, 5, 6 (50, 60, 70 ns) are the scoped ones
    assert spans.scope_ms(bare, two, "ascent", by_name) == pytest.approx(
        180e-6 / 2)


def _xspace(path, modules) -> None:
    """A trace file whose "/host:metadata" plane keeps the HLO of
    `modules`: [(name, [(computation id, root id, [(instruction id, name,
    op_name, called computation id or None)])])]."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/host:metadata")
    plane.stat_metadata[1].name = "Hlo Proto"
    for k, (name, comps) in enumerate(modules, start=1):
        proto = hlo_pb2.HloProto()
        for cid, root, instrs in comps:
            comp = proto.hlo_module.computations.add(id=cid, root_id=root)
            for iid, iname, op_name, calls in instrs:
                i = comp.instructions.add(id=iid, name=iname)
                i.metadata.op_name = op_name
                if calls is not None:
                    i.called_computation_ids.append(calls)
        md = plane.event_metadata[k]
        md.name = name
        md.stats.add(metadata_id=1,
                     bytes_value=proto.SerializeToString())
    path.write_bytes(space.SerializeToString())


def test_op_names_from_the_traced_programs_hlo(tmp_path):
    path = tmp_path / "t.xplane.pb"
    asc = "jit(step)/ascent/transpose(jvp(cross_entropy))/dot_general"
    _xspace(path, [
        ("jit_step(7)", [
            (2, 21, [(21, "convolution.2", asc, None)]),
            (1, 13, [(11, "fusion.2", "", 2),
                     (12, "convolution.6",
                      "jit(step)/descent/jvp(cross_entropy)/dot_general",
                      None),
                     (13, "adamw_update.1",
                      "jit(step)/update/adamw_update/pallas_call", None)])]),
        # a smaller program of the same name, and another program
        ("jit_step(3)", [(1, 1, [(1, "fusion.2", "jit(step)/descent/x",
                                  None)])]),
        ("jit__norms(9)", [(1, 1, [(1, "fusion.2", "jit(_norms)/x",
                                    None)])]),
    ])
    names = spans.op_names_from_xspace(str(path))
    assert names == {"convolution.2": asc, "fusion.2": asc,
                     "convolution.6":
                     "jit(step)/descent/jvp(cross_entropy)/dot_general",
                     "adamw_update.1":
                     "jit(step)/update/adamw_update/pallas_call"}
    assert spans.op_names_from_xspace(str(path), "jit_other") == {}

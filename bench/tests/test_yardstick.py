"""The benchmark's yardstick on the CPU: trace reduction, FLOP and byte
counts, the peaks table."""
from __future__ import annotations

import pathlib

import pytest

from bench import flops, load, peaks, system, trace
from bench.trace import Op, Trace

DATA = pathlib.Path(__file__).parent / "data"


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = [Op("a", 0, 10, "a"), Op("b", 5, 15, "b"), Op("c", 20, 30, "c"),
           Op("d", 40, 50, "d")]
    assert trace.union_ns(ops, 0, 100) == 15 + 10 + 10
    assert trace.union_ns(ops, 8, 45) == 7 + 10 + 5


def test_enclosing_ops_are_dropped():
    # a TPU "while" event spans its body's operations
    ops = [Op("while", 0, 100, ""), Op("x", 10, 20, ""), Op("y", 30, 40, ""),
           Op("z", 120, 130, "")]
    assert [o.name for o in trace._leaves(ops)] == ["x", "y", "z"]


def test_recorded_cpu_trace():
    """Recorded on the CPU from a tiny jitted matmul + tanh, three calls
    inside bench.window / bench.step annotations."""
    t = trace.load(str(DATA / "cpu.xplane.pb"))
    assert list(t.devices) == [0]
    names = [o.name for o in t.devices[0]]
    assert names.count("dot_general.1") == 3
    lo, hi = t.window()
    assert (lo, hi) == (18481.0, 7039422.0)
    assert [h.name for h in t.host].count("bench.step") == 3
    busy = trace.busy_s(t)[0]
    ops_ns = sum(o.end - o.start for o in t.devices[0])
    assert busy == pytest.approx(ops_ns * trace.NS)
    assert trace.idle_share(t) == pytest.approx(1 - busy / ((hi - lo) * 1e-9))
    dots = [o for o in t.devices[0] if o.name.startswith("dot_general")]
    assert len(dots) == 3
    assert trace.summed_s(dots) == pytest.approx(
        (104482.0 + 116333.0 + 105354.0) * 1e-9)
    gaps = trace.idle_gaps(t, 3)
    assert len(gaps) == 3 and all(g[1] > 0 for g in gaps)
    # the gaps lie between the step annotations, in the loop's own code
    assert {g[0] for g in gaps} == {"engine"}
    assert trace.top_ops(t, 1)[0][0] == "dot_general.1"


TPU_ATTN = ('%closed_call.28 = bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)} %a, '
            'bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)} %b, '
            'bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)} %c), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[32,2048,128]{2,1,0}}, frontend_attributes={kernel_metadata'
            '={}}')
TPU_ADAM = ('%step.9 = (f32[304349184]{0:T(1024)}, f32[304349184]{0:T(1024)}, '
            'f32[304349184]{0:T(1024)}) custom-call(f32[4]{0:T(128)} %s, '
            'f32[304349184]{0:T(1024)} %w, f32[304349184]{0:T(1024)} %g, '
            'f32[304349184]{0:T(1024)} %m, f32[304349184]{0:T(1024)} %v), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={f32[4]{0}}')
TPU_FUSION = ('%fusion.523 = (f32[2,2048]{1,0:T(2,128)S(1)}, f32[2,2048]) '
              'fusion(f32[2,2048]{1,0} %x), kind=kOutput')


def _ctx(ops):
    class Ctx:
        pass
    ctx = Ctx()
    ctx.trace = Trace({0: ops}, [Op("bench.window", 0, 10**9, "")])
    ctx.peaks = peaks.peaks("TPU v5 lite")
    return ctx


def test_kernels_are_told_apart_by_their_shapes():
    ops = [Op(TPU_ATTN, 0, 4_000_000, TPU_ATTN),
           Op(TPU_ADAM, 5_000_000, 17_000_000, TPU_ADAM),
           Op(TPU_FUSION, 18_000_000, 19_000_000, TPU_FUSION)]
    calls = trace.custom_calls(ops)
    assert [c.op.name[:6] for c in calls] == ["%close", "%step."]
    attn, adam = calls
    assert attn.results == [("bf16", (32, 2048, 128))]
    assert attn.nbytes == 4 * 32 * 2048 * 128 * 2
    assert adam.nbytes == 7 * 304349184 * 4 + 4 * 4
    ctx = _ctx(ops)
    # attention: 2 * 2 * hd * s (s + 1) / 2 * bh FLOPs in 4 ms
    want = 100 * (flops.attn_fwd_flops(1, 2048, 32, 128) / 197e12) / 4e-3
    assert load.reader("attn_fwd_roofline")(ctx) == pytest.approx(want)
    # the epilogue: bytes over 12 ms at 819 GB/s
    want = 100 * (adam.nbytes / 819e9) / 12e-3
    assert load.reader("epilogue_roofline")(ctx) == pytest.approx(want)
    assert trace.label(TPU_FUSION) == "fusion f32[2,2048]"


def test_kernel_readers_find_nothing_without_kernels():
    ctx = _ctx([Op(TPU_FUSION, 0, 10, TPU_FUSION)])
    assert load.reader("attn_fwd_roofline")(ctx) is None
    assert load.reader("epilogue_roofline")(ctx) is None


def test_olmo_parameter_counts_by_hand():
    olmo = load.arch("olmo")
    dims = load.config("olmo-1b-3l")["model"]
    # per layer: q, k, v, o 4 * 2048^2; SwiGLU 3 * 2048 * 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert olmo.layer_matmul_params(dims) == layer == 67_108_864
    # tied embedding 50304 x 2048; the LayerNorms have no parameters
    assert olmo.param_count(dims) == 3 * layer + 50304 * 2048
    assert olmo.param_count(dims) == 304_349_184      # 0.304 B
    whole = {**dims, "n_layers": 16}     # OLMo-1B as published
    assert olmo.param_count(whole) == 16 * layer + 50304 * 2048
    assert olmo.param_count(whole) == 1_176_764_416   # 1.18 B
    assert olmo.param_count({**dims, "tie_embeddings": False}) == (
        304_349_184 + 50304 * 2048)


def test_step_flops_by_hand():
    olmo = load.arch("olmo")
    dims = load.config("olmo-1b-3l")["model"]
    cell = load.workload("olmo-1b-3l.async_sam")
    per_token = 6 * 304_349_184 + 6 * 3 * 2048 * 2049
    assert olmo.train_flops_per_token(dims, 2048) == per_token
    # descent 2 rows and ascent 1 row of 2048 tokens: about 11.7 TFLOP
    rows, asc = cell["batch"], system.ascent_rows(cell)
    assert (rows, asc) == (2, 1)
    got = flops.step_flops(olmo, dims, 2048, rows, asc)
    assert got == 3 * 2048 * per_token
    assert 11.6e12 < got < 11.8e12


def test_attention_kernel_counts_by_hand():
    # (b, s, h, hd) = (2, 2048, 16, 128): 2 matmuls x 2 hd FLOPs per visible
    # (query, key) pair, s (s + 1) / 2 pairs per head
    assert flops.attn_fwd_flops(2, 2048, 16, 128) == (
        2 * 2 * 128 * (2048 * 2049 // 2) * 32)


def test_roofline_names_its_bound():
    share, bound = flops.roofline_share(197e12, 0.0, 2.0, 197e12, 819e9)
    assert (share, bound) == (50.0, "compute")
    share, bound = flops.roofline_share(0.0, 819e9, 4.0, 197e12, 819e9)
    assert (share, bound) == (25.0, "memory")


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# --- collective_exposed_ms ------------------------------------------------

AG_START = ('%all-gather-start.3 = (f32[4096,2048]{1,0}, f32[16384,2048]{1,0}) '
            'all-gather-start(f32[4096,2048]{1,0} %p), channel_id=7, '
            'replica_groups=[1,4]<=[4], dimensions={0}')
AG_DONE = ('%all-gather-done.3 = f32[16384,2048]{1,0} all-gather-done('
           '(f32[4096,2048]{1,0}, f32[16384,2048]{1,0}) %all-gather-start.3)')
ALL_REDUCE = ('%all-reduce.18 = f32[2048,8192]{1,0} all-reduce(f32[2048,8192]'
              '{1,0} %g), channel_id=9, replica_groups=[1,4]<=[4], '
              'to_apply=%add')
FUSION_OF_GATHERED = ('%fusion.7 = bf16[2,2048,2048]{2,1,0} fusion(f32[16384,'
                      '2048]{1,0} %all-gather-done.3), kind=kLoop')


def _exposed(devices, steps=1, window=(0, 10**9)):
    class Ctx:
        pass
    ctx = Ctx()
    ctx.trace = Trace(devices, [Op("bench.window", *window, "")])
    ctx.steps = steps
    return load.reader("collective_exposed_ms")(ctx)


@pytest.mark.parametrize("name, want", [
    (AG_START, "all-gather-start"), (AG_DONE, "all-gather-done"),
    (ALL_REDUCE, "all-reduce"), (FUSION_OF_GATHERED, "fusion"),
    (TPU_ATTN, "custom-call"), ("all-reduce.12", "all-reduce"),
    ("reduce-scatter.3.1", "reduce-scatter"), ("dot_general.1", "dot_general"),
])
def test_collectives_are_matched_by_opcode(name, want):
    opcode = load.reader("collective_exposed_ms").__globals__["opcode"]
    assert opcode(name) == want


def test_collective_time_under_compute_is_not_exposed():
    # all-reduce 0-10 ms, a fusion inside it 4-7 ms: 7 ms exposed
    ms = 1_000_000
    ops = [Op(ALL_REDUCE, 0, 10 * ms, ""), Op(TPU_FUSION, 4 * ms, 7 * ms, "")]
    assert _exposed({0: ops}) == pytest.approx(7.0)


def test_an_async_pair_counts_its_own_events_not_the_transfer():
    # start 0-1, compute 1-9 hides the transfer, done 9-12 waits on it
    ms = 1_000_000
    ops = [Op(AG_START, 0, 1 * ms, ""), Op(FUSION_OF_GATHERED, 1 * ms, 9 * ms,
                                          ""), Op(AG_DONE, 9 * ms, 12 * ms, "")]
    assert _exposed({0: ops}) == pytest.approx(4.0)
    # with the device idle between them, the idle time is not counted
    assert _exposed({0: [ops[0], ops[2]]}) == pytest.approx(4.0)


def test_collective_time_is_per_step_on_the_busiest_chip():
    ms = 1_000_000
    chip0 = [Op(ALL_REDUCE, 0, 6 * ms, ""), Op(ALL_REDUCE, 20 * ms, 26 * ms, "")]
    chip1 = [Op(ALL_REDUCE, 0, 10 * ms, ""), Op(ALL_REDUCE, 20 * ms, 30 * ms,
                                                 "")]
    chip2 = [Op(TPU_FUSION, 0, 40 * ms, "")]
    # 20 ms on chip 1 over 2 steps; chip 2 runs no collective
    assert _exposed({0: chip0, 1: chip1, 2: chip2}, steps=2) == (
        pytest.approx(10.0))
    # only what lies inside the window counts
    assert _exposed({1: chip1}, window=(5 * ms, 25 * ms)) == pytest.approx(
        10.0)


def test_no_collective_reads_nothing():
    assert _exposed({0: [Op(TPU_FUSION, 0, 10, "")]}) is None

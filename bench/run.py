"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Refuses to run without a TPU (or with fewer chips than the cell asks for):
exit code 2 and no result. Otherwise it makes the weights and the traffic
from --seed, builds the program's training path (`system.ProgramSystem`),
drives its first three steps in set-up, measures `--seconds` of steady
steps, and checks the first steps against the plain reference (the
configuration's architecture, `bench/archs/<arch>.py`, placed on the cell's
chips by `check.placement`). With
`--trace 1` the window runs under the profiler and the per-layer metrics are
reported; with `--trace 0` the end-to-end metrics. The last line of standard
output is the JSON result; the last lines of standard error are the numbers
compared, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Context:
    """What a per-layer metric's reader may read (see bench/metrics/)."""

    def __init__(self, cell, config, window, trace, peaks, peak_bytes):
        from bench import load, system
        self.cell = cell
        self.config = config
        self.arch = load.arch(config["arch"])
        self.dims = system.model_dims(config)
        self.chips = cell["chips"]
        self.rows = cell["batch"]
        self.ascent_rows = system.ascent_rows(cell)
        self.seq = cell["seq"]
        self.window = window
        self.steps = len(window["times"])
        self.window_s = window["times"][-1] - window["t0"]
        self.trace = trace
        self.peaks = peaks
        self.peak_bytes = peak_bytes


def end_to_end(cell: dict, window: dict, setup_s: float) -> dict:
    times = [window["t0"]] + window["times"]
    steps = [b - a for a, b in zip(times, times[1:])]
    tokens = len(steps) * cell["batch"] * cell["seq"]
    return {"tokens_per_s": tokens / (times[-1] - times[0]),
            "step_ms_p90": 1e3 * statistics.quantiles(steps, n=10)[-1],
            "setup_s": setup_s}


def step_report(w: dict) -> str:
    """The window's step times, and each step over 1.5x the median with
    its data wait and step call (host clock)."""
    times = [w["t0"]] + w["times"]
    ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]
    med = statistics.median(ms)
    slow = [(i, round(m, 3), round(1e3 * w["waits"][i], 3),
             round(1e3 * w["step_s"][i], 3))
            for i, m in enumerate(ms) if m > 1.5 * med]
    call = statistics.median(1e3 * s for s in w["step_s"])
    rest = statistics.median(m - 1e3 * (s + d) for m, s, d in zip(
        ms, w["step_s"], w["waits"]))
    return (f"bench: step ms min {min(ms):.3f} median {med:.3f} max "
            f"{max(ms):.3f} (median step call {call:.3f}, median loop "
            f"outside the call and the data wait {rest:.3f}); steps over "
            f"1.5x the median (index, ms, data wait ms, step call ms): "
            f"{slow}")


def run_cell(cell: dict, config: dict, seed: int, seconds: float,
             trace: bool, *, t_start: float, devices: list, peaks,
             system_cls=None) -> dict:
    """One run; returns the result line's fields and the check's lines."""
    import jax

    from bench import check, load, system
    from bench import trace as tr

    bench_json = load.benchmark()
    system_cls = system_cls or system.ProgramSystem
    prog = system_cls(cell, config, seed)
    readings = prog.set_up()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    # host annotations (TraceMe) without the Python call tracer, which would
    # slow every host step it times
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    if not trace:
        # A profiler session started and stopped in set-up puts every run's
        # host path in one mode: without it about two processes in three ran
        # each step's dispatch and scalar reads some 3 ms slower, for their
        # whole window, than every traced run did (PERF.md, Findings).
        jax.profiler.stop_trace()
        shutil.rmtree(tmp)
    try:
        window = prog.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    setup_s = window["t0"] - t_start
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    prog_marks = prog.marks
    prog.close()
    del prog

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"attempted": len(window["times"]),
           "failed": sum(1 for x in window["losses"]
                         if not x == x or abs(x) == float("inf"))}
    if trace:
        try:
            parsed = tr.load(tr.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        busy = tr.busy_s(parsed)
        lo, hi = parsed.window()
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = (hi - lo) * tr.NS
        ctx = Context(cell, config, window, parsed, peaks, peak_bytes)
        metrics = {}
        for m in load.metrics_for(bench_json, "per_layer", cell["name"]):
            value = load.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr.top_ops(parsed),
                            "idle_gaps": tr.idle_gaps(parsed)}
    else:
        e2e = end_to_end(cell, window, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in load.metrics_for(bench_json, "end_to_end",
                                             cell["name"])}

    t_check = time.perf_counter()
    nums = check.program_numbers(cell, config, seed, readings)
    correct, lines = check.verdict(nums, cell["limits"])
    out.update(correct=correct, metrics=metrics, device=device)
    out["checks"] = {k: {"value": nums.get(k), "limit": v}
                     for k, v in cell["limits"].items()}
    marks = prog_marks + [("window", window["t0"]),
                          ("check", time.perf_counter() - t_check)]
    return {"result": out, "lines": lines, "setup_s": setup_s,
            "window": (window["t0"], window["times"][-1]), "marks": marks,
            "steps": window}


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import load
    cell = load.workload(args.workload)
    config = load.config(cell["config"])

    # libtpu would write its logs under /tmp, outside the checkout and the
    # directories a run is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (jax found {devices[0].platform}); refusing to "
              "run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from bench.peaks import peaks
    chip_peaks = peaks(devices[0].device_kind)
    from repro.launch.compile_cache import use_checkout_compile_cache
    use_checkout_compile_cache()

    pauses, started = [], {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            pauses.append((started.pop("t"), time.perf_counter(),
                           info["generation"]))

    gc.callbacks.append(on_gc)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == "/jax/core/compile/backend_compile_duration" else None)
    run = run_cell(cell, config, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, devices=devices[:cell["chips"]],
                   peaks=chip_peaks)
    lo, hi = run["window"]
    print(f"bench: {cell['name']} seed {args.seed}: set-up "
          f"{run['setup_s']:.3f}s, {run['result']['attempted']} steps in "
          f"{hi - lo:.3f}s, {sum(lo <= t <= hi for t in compiles)} compiles "
          "in the window", file=sys.stderr)
    print(step_report(run["steps"]), file=sys.stderr)
    inside = [(b - a, g) for a, b, g in pauses if lo <= a <= hi]
    print(f"bench: {len(inside)} garbage collections in the window, "
          f"{1e3 * sum(d for d, _ in inside):.3f} ms in all, longest "
          f"{1e3 * max((d for d, _ in inside), default=0.0):.3f} ms, "
          f"generation-2 {sum(g == 2 for _, g in inside)}", file=sys.stderr)
    *marks, (_, check_s) = run["marks"]
    print("bench: set-up " + ", ".join(f"{k} at {t - T_START:.3f}s"
                                       for k, t in marks)
          + f"; check {check_s:.3f}s", file=sys.stderr)
    print(json.dumps(run["result"]))
    sys.stdout.flush()
    for line in run["lines"]:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's limits, in one process on the cell's chips.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --fault-seeds 7,8,9

For each of `--seeds`, the program's set-up steps (the same path `run.py`
times) against the reference: the lower readings. For each of
`--control-seeds`, the control (the reference at float8, put in the
program's place) against the reference; for each of `--fault-seeds`, the
program with half of each descent batch left out inside the step (the mean
taken over the rest). One JSON line per reading, then a summary: the
largest sound reading and the smallest control and fault readings of each
number.

The reference, and the control, are the configuration's architecture
(`bench/archs/<arch>.py`) placed on the cell's chips by
`check.placement`, as in `run.py`.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def half_batch(batch: dict) -> dict:
    """The descent rows cut to their first half; the ascent rows kept."""
    out = dict(batch)
    for k in ("tokens", "labels"):
        out[k] = batch[k][: batch[k].shape[0] // 2]
    return out


def program_numbers(cell, config, seed, **faults) -> dict:
    from bench import check, system
    prog = system.ProgramSystem(cell, config, seed, **faults)
    r = prog.set_up()
    prog.close()
    del prog
    return check.program_numbers(cell, config, seed, r)


def control_numbers(cell, config, seed) -> dict:
    """The reference at float8 in the program's place, on the batches the
    program's pipeline would feed (the generator's streams 2k, 2k + 1)."""
    from bench import check, generator, load, system
    arch = load.arch(config["arch"])
    dims = system.model_dims(config)
    mesh = system.make_mesh(cell["mesh"])
    train = {**cell["train"], "method": cell["method"]}
    b, s, a = cell["batch"], cell["seq"], system.ascent_rows(cell)
    batches = []
    for k in range(system.CHECK_STEPS):
        tok = generator.rows(seed, dims["vocab_size"], b, s, 2 * k)
        batch = {"tokens": tok, "labels": generator.labels_of(tok)}
        if a:
            tok = generator.rows(seed, dims["vocab_size"], a, s, 2 * k + 1)
            batch["ascent"] = {"tokens": tok,
                               "labels": generator.labels_of(tok)}
        batches.append(batch)
    params0 = check.seed_params(arch, dims, seed, mesh)
    got = check.reference_readings(arch, dims, train, params0, batches, mesh,
                                   "fp8")
    ref = check.reference_readings(arch, dims, train, params0, batches, mesh)
    return check.numbers(got, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; refusing to run", file=sys.stderr)
        return 2
    from bench import load
    from repro.launch.compile_cache import use_checkout_compile_cache
    use_checkout_compile_cache()
    cell = load.workload(args.workload)
    config = load.config(cell["config"])

    def seeds(text):
        return [int(x) for x in text.split(",") if x]

    rows = {"program": [], "control": [], "half_batch": []}
    runs = ([("program", s, lambda s: program_numbers(cell, config, s))
             for s in seeds(args.seeds)]
            + [("control", s, lambda s: control_numbers(cell, config, s))
               for s in seeds(args.control_seeds)]
            + [("half_batch", s, lambda s: program_numbers(
                cell, config, s, alter_step=half_batch))
               for s in seeds(args.fault_seeds)])
    for kind, seed, fn in runs:
        t0 = time.perf_counter()
        nums = fn(seed)
        rows[kind].append(nums)
        print(json.dumps({"kind": kind, "seed": seed, **nums,
                          "seconds": time.perf_counter() - t0}), flush=True)
    summary = {}
    for name in ("loss_gap", "grad_gap", "change_gap"):
        summary[name] = {
            "lower": max((r[name] for r in rows["program"]), default=None),
            "control": min((r[name] for r in rows["control"]), default=None),
            "half_batch": min((r[name] for r in rows["half_batch"]),
                              default=None)}
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

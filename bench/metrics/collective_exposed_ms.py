"""Device time per step of collective operations during which no other
operation ran on the chip, on the chip where that time is largest (device
trace). Collectives are matched by HLO opcode: all-gather, reduce-scatter,
all-reduce, collective-permute and all-to-all, and their -start and -done
forms. An asynchronous pair counts its two events' own time, not the
transfer between them, which other operations may hide."""
import functools
import re

from bench import trace

COLLECTIVES = {f"{op}{form}" for op in ("all-gather", "reduce-scatter",
                                        "all-reduce", "collective-permute",
                                        "all-to-all")
               for form in ("", "-start", "-done")}


@functools.lru_cache(maxsize=2**16)
def opcode(name: str) -> str:
    """The HLO opcode of a trace event: from the instruction a TPU trace
    names it by (`%x.1 = <type> <opcode>(...)`), else the instruction's
    name without its numeric suffix (the CPU's `all-reduce.3`)."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return re.sub(r"(\.\d+)+$", "", head.lstrip("%"))
    depth, i = 0, 0
    if rest.startswith("("):          # a tuple type
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
    end = rest.find(" ", i)
    return rest[end + 1:].split("(", 1)[0] if end >= 0 else ""


def exposed_ns(ops: list, lo: float, hi: float) -> float:
    """Time inside [lo, hi] in which collectives ran and nothing else did."""
    others = [o for o in ops if opcode(o.name) not in COLLECTIVES]
    return trace.union_ns(ops, lo, hi) - trace.union_ns(others, lo, hi)


def read(ctx):
    lo, hi = ctx.trace.window()
    per_chip = [exposed_ns(ops, lo, hi) for ops in ctx.trace.devices.values()
                if any(opcode(o.name) in COLLECTIVES for o in ops)]
    if not per_chip:
        return None
    return max(per_chip) * trace.NS * 1e3 / ctx.steps

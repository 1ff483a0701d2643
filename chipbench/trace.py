"""Reduction of a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; ``reduce`` turns those lists into device busy time (the union of the
op intervals of each chip, averaged over chips), device time per jitted
program, the ops that took most time, and the longest idle gaps named by
the host span that covers them most.  Times are nanoseconds from the start
of the trace; the window is ``[0, window_ns]``.

Programs are found by their jitted function names (the serve engine's
``decode_fn`` and ``prefill_fn``).  That is a dependency on the program's
names until it gives its steps stable named scopes.
"""

from __future__ import annotations

import dataclasses
import glob
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: host spans that the benchmark itself opens around calls into the program
HOST_SPAN_PREFIXES = ("engine.", "program.")
NO_SPAN = "host: outside any span"


def extract(trace_dir: str) -> dict:
    """Plain lists from the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}``,
    each event ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            out["devices"][plane.name] = {
                key: [[e.name, e.start_ns, e.duration_ns] for e in lines[name].events]
                if name in lines else []
                for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE))
            }
        elif plane.name.startswith("/host:"):
            out["host"] += [[e.name, e.start_ns, e.duration_ns]
                            for ln in plane.lines for e in ln.events
                            if e.name.startswith(HOST_SPAN_PREFIXES)]
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` intervals inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


#: ops that only contain other ops (their bodies are traced as ops too)
CONTAINER_OPS = ("while", "conditional", "call")


def op_label(hlo: str) -> tuple[str, str]:
    """``(label, opcode)`` of an ``XLA Ops`` event named by its HLO text
    (``%fusion.3 = bf16[12,1024]{...} fusion(...)``): the label is the
    instruction's name and the start of its result type."""
    name, _, rest = hlo.partition(" = ")
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.split("(", 1)[0].strip()
    return f"{name.lstrip('%')} {shape[:48]}".strip(), opcode


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _name_gap(g0, g1, host) -> str:
    """The host span that overlaps the gap most; the shorter span wins a tie."""
    best, key = NO_SPAN, (0.0, 0.0)
    for name, s, d in host:
        ov = _overlap(g0, g1, s, s + d)
        if ov > 0 and (ov, -d) > key:
            best, key = name, (ov, -d)
    return best


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # union of op intervals, mean over chips
    programs: dict[str, tuple[float, int]]  # name -> (device seconds, calls)
    device_ops: list[tuple[str, float]]     # top ops by device seconds,
                                            # containers (while) left out
    idle_gaps: list[tuple[str, float]]      # longest gaps, named

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: dict, window_ns: float, programs=("decode_fn", "prefill_fn"),
           top: int = 10) -> Summary:
    """Reduce ``extract``'s lists over the window ``[0, window_ns]``."""
    devices = events["devices"]
    busy_ns, prog, ops = [], {p: [0.0, 0] for p in programs}, {}
    first_gaps: list[tuple[float, float]] = []
    for i, name in enumerate(sorted(devices)):
        dev = devices[name]
        busy = union([(s, s + d) for _, s, d in dev["ops"]], 0.0, window_ns)
        busy_ns.append(sum(e - s for s, e in busy))
        if i == 0:
            first_gaps = gaps(busy, 0.0, window_ns)
        for mod, s, d in dev["modules"]:
            inside = _overlap(s, s + d, 0.0, window_ns)
            for p in programs:
                if p in mod and inside > 0:
                    prog[p][0] += inside
                    prog[p][1] += 1
        for op, s, d in dev["ops"]:
            inside = _overlap(s, s + d, 0.0, window_ns)
            label, opcode = op_label(op)
            if inside > 0 and opcode not in CONTAINER_OPS:
                ops[label] = ops.get(label, 0.0) + inside / len(devices)
    longest = sorted(first_gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=window_ns * 1e-9,
        busy_s=(sum(busy_ns) / len(busy_ns) if busy_ns else 0.0) * 1e-9,
        programs={p: (t * 1e-9, n) for p, (t, n) in prog.items()},
        device_ops=[(n, t * 1e-9) for n, t in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(_name_gap(g0, g1, events["host"]), (g1 - g0) * 1e-9)
                   for g0, g1 in longest],
    )

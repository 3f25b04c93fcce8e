"""Reduction of a profiler trace to device busy time, idle gaps and layers.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain records, so that everything after
it is arithmetic on lists that a test can build by hand:

* ``ops``: ``(device, name, start_s, end_s, text)`` for each operation on
  a chip, where ``text`` is the op's name and every string it carries, for
  matching.  On a TPU v5e the name is the op's HLO text (``%x = shape
  opcode(operands), attributes``) and the strings carry nothing more: no
  ``op_name`` path, and no kernel name on a Pallas ``tpu_custom_call``;
* ``spans``: ``(name, start_s, end_s)`` of the benchmark's own host spans
  (``round.put_batch``, ``round.dispatch``, ``round.wait``).
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "round."


def load(trace_dir: str | Path) -> tuple[list, list]:
    import jax
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    words = [ev.name] + [str(v) for _, v in ev.stats
                                         if isinstance(v, str)]
                    start = ev.start_ns * 1e-9
                    ops.append((dev, ev.name, start,
                                start + ev.duration_ns * 1e-9,
                                " ".join(words)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = ev.start_ns * 1e-9
                        spans.append((ev.name, start,
                                      start + ev.duration_ns * 1e-9))
    return ops, spans


def window(spans: list) -> tuple[float, float]:
    """From the first round span's start to the last one's end."""
    if not spans:
        raise ValueError("the trace holds no round spans")
    return min(s[1] for s in spans), max(s[2] for s in spans)


def clip(ops: list, lo: float, hi: float) -> list:
    """The ops that overlap [lo, hi], cut to it."""
    return [(d, n, max(s, lo), min(e, hi), t) for d, n, s, e, t in ops
            if e > lo and s < hi]


def union(intervals) -> list:
    """Sorted, disjoint cover of the given (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def devices(ops: list) -> list:
    return sorted({o[0] for o in ops})


def busy_seconds(ops: list) -> float:
    """Seconds in which some op ran, per chip, averaged over the chips."""
    devs = devices(ops)
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        total += sum(e - s for s, e in union((o[2], o[3]) for o in ops
                                             if o[0] == d))
    return total / len(devs)


def gaps(ops: list, lo: float, hi: float, device: int) -> list:
    """(start, end) of each stretch of [lo, hi] with no op on ``device``."""
    out, t = [], lo
    for s, e in union((o[2], o[3]) for o in ops if o[0] == device):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple, spans: list) -> str:
    """The host span that covers most of a gap, or ``host.other``."""
    best, most = "host.other", 0.0
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > most:
            best, most = name, cover
    return best


def short_name(text: str) -> str:
    """``opcode result-shape`` of an op's HLO text, without layouts:
    ``%sort.4 = (f32[9]{0}, s32[9]{0}) sort(...)`` -> ``sort (f32[9], s32[9])``.
    """
    m = re.match(r"%[\w.-]+ = (.*?) ([a-z][a-z0-9-]*)\(", text)
    if not m:
        return text[:80]
    return f"{m.group(2)} {re.sub(r'{[^}]*}', '', m.group(1))}"


def leaves(ops: list) -> list:
    """The ops that hold no other op of their chip (a ``while`` op holds
    its body's ops, which the trace lists too)."""
    out = []
    for d in devices(ops):
        mine = sorted((o for o in ops if o[0] == d),
                      key=lambda o: (o[2], -o[3]))
        holder = [False] * len(mine)
        stack: list = []
        for i, o in enumerate(mine):
            while stack and mine[stack[-1]][3] <= o[2]:
                stack.pop()
            if stack:
                holder[stack[-1]] = True
            stack.append(i)
        out += [o for o, h in zip(mine, holder) if not h]
    return out


def breakdown(ops: list, spans: list, lo: float, hi: float,
              top: int = 10) -> dict:
    """The kinds of op (``short_name``) that took most device time, per
    chip, and the longest idle gaps on the first chip labelled by what the
    host was doing, longest first."""
    per_op: dict = {}
    for _, _, s, e, text in leaves(ops):
        name = short_name(text)
        per_op[name] = per_op.get(name, 0.0) + (e - s)
    n_dev = max(1, len(devices(ops)))
    device_ops = sorted(([n, t / n_dev] for n, t in per_op.items()),
                        key=lambda x: -x[1])[:top]
    first = devices(ops)[0] if ops else 0
    idle = sorted(([label(g, spans), g[1] - g[0]]
                   for g in gaps(ops, lo, hi, first)), key=lambda x: -x[1])
    return {"device_ops": device_ops, "idle_gaps": idle[:top]}


def matching_seconds(ops: list, pattern: str) -> float:
    """Device seconds, averaged over the chips, of the ops whose text
    matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    n_dev = max(1, len(devices(ops)))
    return sum(e - s for _, _, s, e, t in ops if rx.search(t)) / n_dev


def ms_per_round(ctx: dict, pattern: str):
    """Device milliseconds per round of the ops matching ``pattern``, or
    None where none ran in the traced window."""
    if not any(re.search(pattern, o[4]) for o in ctx["ops"]):
        return None
    return 1e3 * matching_seconds(ctx["ops"], pattern) / ctx["rounds"]

"""Device time per round of each layer the program names.

A v5e trace names a device op by its HLO text, which begins
``%<instruction> =``, and carries neither the op's ``op_name`` nor a
kernel name of its own.  The program names its layers in the compiled
module instead (``repro.obs.layers``): a ``jax.named_scope`` in each
instruction's op_name, a kernel name as each Pallas call's instruction
name.  So the map from instruction to layer comes from the cell's compiled
step: :func:`op_layers` builds and compiles the cell's program again,
reads the module's text and frees it, once per run; the map is kept in
``ctx["op_layers"]``.  That compile misses the persistent cache (a Pallas
kernel's body carries the source location it was lowered from), so it
costs a traced run about a minute at the cells' sizes.  A program that
names no layers gives no map, and every reader here then reads nothing.

Each instant a chip is busy counts once, for the op that started last
among those running then (:func:`owned_seconds`): the trace lists a
``while`` beside the ops of its body, so summing every op would count the
body twice, and summing only the ops that hold no other
(``tracing.leaves``) would drop the whole of a kernel under which an
asynchronous copy starts.  The window holds the step's module alone, so an instruction name is one
op of it; an op the map gives no layer is ``unnamed``, never a guess.
"""

from __future__ import annotations

import heapq
import time

import tracing

UNNAMED = "unnamed"


def module_text(compiled) -> str:
    """The optimized module of a ``jax.stages.Compiled``, from the
    executable's own modules where ``as_text`` gives nothing (one loaded
    from the persistent cache)."""
    text = compiled.as_text()
    if not text:
        text = "\n".join(m.to_string() for m in
                         compiled.runtime_executable().hlo_modules())
    return text


def op_layers(ctx: dict) -> dict | None:
    """``{instruction name: layer}`` of the cell's compiled step, or None
    where the program names no layers."""
    if "op_layers" not in ctx:
        try:
            from repro.obs import layers
        except ImportError:
            ctx["op_layers"] = None
            return None
        import harness
        t0 = time.perf_counter()
        prog = harness.Program(ctx["model"], ctx["config"], ctx["tr"],
                               ctx["chips"], None)
        t1 = time.perf_counter()
        try:
            text = module_text(prog.step)
        finally:
            prog.free()
        ctx["op_layers"] = layers.op_layers(text)
        harness.log(f"layer map: {len(ctx['op_layers'])} instructions; "
                    f"program {t1 - t0:.3f} s, map "
                    f"{time.perf_counter() - t1:.3f} s")
    return ctx["op_layers"]


def owned_seconds(ops: list) -> list:
    """Per op, the seconds in which it is the innermost op running on its
    chip (the one that started last).  Summed over the ops of a chip this
    is its busy time: a ``while`` keeps the time its body's ops leave."""
    out = [0.0] * len(ops)
    for d in tracing.devices(ops):
        mine = sorted((i for i, o in enumerate(ops) if o[0] == d),
                      key=lambda i: (ops[i][2], -ops[i][3]))
        edges = sorted({t for i in mine for t in ops[i][2:4]})
        running: list = []                  # (-start, -order, end, op)
        k = 0
        for a, b in zip(edges, edges[1:]):
            while k < len(mine) and ops[mine[k]][2] <= a:
                i = mine[k]
                heapq.heappush(running, (-ops[i][2], -k, ops[i][3], i))
                k += 1
            while running and running[0][2] <= a:
                heapq.heappop(running)
            if running:
                out[running[0][3]] += b - a
    return out


def ms_per_round(ctx: dict) -> dict | None:
    """Device milliseconds per round, averaged over the chips, of each
    layer that ran and of ``unnamed``; None where there is no map."""
    if "layer_ms" not in ctx:
        names = op_layers(ctx)
        if names is None:
            ctx["layer_ms"] = None
            return None
        from repro.obs.layers import instruction_name
        out = {UNNAMED: 0.0}
        for op, owned in zip(ctx["ops"], owned_seconds(ctx["ops"])):
            layer = names.get(instruction_name(op[4]), UNNAMED)
            out[layer] = out.get(layer, 0.0) + owned
        n_dev = max(1, len(tracing.devices(ctx["ops"])))
        ctx["layer_ms"] = {k: 1e3 * v / n_dev / ctx["rounds"]
                           for k, v in out.items()}
    return ctx["layer_ms"]


def read(ctx: dict, layer: str):
    """Milliseconds per round of ``layer``; None where no op of it ran or
    there is no map."""
    ms = ms_per_round(ctx)
    return None if ms is None else ms.get(layer)

"""The layers of a FetchSGD round, by the names the program gives them.

The train step names each layer where its work is traced: a
``jax.named_scope`` of :data:`SCOPES` around each layer's call site, and a
kernel name of :data:`KERNELS` on each main-path Pallas call.  XLA keeps
the scope path in every instruction's ``metadata={op_name="..."}`` and
the kernel name as the custom call's instruction name (``%fetchsgd_encode.3
= ...``), so :func:`op_layers` maps the instructions of a compiled module
(``compiled.as_text()``) to layers.  A profiler trace names a device op by
its instruction (its HLO text begins ``%<instruction> =``) but drops the
op_name, so a trace reaches its layers through that map.

Scopes nest: ``server_state`` holds the whole server update, ``topk`` the
whole unsketch-and-select inside it, ``unsketch`` the row estimate inside
that; an instruction belongs to the innermost scope of its path.  Inside
``client_model`` an instruction whose path holds ``transpose(jvp(`` is
backward (recomputed forward ops of a remat included), any other forward.

Stdlib only, like the rest of ``repro.obs``.
"""

from __future__ import annotations

import re

# scopes, in the order a round runs them
CLIENT_MODEL = "client_model"
SKETCH_ENCODE = "sketch_encode"
MERGE = "merge"
SERVER_STATE = "server_state"
UNSKETCH = "unsketch"
TOPK = "topk"
SPARSE_APPLY = "sparse_apply"
SCOPES = (CLIENT_MODEL, SKETCH_ENCODE, MERGE, SERVER_STATE, UNSKETCH, TOPK,
          SPARSE_APPLY)

# the client model splits into two layers
FORWARD = "forward"
BACKWARD = "backward"
BACKWARD_MARK = "transpose(jvp("

# Pallas kernel names (``pl.pallas_call(name=...)``) and their layers: a
# custom call carries no op_name, so its instruction name says where it is
ENCODE_KERNEL = "fetchsgd_encode"
ESTIMATE_KERNEL = "fetchsgd_estimate"
MOMENTUM_ERROR_KERNEL = "fetchsgd_momentum_error"
TOPK_MASK_KERNEL = "fetchsgd_topk_mask"
KERNELS = {ENCODE_KERNEL: SKETCH_ENCODE, ESTIMATE_KERNEL: UNSKETCH,
           MOMENTUM_ERROR_KERNEL: SERVER_STATE, TOPK_MASK_KERNEL: SERVER_STATE}

LAYERS = (FORWARD, BACKWARD, SKETCH_ENCODE, MERGE, SERVER_STATE, UNSKETCH,
          TOPK, SPARSE_APPLY)

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_NAME = re.compile(r"%([\w.-]+)")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|\w+_computations?)="
                     r"\{?(%[\w.-]+(?:, %[\w.-]+)*)")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
# do no work: never placed by their neighbours, and never read from
_INERT = ("parameter", "constant")
# move no data: looked through to the work on their other side
_PLUMBING = ("tuple", "get-tuple-element", "bitcast")


def instruction_name(text: str) -> str | None:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``; None for a
    line that defines no instruction."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else None


def layer_of(op_name: str) -> str | None:
    """The layer of one op_name path, or None outside every scope."""
    scopes = [part for part in op_name.split("/") if part in SCOPES]
    if not scopes:
        return None
    scope = scopes[-1]
    if scope == CLIENT_MODEL:
        return BACKWARD if BACKWARD_MARK in op_name else FORWARD
    return scope


def kernel_layer(name: str) -> str | None:
    """The layer of an instruction named after a kernel of :data:`KERNELS`
    (``fetchsgd_encode.3``, or ``fetchsgd_encode`` alone)."""
    return KERNELS.get(name.split(".", 1)[0])


def _parse(hlo_text: str) -> dict:
    """``{name: (opcode, operands, called names, computation, own layer)}``
    for each instruction, in the text's order."""
    out: dict = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        name = instruction_name(line)
        if name is None:
            continue
        rest = line[line.index(" = ") + 3:]
        op = _OPCODE.search(rest)
        if op is None:
            continue
        depth, i = 1, op.end()
        while depth and i < len(rest):
            depth += {"(": 1, ")": -1}.get(rest[i], 0)
            i += 1
        layer = kernel_layer(name)
        meta = _OP_NAME.search(rest)
        if layer is None and meta:
            layer = layer_of(meta.group(1))
        called = [c for group in _CALLED.findall(rest[i:])
                  for c in _NAME.findall(group)]
        out[name] = (op.group(1), _NAME.findall(rest[op.end():i]), called,
                     comp, layer)
    return out


def op_layers(hlo_text: str) -> dict[str, str]:
    """``{instruction name: layer}`` for the instructions of a compiled
    module's text (``compiled.as_text()``).

    An instruction is placed by its kernel name, else by the innermost
    scope of its op_name.  One that XLA made without a scope (a copy, a
    prefetch, a fusion that lost its metadata, loop-invariant code hoisted
    out of a scope) takes the layer of its nearest work: first of what it
    reads, then of what reads it, named work before work placed by this
    rule; else the layer of the instruction that calls its computation.
    Tuples and bitcasts are looked through.  Parameters and constants are
    never placed, and what no rule reaches is left out: a reader counts it
    as unnamed."""
    insts = _parse(hlo_text)
    named = {n: v[4] for n, v in insts.items() if v[4] is not None}
    layer = dict(named)
    users: dict = {}
    callers: dict = {}
    for name, (_, operands, called, _, _) in insts.items():
        for o in operands:
            users.setdefault(o, []).append(name)
        for c in called:
            callers.setdefault(c, name)

    def through(names, step, seen):
        for n in names:
            op = insts[n][0] if n in insts else _INERT[0]
            if op in _PLUMBING:
                if n not in seen:
                    seen.add(n)
                    yield from through(step(n), step, seen)
            elif op not in _INERT:
                yield n

    todo = [n for n, v in insts.items()
            if n not in layer and v[0] not in _INERT]
    while todo:
        left = []
        for name in todo:
            _, operands, _, comp, _ = insts[name]
            near = (*through(operands, lambda n: insts[n][1], set()),
                    *through(users.get(name, ()),
                             lambda n: users.get(n, ()), set()))
            found = next((named[n] for n in near if n in named),
                         next((layer[n] for n in (*near, callers.get(comp))
                               if n in layer), None))
            if found is None:
                left.append(name)
            else:
                layer[name] = found
        if len(left) == len(todo):
            break
        todo = left
    return layer

"""Which phase of the CADA step each device op belongs to, and each phase's
device time in the measured window.

The program names the phases of its trainer step with ``jax.named_scope``
(``cada.grad_eval``, ``cada.pack``, ``cada.rule_state``, ``cada.gate``,
``cada.eq3``, ``cada.server_update``); the compiler keeps the innermost
name in each instruction's ``op_name`` metadata. A trace keeps only
instruction names (``traces.load_xplane``), so the map from instruction to
phase comes from the compiled step itself: ``step_scopes`` rebuilds the
cell's step as ``harness.Program`` builds it and reads its HLO text. The
persistent compile cache serves that rebuild, so it is the executable that
ran and its instruction names are the trace's.

``op_scopes`` maps every instruction of every computation. An instruction
the compiler made with no metadata at all (a copy, a split reduction, the
loop a gather became), or with an instruction's name for its whole
``op_name`` (``convert.57``: a pass of the partitioned four-chip step names
what it makes after what it replaced, with no name stack), takes the phase
of the fusion body it calls, else of the instruction that calls its
computation (a ``while`` or a fusion), else of its operands, else of its
users; one whose ``op_name`` names no phase stays unscoped.
"""
from __future__ import annotations

import re
import sys
import time
from collections import Counter

from bench import traces

PREFIX = "cada."
_SCOPE = re.compile(r"cada\.([A-Za-z_]\w*)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION_NAME = re.compile(r"[a-z][\w\-]*\.\d+")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)"
                     r"|\b(?:branch|called)_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def innermost(op_name: str) -> str | None:
    """The innermost ``cada.<name>`` component of an ``op_name``, or None:
    ``jit(step)/cada.eq3/jit(f)/while/body/cada.pack/slice`` gives
    ``cada.pack``, ``vmap(transpose(jvp(cada.grad_eval)))/dot_general``
    gives ``cada.grad_eval``."""
    found = _SCOPE.findall(op_name)
    return PREFIX + found[-1] if found else None


def _operands(line: str, opcode: str) -> list:
    """Names of the instructions the instruction line reads."""
    start = line.find(f" {opcode}(")
    if start < 0:
        return []
    i = start + len(opcode) + 2
    depth, j = 1, i
    while j < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        j += 1
    return _OPERAND.findall(line[i:j - 1])


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: "cada.<phase>" | None}`` for every instruction
    of every computation of a compiled module's text
    (``compiled.as_text()``)."""
    scope, bare, calls, operands = {}, [], {}, {}
    members, comp_of, callers = {}, {}, {}
    comp = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if " = " not in line.split("(", 1)[0]:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
            continue
        name, opcode = traces.split_instruction(line)
        if not opcode:
            continue
        members.setdefault(comp, []).append(name)
        comp_of[name] = comp
        op_name = _OP_NAME.search(line)
        if op_name and not _INSTRUCTION_NAME.fullmatch(op_name.group(1)):
            scope[name] = innermost(op_name.group(1))
        else:
            scope[name] = None
            bare.append(name)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        for one, many in _CALLEE.findall(line):
            for callee in [one] if one else _OPERAND.findall(many):
                callers.setdefault(callee, []).append(name)
        operands[name] = _operands(line, opcode)
    users: dict = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)

    def from_called(name):
        found = Counter(scope[n] for n in members.get(calls.get(name), ())
                        if scope.get(n))
        return found.most_common(1)[0][0] if found else None

    for name in bare:
        scope[name] = from_called(name)
    pending = [n for n in bare if scope[n] is None]
    while pending:
        left = []
        for name in pending:
            near = [scope.get(c) for c in callers.get(comp_of[name], ())] \
                + [scope.get(o) for o in operands[name]] \
                + [scope.get(u) for u in users.get(name, ())]
            near = [s for s in near if s]
            if near:
                scope[name] = near[0]
            else:
                left.append(name)
        if len(left) == len(pending):
            break
        pending = left
    return scope


def scope_seconds(trace: dict, op_map: dict) -> tuple:
    """``({scope: seconds}, unscoped seconds)``: the device time of each
    phase's ops inside the window (ops clipped to it), the ``while`` /
    ``conditional`` / ``call`` containers left out as ``traces.summarize``
    leaves them out, mean over devices."""
    lo, hi = traces.window_of(trace)
    devs = trace["devices"]
    totals, unscoped = {}, 0.0
    for evs in devs.values():
        for e in evs:
            if traces.opcode(e) in traces.CONTAINERS:
                continue
            inside = traces.length(traces.clip([(e[1], e[1] + e[2])], lo, hi))
            if not inside:
                continue
            s = op_map.get(e[0])
            if s is None:
                unscoped += inside
            else:
                totals[s] = totals.get(s, 0.0) + inside
    n = max(len(devs), 1)
    return ({s: v / n * 1e-9 for s, v in totals.items()},
            unscoped / n * 1e-9)


def _abstract(tree, shardings):
    """``tree`` as ShapeDtypeStructs laid out by ``shardings``: one
    sharding for every leaf, or a tree of them shaped as ``tree``."""
    import jax
    if isinstance(shardings, jax.sharding.Sharding):
        shardings = jax.tree.map(lambda _: shardings, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s,
            weak_type=getattr(x, "weak_type", False)), tree, shardings)


def step_scopes(view) -> dict:
    """The instruction -> phase map of the cell's compiled step:
    ``Program(cell, devices).jitted.lower(<state and batch as
    ShapeDtypeStructs laid out as the program lays them out>).compile()``.
    On several chips that is the partitioned module, the collectives that
    the SPMD partitioner put in among its instructions."""
    import jax

    from bench.harness import Program
    from bench.refstep import seed_key
    t = time.perf_counter()
    prog = Program(view.cell, jax.devices()[:view.chips])
    state = _abstract(jax.eval_shape(prog.init, seed_key(0)),
                      prog.state_sharding)
    batch = _abstract(prog.batch(0, 0), prog.batch_sharding)
    text = prog.jitted.lower(state, batch).compile().as_text()
    op_map = op_scopes(text)
    log(f"scopes: step rebuilt in {time.perf_counter() - t:.2f} s, "
        f"{sum(s is not None for s in op_map.values())} of {len(op_map)} "
        f"instructions in a phase")
    return op_map


def phases(view):
    """``({scope: seconds}, unscoped seconds)`` of the traced window, or
    None: on a run without a device trace, or where the step carries no
    ``cada.`` scope (code without the names, or an executable compiled
    from it). Worked out once per run: the readers of every phase share
    it through the view."""
    if view.summary is None:
        return None
    cached = getattr(view, "scope_phases", False)
    if cached is not False:
        return cached
    op_map = step_scopes(view)
    result = None
    if any(s is not None for s in op_map.values()):
        result = scope_seconds(view.trace, op_map)
        scoped = sum(result[0].values())
        op_s = scoped + result[1]
        log(f"scopes: {100.0 * scoped / op_s if op_s else 0.0:.3f}% of the "
            f"window's op time in a phase ({scoped:.4f} of {op_s:.4f} s; "
            f"device busy {view.summary.busy_s:.4f} s); "
            + ", ".join(f"{s} {v:.4f} s"
                        for s, v in sorted(result[0].items())))
    view.scope_phases = result
    return result


def phase_ms(view, scope: str):
    """Device time of ``scope`` per completed window step, in ms, or None
    where ``phases`` is None or the phase has no op in the window."""
    got = phases(view)
    if got is None or view.steps <= 0:
        return None
    secs = got[0].get(scope)
    if not secs:
        return None
    return secs / view.steps * 1e3

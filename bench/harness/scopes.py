"""Device time by named scope: which layer of the program an op belongs to.

The program opens ``jax.named_scope`` blocks at its layer boundaries
(``attention``, ``mlp``, ``head``, ``ivf.*``, ``tail.*``). They reach the
compiled program as each instruction's name stack, its ``op_name``
(``jit(decode_step)/while/body/attention/dot_general``). A TPU trace names
an op by its instruction (``%fusion.7 = ...``) and gives its events no
name stack; the trace file's ``/host:metadata`` plane holds the HLO of
every program that ran, instructions and name stacks included.
:func:`name_stacks` reads them from there.

An op counts under a scope when the scope is a component of its name
stack, a transform's wrapper aside (``vmap(tail.draw)`` is ``tail.draw``),
within the named program's executions, on the first device plane. Times
are self times: a ``while`` op's body ops count once, under their own
scopes.
"""
from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path

from harness import cells, trace as tr

TRACE_DIR = cells.ROOT / ".bench_trace"  # where the runners trace a window
METADATA = "/host:metadata"
HLO_PROTO = "Hlo Proto"

Stacks = dict[str, dict[str, str]]  # program -> instruction -> name stack


# ---------------------------------------------------------------- protobuf
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of each field of the message in
    ``buf[start:end]``: an int for a varint, a (start, end) span of
    ``buf`` for a length-delimited value, None for fixed-width ones."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield field, v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _sub(buf: bytes, span, field: int):
    """The values of ``field`` in the message at ``span``."""
    return [v for f, v in _fields(buf, *span) if f == field]


def _hlo_stacks(buf: bytes, span) -> dict[str, str]:
    """Instruction -> name stack of an ``HloProto``: its ``hlo_module`` (1),
    the module's ``computations`` (3), their ``instructions`` (2), each
    one's ``name`` (1) and ``metadata`` (7) ``op_name`` (2)."""
    out = {}
    for module in _sub(buf, span, 1):
        for comp in _sub(buf, module, 3):
            for instr in _sub(buf, comp, 2):
                name, stack = "", ""
                for f, v in _fields(buf, *instr):
                    if f == 1:
                        name = _str(buf, v)
                    elif f == 7:
                        stack = "".join(_str(buf, s)
                                        for s in _sub(buf, v, 2))
                out[name] = stack
    return out


def name_stacks(path: Path) -> Stacks:
    """Every program of a trace file (``jit_f(<id>)``, as its ``XLA
    Modules`` events name it) with each instruction's name stack, from the
    HLO the ``/host:metadata`` plane holds (XSpace planes are field 1; a
    plane's name 2, event metadata 4, stat metadata 5; an event
    metadata's name 2 and stats 5; a stat's metadata id 1, bytes 6)."""
    return _name_stacks(str(path), Path(path).stat().st_mtime_ns)


@functools.lru_cache(maxsize=4)
def _name_stacks(path: str, _mtime_ns: int) -> Stacks:
    buf = Path(path).read_bytes()
    for plane in (v for f, v in _fields(buf) if f == 1):
        fields = list(_fields(buf, *plane))
        if not any(f == 2 and _str(buf, v) == METADATA for f, v in fields):
            continue
        hlo_ids = set()
        for f, v in fields:
            if f == 5:  # map entry: key 1, value 2 (a stat's id 1, name 2)
                entry = dict(_fields(buf, *v))
                if 2 in entry and any(_str(buf, n) == HLO_PROTO
                                      for n in _sub(buf, entry[2], 2)):
                    hlo_ids.add(entry.get(1))
        out = {}
        for f, v in fields:
            if f != 4:
                continue
            for md in _sub(buf, v, 2):
                name, protos = "", []
                for g, w in _fields(buf, *md):
                    if g == 2:
                        name = _str(buf, w)
                    elif g == 5:
                        stat = dict(_fields(buf, *w))
                        if stat.get(1) in hlo_ids and 6 in stat:
                            protos.append(stat[6])
                for p in protos:
                    out[name] = _hlo_stacks(buf, p)
        return out
    return {}


def latest_trace(root: Path = TRACE_DIR) -> Path | None:
    """The trace file the benchmark wrote last (the run's own)."""
    files = list(root.glob("*/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime_ns) if files else None


def stacks_of(ctx) -> Stacks:
    """The name stacks a reader's context gives (``ctx.stacks``, as a
    recorded slice does), else those of the newest trace file."""
    if getattr(ctx, "stacks", None) is not None:
        return ctx.stacks
    path = latest_trace()
    return name_stacks(path) if path else {}


# ---------------------------------------------------------------- scopes
def components(stack: str) -> list[str]:
    """``jit(f)/vmap(tail.draw)/sort`` -> ``["f", "tail.draw", "sort"]``:
    each component of a name stack without its transform wrappers."""
    return [re.sub(r"^(?:[\w.-]*\()+|\)+$", "", c) for c in stack.split("/")]


def in_scope(stack: str, scope: str) -> bool:
    return scope in components(stack)


def _table(stacks: Stacks, program: str, names: set[str]
           ) -> dict[str, str] | None:
    """The name stacks of ``names``, the instructions the trace shows in
    executions of ``program`` (``jit_f(<id>)``). The metadata may name a
    program by another id than its executions do, and hold it more than
    once (compiled twice): a program whose HLO holds every one of
    ``names`` fits, and the fitting ones have to agree."""
    if program in stacks:
        cands = [stacks[program]]
    else:
        cands = [t for name, t in stacks.items()
                 if tr.base_name(name) == tr.base_name(program)]
    fits = [{n: t[n] for n in names} for t in cands if names <= t.keys()]
    if not fits or any(f != fits[0] for f in fits[1:]):
        return None
    return fits[0]


def program_ops(events: list[tr.Event], stacks: Stacks, module: str
                ) -> tuple[int, list[tuple[str, float]]]:
    """(executions of ``module``, [(name stack, self ns)] of the ops that
    ran inside them) on the first device plane; no ops where the trace
    holds no HLO that fits the program."""
    execs = sorted(tr.module_events(events, module), key=lambda e: e.start_ns)
    if not execs:
        return 0, []
    plane = execs[0].plane
    ops = [e for e in events if e.plane == plane and e.line == tr.OPS]
    starts = [e.start_ns for e in execs]
    inside: list[tuple[str, str, float]] = []  # (program, op, self ns)
    for op, t in tr.self_times(ops):
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and op.end_ns <= execs[i].end_ns:
            inside.append((execs[i].name, tr.op_name(op.name).lstrip("%"), t))
    tables = {}
    for program in {p for p, _, _ in inside}:
        tables[program] = _table(
            stacks, program, {n for p, n, _ in inside if p == program})
        if tables[program] is None:
            return len(execs), []
    return len(execs), [(tables[p][n], t) for p, n, t in inside]


def scope_ns(ctx, module: str, scopes) -> tuple[int, float, float]:
    """(executions of ``module``, self ns of its ops under any of
    ``scopes``, self ns of all its ops)."""
    n, ops = program_ops(ctx.events, stacks_of(ctx), module)
    under = sum(t for st, t in ops if any(in_scope(st, s) for s in scopes))
    return n, under, sum(t for _, t in ops)


def per_execution_ms(ctx, module: str, scopes, per: int = 1) -> float | None:
    """Self time under ``scopes`` per execution of ``module`` (and per
    ``per`` units of work an execution does), in ms; None where the
    program did not run or no op of it lies under the scopes (a program
    built without them)."""
    n, ns, _ = scope_ns(ctx, module, scopes)
    return ns / (n * per) / 1e6 if n and ns else None


def coverage(ctx, module: str, scopes) -> float | None:
    """Share of ``module``'s op self time under any of ``scopes``."""
    _, under, total = scope_ns(ctx, module, scopes)
    return under / total if total else None

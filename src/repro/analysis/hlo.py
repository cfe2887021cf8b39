"""Structured HLO walk: per-line op parse + input-output alias table.

This replaces the four copy-pasted ``re.findall(r"all-to-all...")``
counters: instead of substring-matching anywhere in the module text, each
instruction line is parsed into ``(var, shape, opcode)`` — so operand
references, metadata ``op_name`` strings and comments can never be
miscounted, and async ``-start``/``-done`` pairs collapse to one op.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Sequence, Tuple, Union


class HloOp(NamedTuple):
    var: str       # "%all-to-all.1" (or "" when unparsable)
    shape: str     # "s32[8,4]{1,0}" or "(s32[4]{0}, s32[4]{0})"
    opcode: str    # normalized: "all-to-all-start" -> "all-to-all"
    line_no: int   # 1-based line in the module text


class HloAlias(NamedTuple):
    output_index: str  # tuple index of the aliased output, e.g. "0" or "1,2"
    param: int         # parameter number it aliases
    param_index: str   # tuple index within the parameter (usually "")
    kind: str          # "may-alias" | "must-alias"


class HloProgram(NamedTuple):
    ops: Tuple[HloOp, ...]
    aliases: Tuple[HloAlias, ...]


# Collective opcodes the budget rule understands.
COLLECTIVE_OPS = frozenset({
    "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute", "collective-broadcast", "all-gather-done",
})

_ALIAS_ENTRY = re.compile(
    r"\{\s*([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([\d,\s]*)\}"
    r"(?:\s*,\s*([a-z-]+))?\s*\)")
_SHAPE_TOKEN = re.compile(r"\S+")
_OPCODE = re.compile(r"([A-Za-z][\w-]*)\(")


def _balanced_brace_span(line: str, marker: str) -> str:
    """Contents of the ``{...}`` (nested braces balanced) right after
    ``marker`` in ``line``; "" when the marker is absent."""
    at = line.find(marker)
    if at < 0:
        return ""
    i = line.find("{", at)
    if i < 0:
        return ""
    depth = 0
    for j in range(i, len(line)):
        depth += line[j] == "{"
        depth -= line[j] == "}"
        if depth == 0:
            return line[i + 1:j]
    return ""


def _parse_rhs(rhs: str) -> Union[Tuple[str, str], None]:
    """Parse ``<shape> <opcode>(...)`` — the RHS of one instruction."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):           # tuple shape: balanced-paren scan
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        m = _SHAPE_TOKEN.match(rhs)
        if not m:
            return None
        shape, rest = m.group(0), rhs[m.end():]
    m = _OPCODE.match(rest.lstrip())
    if not m:
        return None
    return shape, m.group(1)


def normalize_opcode(opcode: str) -> Union[str, None]:
    """Collapse async pairs: ``*-start`` is the op, ``*-done`` is dropped
    (returns None).  Plain opcodes pass through."""
    if opcode.endswith("-done") or opcode.endswith("-update"):
        return None
    if opcode.endswith("-start"):
        return opcode[:-len("-start")]
    return opcode


def parse_hlo(text: str) -> HloProgram:
    """Walk compiled HLO text line by line into structured ops + aliases."""
    ops: List[HloOp] = []
    aliases: List[HloAlias] = []
    for line_no, line in enumerate(text.splitlines(), start=1):
        s = line.strip()
        if not s:
            continue
        if s.startswith("HloModule"):
            span = _balanced_brace_span(s, "input_output_alias=")
            for om in _ALIAS_ENTRY.finditer(span):
                aliases.append(HloAlias(
                    output_index=om.group(1).replace(" ", ""),
                    param=int(om.group(2)),
                    param_index=om.group(3).replace(" ", ""),
                    kind=om.group(4) or "may-alias"))
            continue
        # instruction lines: "[ROOT] %var = <shape> <opcode>(...)"
        eq = s.find(" = ")
        if eq < 0:
            continue
        lhs = s[:eq].strip()
        if lhs.startswith("ROOT "):
            lhs = lhs[5:].strip()
        if not lhs.startswith("%") and not re.match(r"^[\w.-]+$", lhs):
            continue
        parsed = _parse_rhs(s[eq + 3:])
        if parsed is None:
            continue
        shape, opcode = parsed
        norm = normalize_opcode(opcode)
        if norm is None:
            continue
        ops.append(HloOp(var=lhs, shape=shape, opcode=norm, line_no=line_no))
    return HloProgram(ops=tuple(ops), aliases=tuple(aliases))


def op_counts(program: Union[HloProgram, str]) -> Dict[str, int]:
    if isinstance(program, str):
        program = parse_hlo(program)
    counts: Dict[str, int] = {}
    for op in program.ops:
        counts[op.opcode] = counts.get(op.opcode, 0) + 1
    return counts


def collective_counts(program: Union[HloProgram, str]) -> Dict[str, int]:
    """Counts restricted to cross-device collectives (budget domain)."""
    return {k: v for k, v in op_counts(program).items()
            if k in COLLECTIVE_OPS}


def input_output_aliases(program: Union[HloProgram, str]
                         ) -> Tuple[HloAlias, ...]:
    if isinstance(program, str):
        program = parse_hlo(program)
    return program.aliases


def compiled_text(jitted, args: Sequence) -> str:
    """Lower + compile a jitted callable and return its HLO text."""
    return jitted.lower(*args).compile().as_text()


def count_op(program: Union[HloProgram, str], opcode: str) -> int:
    return op_counts(program).get(opcode, 0)


def count_all_to_all(jitted, args: Sequence) -> int:
    """Drop-in replacement for the four regex counters in the tier-1
    tests: number of all-to-all ops (async pairs counted once) in the
    compiled module of ``jitted(*args)``."""
    return count_op(compiled_text(jitted, tuple(args)), "all-to-all")


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def scope_table(text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """Instruction name -> scope, for the instructions of HLO module text
    whose ``op_name`` metadata lies under one of ``scopes`` (the innermost
    named one wins).  An instruction without such metadata takes the scope
    of the instruction that calls its computation (a fusion's, or a loop's
    whose body it is), where every caller agrees; instructions the compiler
    made without metadata are thereby put where they came from."""
    wanted = set(scopes)
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}          # instruction -> its computation
    callers: Dict[str, List[str]] = {}  # computation -> calling instructions
    comp = ""
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith((" ", "\t")):
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        home[name] = comp
        meta = _OP_NAME.search(line)
        if meta:
            hit = [p for p in meta.group(1).split("/") if p in wanted]
            if hit:
                own[name] = hit[-1]
        called = _CALLED.findall(line)
        for b in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        for c in called:
            callers.setdefault(c, []).append(name)

    memo: Dict[str, str] = {}

    def scope_of(name: str) -> str:      # HLO calls form no cycle
        if name not in memo:
            up = {scope_of(c) for c in callers.get(home[name], [])}
            memo[name] = own.get(name) or (up.pop() if len(up) == 1 else "")
        return memo[name]

    return {name: scope_of(name) for name in home if scope_of(name)}

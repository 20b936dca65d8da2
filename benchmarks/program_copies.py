#!/usr/bin/env python3
"""What a compiled program copies: every ``copy`` and every fusion with a
large result, with the parameter it reads.

    python benchmarks/program_copies.py [--min-mib 1] [--reads REGEX]
                                        [--json | --fingerprint] <hlo.txt> ...

Input is optimized HLO text: ``jax.jit(f).lower(...).compile().as_text()``,
or a ``*after_optimizations.txt`` that a server wrote under
``XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text"``.  No JAX is
imported and nothing runs: the text is read as text.

A step program should stream each weight once, where it lies.  When the
compiler wants a stack of weights in another layout it copies the whole
stack once a program (a ``copy`` in the entry computation) and then
slices the copy a layer (a fusion in the layer loop's body whose result
is a layer's matrix): both show here, the first as ``program``, the
second as ``x4 x39`` (inside a loop of 4 inside... of 39 trips), each
with the entry parameter it was traced back to through tuples, loop
carries, bitcasts and fusion operands.  No time is in this table: a
trace says what an op costs (``kbench/trace_reduce.py`` names ops
``<program>/<HLO name>``, the names listed here).

``--fingerprint`` prints a SHA-256 of the text without its metadata and
without the bytes of serialized Pallas kernels (both carry source paths
and lines, which move when a file is edited; the program does not): two
trees compile the same XLA program where the fingerprints agree.
"""

import argparse
import hashlib
import json
import re
import sys
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "c64": 8,
    "c128": 16, "token": 0,
}
_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s*([a-z][a-z\-]*)\(")
# ops a value passes through unchanged on its way back to a parameter
_TRANSPARENT = {"bitcast", "reshape", "copy", "copy-start", "copy-done",
                "convert", "transpose", "optimization-barrier",
                "dynamic-slice", "slice"}


@dataclass
class Instr:
    name: str
    shape: str            # the result's type as printed, layout included
    opcode: str
    operands: list        # operand names, in order
    attrs: str            # what follows the operand list
    literal: str = ""     # a constant's or a parameter's printed value
    root: bool = False

    @property
    def bytes(self) -> int:
        return shape_bytes(self.shape)


@dataclass
class Computation:
    name: str
    entry: bool = False
    instrs: dict = field(default_factory=dict)     # name -> Instr, in order
    params: dict = field(default_factory=dict)     # index -> name


def shape_bytes(shape: str) -> int:
    """Bytes of a printed type, tuples summed; tiling's padding is not
    counted."""
    total = 0.0
    for dtype, dims, _ in _ARRAY.findall(shape):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return int(total)


def _split_type(rest: str) -> tuple:
    """``<type> <opcode>(...)...`` -> (type, remainder); a tuple's type is
    parenthesised and may nest."""
    if not rest.startswith("("):
        cut = rest.index(" ")
        return rest[:cut], rest[cut:]
    depth = 0
    for i, c in enumerate(rest):
        depth += c == "("
        depth -= c == ")"
        if depth == 0:
            return rest[:i + 1], rest[i + 1:]
    raise ValueError(f"unbalanced type in {rest[:80]!r}")


def _operand_span(rest: str) -> tuple:
    """``(a, b), attrs`` -> ("a, b", ", attrs")."""
    depth = 0
    for i, c in enumerate(rest):
        depth += c in "([{"
        depth -= c in ")]}"
        if depth == 0:
            return rest[1:i], rest[i + 1:]
    raise ValueError(f"unbalanced operands in {rest[:80]!r}")


def parse(text: str) -> dict:
    """The module's computations by name."""
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_HEAD.match(line)
            if m:
                cur = Computation(m.group(2), entry=bool(m.group(1)))
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        shape, rest = _split_type(m.group(3))
        op = _OPCODE.match(rest)
        if not op:
            continue
        try:
            inner, attrs = _operand_span(rest[op.end() - 1:])
        except ValueError:
            # a printed constant cut short by whoever kept the file
            inner, attrs = "", ""
        ins = Instr(m.group(2), shape, op.group(1),
                    re.findall(r"%([\w.\-]+)", inner), attrs,
                    literal=inner if op.group(1) in ("constant", "parameter")
                    else "", root=bool(m.group(1)))
        if ins.opcode == "parameter":
            cur.params[int(inner)] = ins.name
        cur.instrs[ins.name] = ins
    return comps


def _callers(comps: dict) -> dict:
    """computation name -> (calling computation, calling instruction)."""
    out = {}
    for comp in comps.values():
        for ins in comp.instrs.values():
            for callee in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)",
                    ins.attrs):
                out.setdefault(callee, (comp, ins))
            m = re.search(r"branch_computations=\{([^}]*)\}", ins.attrs)
            if m:
                for callee in re.findall(r"%([\w.\-]+)", m.group(1)):
                    out.setdefault(callee, (comp, ins))
    return out


def _trips(ins: Instr, comps: dict) -> str:
    """A loop's trip count: the compiler's own note where it prints one,
    else the constant its condition compares the counter with (``i <
    N`` from 0, what a ``lax.scan`` lowers to), else ``?``."""
    m = re.search(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)', ins.attrs)
    if m:
        return m.group(1)
    m = re.search(r"condition=%?([\w.\-]+)", ins.attrs)
    cond = comps.get(m.group(1)) if m else None
    root = next((i for i in cond.instrs.values() if i.root), None) \
        if cond else None
    if root is not None and root.opcode == "compare" \
            and "direction=LT" in root.attrs:
        bound = cond.instrs.get(root.operands[-1])
        if bound is not None and bound.opcode == "constant" \
                and bound.literal.isdigit():
            return bound.literal
    return "?"


def where(comp: Computation, comps: dict, callers: dict) -> str:
    """``program`` for the entry computation, else the trip counts of
    the loops round the computation, outermost first."""
    loops = []
    while comp.name in callers:
        comp, ins = callers[comp.name]
        if ins.opcode == "while":
            loops.append("x" + _trips(ins, comps))
    return " ".join(reversed(loops)) or "program"


def sources(comp: Computation, name: str, comps: dict, callers: dict,
            seen=None) -> set:
    """The entry parameters ``name`` was made of, followed back through
    the ops that move a value and change nothing of it."""
    seen = set() if seen is None else seen
    if (comp.name, name) in seen or name not in comp.instrs:
        return set()
    seen.add((comp.name, name))
    ins = comp.instrs[name]
    if ins.opcode == "parameter":
        if comp.entry:
            return {ins.name}
        if comp.name not in callers:
            return set()
        up, call = callers[comp.name]
        index = next(i for i, n in comp.params.items() if n == name)
        if call.opcode == "conditional":
            return set()
        # a loop's body takes the loop's one operand; a fusion's or a
        # call's parameter i is operand i
        return sources(up, call.operands[0 if call.opcode == "while"
                                         else index],
                       comps, callers, seen)
    if ins.opcode == "get-tuple-element":
        index = int(re.search(r"index=(\d+)", ins.attrs).group(1))
        return _tuple_element(comp, ins.operands[0], index, comps, callers,
                              seen)
    if ins.opcode in _TRANSPARENT:
        return sources(comp, ins.operands[0], comps, callers, seen)
    if ins.opcode == "fusion" and _moves_only(ins, comps):
        # a layer's slice of a stack, a transpose: still that parameter
        out = set()
        for operand in ins.operands:
            out |= sources(comp, operand, comps, callers, seen)
        return out
    return set()


def _moves_only(fusion: Instr, comps: dict) -> bool:
    """Whether a fusion computes nothing: its computation holds only
    ops that move a value (and the constants that index them)."""
    m = re.search(r"calls=%?([\w.\-]+)", fusion.attrs)
    comp = comps.get(m.group(1)) if m else None
    return comp is not None and all(
        i.opcode in _TRANSPARENT or i.opcode in ("parameter", "constant")
        for i in comp.instrs.values())


def _tuple_element(comp, name, index, comps, callers, seen) -> set:
    ins = comp.instrs.get(name)
    if ins is None:
        return set()
    if ins.opcode == "tuple":
        return sources(comp, ins.operands[index], comps, callers, seen)
    if ins.opcode == "while":          # a loop hands its carry on
        return _tuple_element(comp, ins.operands[0], index, comps, callers,
                              seen)
    if ins.opcode == "parameter" and comp.name in callers:
        up, call = callers[comp.name]
        if call.opcode == "while":
            return _tuple_element(up, call.operands[0], index, comps,
                                  callers, seen)
    if ins.opcode in ("get-tuple-element", "bitcast", "copy",
                      "optimization-barrier"):
        return sources(comp, name, comps, callers, seen)
    return set()


def _pretty(comps: dict, entry_param: str) -> str:
    """``params['moe']['q_b']`` where the entry parameter's metadata
    names the argument's path, else the HLO name."""
    entry = next(c for c in comps.values() if c.entry)
    attrs = entry.instrs[entry_param].attrs
    m = re.search(r'op_name="((?:[^"\\]|\\.)*)"', attrs)
    return m.group(1).replace("\\'", "'") if m else entry_param


def program_name(text: str) -> str:
    m = re.match(r"HloModule\s+([\w.\-]+)", text)
    return m.group(1) if m else "?"


def fingerprint(text: str) -> str:
    """SHA-256 of the program without what moves when a source file is
    edited: the header's stack-frame tables, per-op metadata, the
    bytes of a serialized Pallas kernel (a change inside a kernel does
    not show here; one round it does)."""
    first = re.search(r"^(?:%|ENTRY )", text, re.M)
    body = text[first.start():] if first else text
    body = re.sub(r",?\s*metadata=\{(?:[^{}\"]|\"(?:[^\"\\]|\\.)*\")*\}", "",
                  body)
    # a Mosaic kernel's serialized body carries its callers' paths and
    # source lines too: what XLA compiled round it is fingerprinted,
    # the kernel's own bytes are not
    body = re.sub(r"[A-Za-z0-9+/=]{200,}", "<kernel>", body)
    return hashlib.sha256(body.encode()).hexdigest()


def large_ops(text: str, min_bytes: int = 1 << 20) -> list:
    """Every ``copy`` and every fusion outside a fused computation whose
    result is at least ``min_bytes``."""
    comps = parse(text)
    callers = _callers(comps)
    fused = {callee for callee, (_, ins) in callers.items()
             if ins.opcode == "fusion"}
    entry = next((c for c in comps.values() if c.entry), None)
    entry_bytes = {} if entry is None else {
        n: entry.instrs[n].bytes for n in entry.params.values()}
    rows = []
    for comp in comps.values():
        if comp.name in fused:
            continue
        for ins in comp.instrs.values():
            if ins.opcode not in ("copy", "fusion") or ins.bytes < min_bytes:
                continue
            reads = set()
            for operand in ins.operands:
                reads |= sources(comp, operand, comps, callers)
            # of a fusion's operands, the large ones: a stack of weights
            # or a pool, not the step's scalars
            reads = sorted(r for r in reads
                           if ins.opcode == "copy"
                           or entry_bytes.get(r, 0) >= min_bytes)
            op_name = re.search(r'op_name="((?:[^"\\]|\\.)*)"', ins.attrs)
            # a fusion whose result has an operand's very type updates
            # that operand where it lies (a cache write into the pool)
            in_place = ins.opcode == "fusion" and any(
                comp.instrs[o].shape == ins.shape for o in ins.operands
                if o in comp.instrs)
            rows.append({
                "where": where(comp, comps, callers),
                "op": ins.name,
                "kind": ins.opcode + (" (in place)" if in_place else ""),
                "shape": ins.shape,
                "mib": round(ins.bytes / 2**20, 2),
                "reads": [_pretty(comps, r) for r in reads],
                "op_name": op_name.group(1).replace("\\'", "'")
                .split("/")[-1] if op_name else "",
            })
    rows.sort(key=lambda r: (r["where"] != "program", -r["mib"]))
    return rows


def render(rows: list) -> str:
    lines = ["| where | op | kind | result | MiB | reads | traced from |",
             "| --- | --- | --- | --- | --- | --- | --- |"]
    for r in rows:
        lines.append("| {where} | `{op}` | {kind} | `{shape}` | {mib} | {reads} | {op_name} |"
                     .format(**{**r, "reads": ", ".join(
                         f"`{x}`" for x in r["reads"]) or "-"}))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+", help="optimized HLO text")
    ap.add_argument("--min-mib", type=float, default=1.0)
    ap.add_argument("--reads", metavar="REGEX", default="",
                    help="only ops traced to a parameter whose path matches")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--fingerprint", action="store_true",
                    help="print each program's fingerprint and no table")
    args = ap.parse_args(argv)
    out = []
    for path in args.files:
        with open(path, errors="replace") as f:
            text = f.read()
        if args.fingerprint:
            print(fingerprint(text), program_name(text), path)
            continue
        rows = [r for r in large_ops(text, int(args.min_mib * 2**20))
                if not args.reads
                or any(re.search(args.reads, x) for x in r["reads"])]
        if args.json:
            out.append({"file": path, "program": program_name(text),
                        "ops": rows})
        else:
            print(f"## {program_name(text)} ({path}): {len(rows)} ops of "
                  f"{args.min_mib:g} MiB or more\n\n{render(rows)}\n")
    if args.json:
        json.dump(out, sys.stdout, indent=1)
        print()
    return 0


if __name__ == "__main__":
    sys.exit(main())

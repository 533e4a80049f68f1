"""The step XLA compiled, accounted by node: the BYTES of the executable `fit`
runs, booked by the scopes `step_anatomy` books its time by.

`observability/trace.py` names every operation of the step `ff.<kind>.<name>`
and `benchmark/step_anatomy.py` reads those names back from a device trace:
where the TIME went. This module reads the same names from the compiled
program's text: who holds the peak, what a plan keeps for its backward pass,
what lies in `S(1)`, beside XLA's own totals (`memory_analysis()`), one of
which no other reader of the program shows (`peak_memory_in_bytes`).

`account(compiled)` is a pure function of a `jax.stages.Compiled`: the chip's
executable, a compile for a described chip and a CPU compile alike. What it
reads of the text is the ENTRY computation, which XLA prints in schedule
order (`is_scheduled=true`) and where every result is a buffer. It returns

- `memory`: XLA's totals, and `total` as the benchmark adds `step_hbm_gb`;
- `rows`: per `(phase, kind, name)` of `parse_scope` and per operation family
  (the names `benchmark/trace_reduce.py` prints), the buffers the scope's
  instructions make (`written_bytes`), read (`read_bytes`) and make in
  memory space 1 (`s1_bytes`);
- `walk`: a liveness walk over the schedule, an ESTIMATE of XLA's assignment
  that says how good it is (`walk_over_xla`): the peak, who holds it, and
  what the forward pass leaves for the backward pass. A result, and a donated
  argument, lies in an allocation that outlives the run; a temporary that
  lives wholly while such an allocation holds nothing adds no byte
  (`lodged_bytes`, `_Walk._lodge`);
- `not_walked`: what the walk did not enter, by name.

Bytes are a buffer's own: its dimensions rounded up to the tiles of its
layout (`bf16[8192,64]{1,0:T(8,128)(2,1)}` takes twice its elements), the
plain product where the layout has no tile (`_nbytes`, which the ENTRY
listings of `tests/test_ssm_node_compiles_for_v5e.py` print, is that plain
product). A dtype or a layout field the sizer does not know raises with the
instruction's line: nothing is counted 0 for being unreadable.

`note_step` is called where the step is lowered (`analysis/lowering.py`, under
`compile/lower_step`) and keeps a reference to what was lowered: the MLIR
module JAX's own lowering cache holds anyway. Nothing is computed until
`last()` (or `FFModel.step_account()`) asks: then the noted lowering is
compiled, which finds the executable `fit` runs in JAX's in-memory cache
where the process has compiled it (no second trace, no second compile), and
the account is made once, under the span `step_account`. `report()` is the
text of it, as `setup_report()` is of set-up.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.observability.trace import _table, parse_scope, record_span

# bytes an element of each HLO primitive type (`xla_data.proto`); a `token`
# holds no data. The sub-byte types take a byte an element unless the layout
# packs them (`E(4)`).
_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u2": 1, "u4": 1, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f16": 2, "bf16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 1, "token": 0,
}
# a shape of a known dtype anywhere in a text (`shapes_of` is also given
# whole programs, whose metadata holds `operands[0]` and the like)
_SHAPE = re.compile(
    r"\b(" + "|".join(sorted(_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]"
)
# a shape as a result type spells it, dtype known or not, with its layout
_LEAF = re.compile(r"([A-Za-z_]\w*)\[([^\]]*)\](\{[^{}]*\})?")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
# opcodes whose result is no buffer of its own in the listings
_NO_BUFFER = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant")
# opcodes that only forward what their operands hold, and do not read it
_FORWARDS = (
    "get-tuple-element", "tuple", "bitcast", "opt-barrier", "add-dependency",
)
# opcodes that run a computation of their own, which the walk does not enter
# (a reduction's `to_apply` works on scalars, and the chip's `async-start`
# wraps the one operation it is named after, `slice-start.3`)
_CALLS = ("while", "conditional", "call")
_LAYOUT_FIELD = re.compile(r"(T|S|E|L)((?:\([^()]*\))+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ALIAS = re.compile(r"\{([0-9, ]*)\}: \((\d+), \{([0-9, ]*)\}")
_CALLED = re.compile(
    r"\b(condition|body|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}"
)
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv|async-collective)(-start|-done)?"
    r"(\.\d+|/.*)?$"
)
ARGUMENTS = "arguments"


# -- the ENTRY parser ----------------------------------------------------------


def _computation_lines(text: str, entry: bool = True) -> Dict[str, List[str]]:
    """`{computation: its instruction lines}` of an HLO module's text; with
    `entry` the ENTRY computation alone."""
    found, name = {}, None
    for line in text.splitlines():
        if name is None:
            m = _COMPUTATION.match(line)
            if m and (line.startswith("ENTRY") or not entry):
                name = m.group(1)
                found[name] = []
        elif line.startswith("}"):
            if entry:
                break
            name = None
        else:
            found[name].append(line)
    return found


def _instructions(lines):
    rows = []
    for line in lines:
        m = _INSTRUCTION.match(line)
        if m:
            name, result, opcode, rest = m.groups()
            depth, end = 1, len(rest)
            for at, c in enumerate(rest):
                depth += (c == "(") - (c == ")")
                if depth == 0:
                    end = at
                    break
            operands = re.findall(r"%([\w.\-]+)", rest[:end])
            rows.append((name, result, opcode, operands, line))
    return rows


def entry_instructions(text):
    """[(name, result, opcode, operand names, line)] of the ENTRY
    computation, in schedule order."""
    lines = _computation_lines(text)
    if not lines:
        raise ValueError("the text holds no ENTRY computation")
    return _instructions(next(iter(lines.values())))


def shapes_of(result):
    """[(dtype, dims)] of an instruction's result, a tuple's members each."""
    return [
        (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
        for m in _SHAPE.finditer(result)
    ]


def _nbytes(result):
    """The elements' bytes of a result, a tuple's members together: the
    plain product of the dimensions, no layout read."""
    total = 0
    for dtype, dims in shapes_of(result):
        n = _BYTES[dtype]
        for d in dims:
            n *= d
        total += n
    return total


def listing_row(name, result, opcode, line, read, written):
    """One line of an ENTRY listing: instruction, opcode, a fusion's kind,
    the bytes read and written, the result's type, the end of its scope."""
    kind = re.search(r"kind=(k\w+)", line)
    scope = _OP_NAME.search(line)
    return (
        f"{name:42s} {opcode:12s} {kind.group(1) if kind else '':8s}"
        f" reads {read / 1e6:7.1f} MB writes {written / 1e6:7.1f} MB  "
        f"{result[:64]:64s} {scope.group(1)[-56:] if scope else ''}"
    )


def family(name: str, opcode: str, line: str) -> str:
    """The operation family of an instruction, under the name
    `benchmark/trace_reduce.py` prints for its device events
    (`op_family(short_name(...))`): the instruction's name without XLA's
    serial number, a fusion with its kind (`fusion.kLoop`), a Pallas kernel
    as `pallas/<kernel>`."""
    if 'custom_call_target="tpu_custom_call"' in line:
        name = "pallas/" + name
    elif _COLLECTIVE.match(opcode) and not _COLLECTIVE.match(name):
        name = f"{opcode}/{name}"
    elif name.startswith("fusion"):
        kind = re.search(r"\bkind=(k[A-Za-z]+)", line)
        if kind:
            name = f"fusion.{kind.group(1)}{name[len('fusion'):]}"
    return re.sub(r"[._]\d+$", "", name)


# -- sizes ---------------------------------------------------------------------


def _laid_out(dims, layout, bits):
    """(physical dims, bits an element, memory space, tail alignment) of
    `dims` under a layout's text, `2,1,0:T(8,128)(2,1)S(1)`: the dimensions
    major to minor, each tile rounding the minor ones up to whole tiles.
    Raises ValueError at a field it does not know."""
    order, _, fields = layout.partition(":")
    minor_to_major = [int(d) for d in order.split(",") if d]
    if len(minor_to_major) == len(dims):
        dims = [dims[d] for d in reversed(minor_to_major)]
    space, align, at = 0, 1, 0
    for m in _LAYOUT_FIELD.finditer(fields):
        if m.start() != at:
            break
        at = m.end()
        groups = re.findall(r"\(([^()]*)\)", m.group(2))
        if m.group(1) == "T":
            for tile in groups:
                tile = [int(t) for t in tile.split(",") if t]
                k = len(tile)
                dims = [1] * (k - len(dims)) + dims
                dims = (
                    dims[: len(dims) - k]
                    + [-(-d // t) for d, t in zip(dims[-k:], tile)]
                    + tile
                )
        elif m.group(1) == "S":
            space = int(groups[0])
        elif m.group(1) == "E":
            bits = int(groups[0])
        else:
            align = int(groups[0])
    if at != len(fields):
        raise ValueError(fields[at:])
    return dims, bits, space, align


def _leaf(dtype, dims_text, layout, line):
    """(bytes, memory space) of one array of a result type."""
    if dtype not in _BYTES:
        raise ValueError(f"no size known for dtype {dtype!r} in: {line.strip()}")
    try:
        dims = [int(d) for d in dims_text.split(",") if d]
    except ValueError:
        raise ValueError(
            f"cannot size the dimensions [{dims_text}] in: {line.strip()}"
        ) from None
    bits, space, align = 8 * _BYTES[dtype], 0, 1
    if layout:
        try:
            dims, bits, space, align = _laid_out(dims, layout[1:-1], bits)
        except ValueError:
            raise ValueError(
                f"cannot size the layout {layout} in: {line.strip()}"
            ) from None
    n = 1
    for d in dims:
        n *= d
    n = -(-n // align) * align
    return -(-n * bits // 8), space


def _type_tree(result, line):
    """A result type as a tree: a tuple of trees, or `(bytes, space)`."""
    at = 0
    # XLA numbers the members of a long tuple: `/*index=5*/`
    result = re.sub(r"/\*.*?\*/", "", result)

    def parse():
        nonlocal at
        while result[at : at + 1] == " ":
            at += 1
        if result[at : at + 1] == "(":
            at += 1
            members = []
            while True:
                while result[at : at + 1] in (" ", ","):
                    at += 1
                if result[at : at + 1] == ")":
                    at += 1
                    return tuple(members)
                members.append(parse())
        m = _LEAF.match(result, at)
        if m is None:
            raise ValueError(f"cannot read the result type of: {line.strip()}")
        at = m.end()
        return [_leaf(m.group(1), m.group(2), m.group(3), line)]

    tree = parse()
    if result[at:].strip():
        raise ValueError(f"cannot read the result type of: {line.strip()}")
    return tree


def _ids(tree):
    """Every buffer id under a value's tree."""
    if isinstance(tree, tuple):
        return [b for member in tree for b in _ids(member)]
    return list(tree)


def _at_path(tree, path):
    for i in path:
        tree = tree[i]
    return tree


def _path(text):
    return tuple(int(i) for i in text.replace(" ", "").split(",") if i)


def _aliases(text, key):
    """[(path in the output, operand or parameter number, path in it)] of an
    `input_output_alias={...}` or `output_to_operand_aliasing={...}`."""
    _, found, inside = text.partition(key + "={")
    if not found:
        return []
    depth = 1
    for end, c in enumerate(inside):
        depth += (c == "{") - (c == "}")
        if depth == 0:
            break
    return [
        (_path(out), int(number), _path(within))
        for out, number, within in _ALIAS.findall(inside[:end])
    ]


# -- the account ----------------------------------------------------------------


class _Walk:
    """The ENTRY computation's buffers: who makes each, how large, where, and
    from which instruction to which it lives."""

    def __init__(self, text):
        self.rows = entry_instructions(text)
        self.size: List[int] = []
        self.space: List[int] = []
        self.owner: List[int] = []  # index into rows
        self.born: List[int] = []
        self.last: List[int] = []  # the last reader's index
        self.free: set = set()  # outputs that come back in a donated argument
        self.lodged: set = set()  # temporaries laid where a result will lie
        self.made: List[List[int]] = [[] for _ in self.rows]
        self.read: List[List[int]] = [[] for _ in self.rows]
        names = [_OP_NAME.search(r[4]) for r in self.rows]
        self.scope = [
            parse_scope(m.group(1)) if m else ("unattributed", "", "")
            for m in names
        ]
        # an argument's own name on another instruction is XLA's relayout of
        # the argument: no name of its own
        arguments = {
            m.group(1) for m, r in zip(names, self.rows)
            if m and r[2] == "parameter"
        }
        self._named = [
            m is not None and (r[2] == "parameter" or m.group(1) not in arguments)
            for m, r in zip(names, self.rows)
        ]
        # rows whose scope is what they move's -> the buffers they hand on
        self.unnamed: Dict[int, List[int]] = {}
        self.called: List[Tuple[int, str, str]] = []  # row, attribute, name
        self.fusions = 0
        self._walk(text)

    def _new(self, tree, i, born):
        """The tree of a result type with a fresh buffer at every array."""
        if isinstance(tree, tuple):
            return tuple(self._new(member, i, born) for member in tree)
        [(size, space)] = tree
        self.size.append(size)
        self.space.append(space)
        self.owner.append(i)
        self.born.append(born)
        self.last.append(born)
        self.made[i].append(len(self.size) - 1)
        return [len(self.size) - 1]

    def _walk(self, text):
        value: Dict[str, object] = {}
        parameters: Dict[int, object] = {}
        root = None
        for i, (name, result, opcode, operands, line) in enumerate(self.rows):
            missing = [o for o in operands if o not in value]
            if missing:
                raise ValueError(
                    f"operand {missing[0]} is read before the schedule makes "
                    f"it in: {line.strip()}"
                )
            held = [value[o] for o in operands]
            tail = line[line.index(opcode + "(") :]
            if opcode == "fusion":
                self.fusions += 1
            elif opcode in _CALLS:
                for m in _CALLED.finditer(tail):
                    names = [m.group(2)] if m.group(2) else re.findall(
                        r"%?([\w.\-]+)", m.group(3)
                    )
                    self.called += [
                        (i, m.group(1) or "branch_computations", n)
                        for n in names
                    ]
            concat = 'custom_call_target="ConcatBitcast"' in line
            if opcode == "parameter":
                tree = self._new(_type_tree(result, line), i, -1)
                parameters[int(tail[len("parameter(") :].split(")")[0])] = tree
            elif opcode == "constant":
                tree = _empty(_type_tree(result, line))
            elif opcode == "get-tuple-element":
                tree = held[0][int(re.search(r"index=(\d+)", tail).group(1))]
            elif opcode == "tuple":
                tree = tuple(held)
            elif opcode in _FORWARDS or opcode == "while":
                tree = held[0]
            elif concat:
                # pieces laid end to end when they were made: the whole is
                # its pieces, no buffer of its own
                tree = [b for h in held for b in _ids(h)]
            elif opcode.endswith("-done"):
                tree = _done(_type_tree(result, line), held[0], opcode)
            else:
                tree = self._made(i, result, opcode, held, tail, line)
            if opcode not in _FORWARDS and not concat:
                # a real instruction: what it is handed lives until it runs
                seen = dict.fromkeys(b for h in held for b in _ids(h))
                self.read[i] = list(seen)
                for b in seen:
                    self.last[b] = i
                if seen and not self._named[i]:
                    # XLA's own (an asynchronous copy into or out of S(1), a
                    # slice of one): booked to the scope of what it moves
                    scopes = [self.scope[self.owner[b]] for b in seen]
                    self.scope[i] = next(
                        (s for s in scopes if s[0] != "unattributed"), scopes[0]
                    )
                    self.unnamed[i] = _ids(tree)
            value[name] = tree
            if line.lstrip().startswith("ROOT "):
                root = tree
        if root is None:
            raise ValueError("the ENTRY computation has no ROOT instruction")
        header = re.search(r"^HloModule .*$", text, re.M)
        over = {}  # a result's buffer -> the donated argument's it is written over
        for out, parameter, inside in _aliases(
            header.group(0) if header else "", "input_output_alias"
        ):
            ours = _ids(_at_path(parameters[parameter], inside))
            theirs = _ids(_at_path(root, out))
            # the output is written where the donated argument lay: one buffer
            self.free |= set(theirs) - set(ours)
            if len(theirs) == len(ours):  # (not pieces laid end to end)
                over.update(zip(theirs, ours))
        self._scope_what_moves_nothing()
        self._lodge(dict.fromkeys(_ids(root)), over)
        end = len(self.rows)
        for b in _ids(root):
            # what the program returns lies in an allocation of its own, which
            # the caller holds from the start (the only temporaries XLA lays
            # there are `lodged`)
            self.born[b], self.last[b] = -1, end
        for b, owner in enumerate(self.owner):
            if self.rows[owner][2] == "parameter":
                self.last[b] = end

    def _scope_what_moves_nothing(self):
        """An instruction XLA added without a name that moves nothing a scope
        made (an argument prefetched into `S(1)` or relaid, the zeros a
        gradient is put together in) is booked to the scope of the first
        reader of what it makes: the node it was made for. Last to first, so
        that a chain of them (a copy's start, its done) ends at a name."""
        readers: Dict[int, List[int]] = {}
        for i, read in enumerate(self.read):
            for b in read:
                readers.setdefault(b, []).append(i)
        for i in reversed(range(len(self.rows))):
            if self._named[i] or self.scope[i][0] != "unattributed":
                continue
            # what it makes, or hands on of what it was handed (a copy's done)
            later = [
                next((j for j in readers.get(b, ()) if j > i), None)
                for b in self.made[i] + self.unnamed.get(i, [])
            ]
            later = [j for j in later if j is not None]
            if later and self.rows[i][2] != "parameter":
                self.scope[i] = self.scope[min(later)]

    def _lodge(self, results, over):
        """Mark the temporaries XLA can lay in an allocation that outlives the
        run, from the lifetimes as walked. Such an allocation is a result's
        (held by the caller from the start, empty until the result is made)
        or a donated argument's (empty from its last reader until the result
        in its place is made); XLA's buffer assignment gives it, besides, to
        any buffer that fits and lives wholly while it is empty, one at a time
        (each lies at offset 0), the largest buffers choosing first: the q
        projection of an attention node ALONE lies where its weights' gradient
        is put together, 67 MB the heap never holds. Here the smallest
        allocation that fits takes it."""
        gaps = []  # (bytes, first instruction empty, last instruction empty)
        for b in results:
            if self.space[b] or self.rows[self.owner[b]][2] == "parameter":
                continue
            if b not in over:
                if b not in self.free:
                    gaps.append((self.size[b], 0, self.born[b] - 1))
            elif over[b] != b and not self.space[over[b]]:
                gaps.append(
                    (self.size[over[b]], self.last[over[b]] + 1, self.born[b] - 1)
                )
        gaps = sorted(g for g in gaps if g[1] <= g[2])
        if not gaps:
            return
        sizes = [g[0] for g in gaps]
        held: List[List[Tuple[int, int]]] = [[] for _ in gaps]
        temporaries = sorted(
            (
                b for b in range(len(self.size))
                if b not in results and 0 < self.size[b] <= sizes[-1]
                and not self.space[b]
                and self.rows[self.owner[b]][2] != "parameter"
            ),
            key=lambda b: (-self.size[b], self.born[b]),
        )
        for b in temporaries:
            born, last = self.born[b], self.last[b]
            for k in range(bisect.bisect_left(sizes, self.size[b]), len(gaps)):
                _, first, until = gaps[k]
                if first <= born and last <= until and all(
                    last < s or e < born for s, e in held[k]
                ):
                    held[k].append((born, last))
                    self.lodged.add(b)
                    break

    def _made(self, i, result, opcode, held, tail, line):
        """The value of an instruction that makes buffers: a fresh one at every
        array of its result but those it writes over an operand."""
        shape = _type_tree(result, line)
        over = {}  # path in the result -> the operand's buffers there
        for out, operand, inside in _aliases(tail, "output_to_operand_aliasing"):
            over[out] = _at_path(held[operand], inside)
        if opcode == "dynamic-update-slice":
            over[()] = held[0]
        elif opcode.endswith("-start") and isinstance(shape, tuple):
            # an asynchronous start's result carries its operands beside its
            # output: `((operands), output, context)`, a copy's `(output,
            # operand, context)`, a collective's `(operand, output)`
            if isinstance(shape[0], tuple):
                over[(0,)] = tuple(held[: len(shape[0])])
            elif opcode == "copy-start":
                over[(1,)] = held[0]
            elif opcode in ("all-gather-start", "collective-permute-start"):
                over[(0,)] = held[0]

        def build(tree, path):
            if path in over:
                return over[path]
            if isinstance(tree, tuple):
                return tuple(
                    build(member, path + (k,)) for k, member in enumerate(tree)
                )
            return self._new(tree, i, i)

        return build(shape, ())

    # -- readings ----------------------------------------------------------

    def last_named_reader(self, b):
        """The last instruction with a name of its own that reads buffer `b`,
        itself or through the copies XLA made of it; None where none does
        (a result of the program is read by its caller)."""
        reader = self.last[b]
        if reader >= len(self.rows) or reader == self.owner[b]:
            return None
        if reader not in self.unnamed:
            return reader
        onward = [
            self.last_named_reader(c) for c in self.unnamed[reader]
            if self.last[c] > reader
        ]
        return max((r for r in onward if r is not None), default=None)

    def counted(self, b):
        """Bytes buffer `b` adds to the device's memory while it lives: none
        where it is a donated argument's own or lies where a result will
        (`_lodge`), none outside memory space 0."""
        if b in self.free or b in self.lodged or self.space[b]:
            return 0
        return self.size[b]

    def live_bytes(self):
        """Bytes of memory space 0 live at each instruction of the schedule."""
        end = len(self.rows)
        delta = [0] * (end + 1)
        for b in range(len(self.size)):
            n = self.counted(b)
            if n:
                delta[max(self.born[b], 0)] += n
                delta[min(self.last[b], end - 1) + 1] -= n
        live, total = [], 0
        for d in delta[:end]:
            total += d
            live.append(total)
        return live


def _empty(tree):
    if isinstance(tree, tuple):
        return tuple(_empty(member) for member in tree)
    return []


def _done(shape, start, opcode):
    """What an asynchronous `*-done` forwards of its start's value: the
    output the start made beside its operands (`_Walk._made`), the whole
    where the start's result was its output alone (an all-reduce's)."""
    if not isinstance(start, tuple):
        return start
    if isinstance(start[0], tuple):
        return start[1]
    if opcode == "copy-done":
        return start[0]
    if opcode in ("all-gather-done", "collective-permute-done"):
        return start[1]
    if isinstance(shape, tuple) and len(shape) == len(start):
        return start  # a combined collective: a result an operand
    raise ValueError(f"cannot tell what {opcode} forwards of its start")


def _memory(stats) -> dict:
    """XLA's own totals of a compiled program (`memory_analysis()`)."""
    memory = {
        "arguments": int(stats.argument_size_in_bytes),
        "outputs": int(stats.output_size_in_bytes),
        "aliased": int(stats.alias_size_in_bytes),
        "temp": int(stats.temp_size_in_bytes),
        "code": int(stats.generated_code_size_in_bytes),
    }
    # donated state comes back in place: outputs that alias arguments are
    # counted once (`benchmark/run.py` adds `step_hbm_gb` so)
    memory["total"] = (
        memory["arguments"] + memory["outputs"] - memory["aliased"]
        + memory["temp"]
    )
    peak = getattr(stats, "peak_memory_in_bytes", None)
    memory["xla_peak"] = None if peak is None else int(peak)
    memory["total_less_xla_peak"] = (
        None if peak is None else memory["total"] - int(peak)
    )
    return memory


def account(compiled) -> dict:
    """The account of a `jax.stages.Compiled` (module docstring)."""
    return account_of_text(
        compiled.as_text(), _memory(compiled.memory_analysis())
    )


def account_of_text(text: str, memory: Optional[dict] = None) -> dict:
    """`account` from a compiled module's text and, where there are any,
    XLA's totals as `_memory` lays them out."""
    walk = _Walk(text)
    rows: Dict[tuple, dict] = {}
    for i, (name, _, opcode, _, line) in enumerate(walk.rows):
        if opcode == "parameter" or not walk.made[i]:
            continue
        written = sum(walk.size[b] for b in walk.made[i])
        in_s1 = sum(walk.size[b] for b in walk.made[i] if walk.space[b] == 1)
        read = sum(walk.size[b] for b in walk.read[i])
        row = rows.setdefault(walk.scope[i], dict(_zero(), families={}))
        by_family = row["families"].setdefault(
            family(name, opcode, line), _zero()
        )
        for table in (row, by_family):
            table["instructions"] += 1
            table["written_bytes"] += written
            table["read_bytes"] += read
            table["s1_bytes"] += in_s1

    live = walk.live_bytes()
    peak_at = max(range(len(live)), key=live.__getitem__)
    held: Dict[tuple, int] = {}
    kept: Dict[tuple, int] = {}
    for b in range(len(walk.size)):
        owner = walk.owner[b]
        scope = walk.scope[owner]
        if walk.rows[owner][2] == "parameter":
            scope = (ARGUMENTS, "", "")
        if max(walk.born[b], 0) <= peak_at <= walk.last[b] and walk.counted(b):
            held[scope] = held.get(scope, 0) + walk.counted(b)
        if scope[0] == "fwd" and owner not in walk.unnamed:
            # XLA's copies of it (a prefetch into S(1) in the backward pass)
            # are the same bytes again, and no reader of their own
            reader = walk.last_named_reader(b)
            if reader is not None and walk.scope[reader][0] == "bwd":
                kept[scope[1:]] = kept.get(scope[1:], 0) + walk.size[b]

    bodies = _computation_lines(text, entry=False) if walk.called else {}
    not_walked = []
    for i, attribute, name in walk.called:
        inside = _instructions(bodies.get(name, ()))
        not_walked.append({
            "computation": name,
            "called_by": walk.rows[i][0],
            "as": f"{walk.rows[i][2]}/{attribute}",
            "scope": walk.scope[i],
            "instructions": len(inside),
            # what its instructions make, were every one a buffer of its own
            "written_bytes": sum(
                _nbytes(result) for _, result, opcode, _, _ in inside
                if opcode not in _NO_BUFFER
            ),
        })
    peak = live[peak_at]
    xla_peak = (memory or {}).get("xla_peak")
    at = walk.rows[peak_at]
    return {
        "memory": memory,
        "rows": sorted(
            (
                dict(zip(("phase", "kind", "name"), scope), **row)
                for scope, row in rows.items()
            ),
            key=lambda r: -r["written_bytes"],
        ),
        "walk": {
            "instructions": len(walk.rows),
            "buffers": len(walk.size),
            "peak_bytes": peak,
            "lodged_bytes": sum(walk.size[b] for b in walk.lodged),
            "peak_at": {
                "index": peak_at, "instruction": at[0], "opcode": at[2],
                "scope": walk.scope[peak_at],
            },
            "held_at_peak": _sorted(held, ("phase", "kind", "name")),
            "kept_for_backward": _sorted(kept, ("kind", "name")),
            "walk_over_xla": peak / xla_peak if xla_peak else None,
        },
        "not_walked": {
            "fusions": walk.fusions,
            "computations": sorted(
                not_walked, key=lambda c: -c["written_bytes"]
            ),
        },
    }


def _zero():
    return {"instructions": 0, "written_bytes": 0, "read_bytes": 0, "s1_bytes": 0}


def _sorted(table, names):
    return [
        dict(zip(names, key), bytes=n)
        for key, n in sorted(table.items(), key=lambda kv: -kv[1])
    ]


# -- the step `fit` runs --------------------------------------------------------

_noted = None  # (instance, what `analysis/lowering.py` lowered last)
_account: Optional[dict] = None


def note_step(instance, program) -> None:
    """Called where the step is lowered: `program` is the
    `jax.stages.Lowered` or the `jax.stages.Compiled` of `instance`'s step.
    Keeps the reference and computes nothing."""
    global _noted, _account
    _noted, _account = (instance, program), None


def noted_instance():
    """The training instance whose step was lowered last, or None."""
    return _noted[0] if _noted else None


def last() -> Optional[dict]:
    """The account of the step lowered last in this process, made on the
    first call and kept; None where no step was lowered."""
    global _account
    if _account is None and _noted is not None:
        with record_span("step_account"):
            program = _noted[1]
            if not hasattr(program, "memory_analysis"):
                program = program.compile()
            _account = account(program)
    return _account


def made_by_kind(of: dict) -> Dict[str, List[int]]:
    """`{kind: [bytes made in S(1), bytes made]}` of an account's rows, a row
    with no kind (`unattributed`) under its phase."""
    by_kind: Dict[str, List[int]] = {}
    for r in of["rows"]:
        cell = by_kind.setdefault(r["kind"] or r["phase"], [0, 0])
        cell[0] += r["s1_bytes"]
        cell[1] += r["written_bytes"]
    return by_kind


def _mb(n):
    return "none" if n is None else f"{n / 1e6:.1f}"


def report(top: int = 12, of: Optional[dict] = None) -> str:
    """What an operator prints to see where a step's bytes are, as text in
    five parts: XLA's totals with the walk's peak beside them, the `top`
    largest holders at the peak, the `top` largest of what the forward pass
    leaves for the backward pass, memory space 1 by kind, and what the walk
    did not enter. `of` is an account; the last step's where none is given."""
    of = last() if of is None else of
    if of is None:
        return "no step was lowered in this process"
    memory, walk = of["memory"] or {}, of["walk"]
    at = walk["peak_at"]
    ratio = walk["walk_over_xla"]
    lines = [
        "memory (MB): " + " ".join(
            f"{k} {_mb(memory.get(k))}" for k in (
                "arguments", "outputs", "aliased", "temp", "total", "xla_peak",
                "total_less_xla_peak", "code",
            )
        ),
        f"walk: peak {_mb(walk['peak_bytes'])} MB at instruction "
        f"{at['index']} of {walk['instructions']} ({at['instruction']}, "
        f"{'/'.join(at['scope'])}), {_mb(walk['lodged_bytes'])} MB of "
        "temporaries laid where a result will lie, walk_over_xla "
        + ("none" if ratio is None else f"{ratio:.4f}"),
        f"held at the peak (the {top} largest of {len(walk['held_at_peak'])}), MB:",
    ]
    lines += _table(
        ("phase", "kind", "name", "MB"),
        [(r["phase"], r["kind"], r["name"], r["bytes"] / 1e6)
         for r in walk["held_at_peak"][:top]],
    )
    kept = walk["kept_for_backward"]
    lines.append(
        f"kept for the backward pass: {_mb(sum(r['bytes'] for r in kept))} MB "
        f"(the {top} largest of {len(kept)}), MB:"
    )
    lines += _table(
        ("kind", "name", "MB"),
        [(r["kind"], r["name"], r["bytes"] / 1e6) for r in kept[:top]],
    )
    by_kind = made_by_kind(of)
    lines.append(
        f"made in S(1): {_mb(sum(c[0] for c in by_kind.values()))} MB of "
        f"{_mb(sum(c[1] for c in by_kind.values()))} MB made, by kind, MB:"
    )
    lines += _table(
        ("kind", "S(1) MB", "made MB"),
        [(k, c[0] / 1e6, c[1] / 1e6) for k, c in sorted(
            by_kind.items(), key=lambda kc: -kc[1][0]
        ) if c[0]][:top],
    )
    bodies = of["not_walked"]["computations"]
    lines.append(
        f"not walked: the inside of {of['not_walked']['fusions']} fusions and "
        f"{len(bodies)} called computations:"
    )
    lines += _table(
        ("computation", "called by", "as", "instructions", "made MB"),
        [(c["computation"], c["called_by"], c["as"], c["instructions"],
          c["written_bytes"] / 1e6) for c in bodies[:top]],
    ) if bodies else []
    return "\n".join(lines)

"""Static execution-contract verification of a compiled step program
(ISSUE 14): determinism census + donation/aliasing audit.

Every elastic-runtime and serving guarantee this repo makes — bitwise
preemption resume (PR 7), chaos-soak recovery to bitwise-identical
params (PR 8), fused-vs-per-step decode parity (PR 12), 1F1B parity
(PR 13) — rests on two properties of the compiled step program that
were, until now, only *tested* on a handful of plans:

1. the program is **deterministic**: same inputs, same bits, every
   process, every run;
2. its **donated buffers are actually aliased** by XLA: the memory
   accounting (MEM001-005) assumes params/optimizer state are updated
   in place, so an unconsumed donation silently doubles parameter
   residency and invalidates every HBM verdict.

This pass reads the SAME `LoweredStepProgram` one XLA compile already
serves for the memory and communication cross-checks
(`analysis/lowering.py`) — the optimized `hlo_text()` plus the compiled
module's `input_output_alias` table — and checks both properties on
every plan the Unity search emits.

Rule ids (catalogued in pcg_verify.PCG_RULE_CATALOG):

DET001 nondeterministic-instruction  the optimized step program contains
       an instruction whose result is not a pure function of its inputs
       across runs/schedules: an `rng-bit-generator` with a non-threefry
       algorithm (backend-varying bit streams), a floating-point
       `scatter` without `unique_indices=true` (colliding updates
       combine in schedule order), or a floating-point cross-replica
       `all-reduce`/`reduce-scatter` with no `channel_id` (the unordered
       cross-replica form — participant grouping is resolved at run
       time) (error)
DET002 fingerprint-drift  the canonicalized step-program fingerprint
       recorded at compile (`search_provenance["exec"]`, persisted to
       the checkpoint directory as `exec_contract.json`) no longer
       matches the program about to run — `fit(resume=True)` or
       `recompile()` built a DIFFERENT program, so "bitwise resume" is
       not on the table (error)
DON001 dropped-donation  an argument the step program donates
       (params/opt-state/KV-cache leaves) was NOT aliased by XLA — the
       donation was dropped (dtype/shape/layout mismatch, or the leaf
       is never consumed), so the old buffer stays live beside its
       update: names the leaf and the wasted bytes (error)
DON002 undonated-state  a large state leaf the memory model priced as
       updated in place is not donated at all (the jit lacks the
       donate annotation for it), so XLA must keep argument AND result
       buffers live exactly where the HBM budget binds (error)

`verify_exec` is the one-call driver behind `ffcheck --exec`;
`FFModel.compile` always runs `analyze_step_program` on the searched
winner into `search_provenance["exec"]`.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.analysis.diagnostics import (
    Diagnostic,
    error,
    human_bytes as _human_bytes,
)

EXEC_RULE_IDS = ("DET001", "DET002", "DON001", "DON002")

# DON002 floor: state leaves below this are never flagged (a handful of
# undonated scalars — step counters, schedules — cannot move an HBM
# verdict; a weight matrix can)
DEFAULT_STATE_BYTES_FLOOR = 1024

CONTRACT_SCHEMA = 1
CONTRACT_FILENAME = "exec_contract.json"

_FLOAT_DTYPES = ("f16", "bf16", "f32", "f64", "f8e4m3fn", "f8e5m2")

# -- canonicalization + fingerprints ----------------------------------------

# optimized-HLO metadata carries absolute source paths and line numbers:
# identical programs built from different checkouts must fingerprint
# identically, so metadata is stripped before hashing
_HLO_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
# the module header's stack-frame tables (file names with the checkout
# path, function names, line/column locations, the frames the
# per-instruction `stack_frame_id`s index): each is a title line followed
# by numbered rows — they describe the call stack, not the program
_HLO_STACK_TABLES_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:\d+ .*\n)*\n?",
    re.MULTILINE,
)
# StableHLO location info (same role as HLO metadata)
_MLIR_LOC_RE = re.compile(r"\s*loc\([^)]*\)")
_MLIR_LOCDEF_RE = re.compile(r"^#loc.*$", re.MULTILINE)


def canonicalize_hlo(hlo_text: str) -> str:
    """The optimized HLO module with per-instruction metadata (source
    paths/lines, op_name) and the header's stack-frame tables stripped —
    what the `hlo_fingerprint` hashes. The fingerprint must not depend on
    the call stack or on where the checkout lives."""
    return _HLO_METADATA_RE.sub("", _HLO_STACK_TABLES_RE.sub("", hlo_text))


def canonicalize_stablehlo(mlir_text: str) -> str:
    """The pre-optimization lowered module with `loc(...)` info stripped
    — what the cheap (no-XLA-compile) `program_fingerprint` hashes."""
    return _MLIR_LOCDEF_RE.sub("", _MLIR_LOC_RE.sub("", mlir_text))


def fingerprint_text(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


# -- determinism census (DET001) --------------------------------------------


@dataclass
class DeterminismFinding:
    """One nondeterministic instruction of the optimized step program."""

    kind: str  # "rng-algorithm" | "nonunique-scatter" | "unordered-reduction"
    name: str  # HLO instruction name
    detail: str

    def to_json(self) -> dict:
        return {"kind": self.kind, "name": self.name, "detail": self.detail}


# the result type is a TUPLE on real lowerings — (new_state, bits) —
# so the type token must span spaces like the scatter/reduce forms
_RNG_RE = re.compile(
    r"%(?P<name>[\w.\-]+)\s*=\s*\(?[a-z0-9\[\],\{\} ]*?\)?\s*"
    r"rng-bit-generator\("
)
_RNG_ALGO_RE = re.compile(r"algorithm=(\w+)")
# plain `rng` (the legacy HLO RNG instruction) is implementation-defined
# per backend — always nondeterministic across backends
_LEGACY_RNG_RE = re.compile(r"%(?P<name>[\w.\-]+)\s*=\s*\S+\s+rng\(")
_SCATTER_RE = re.compile(
    r"%(?P<name>[\w.\-]+)\s*=\s*(?P<type>\(?[a-z0-9\[\],\{\} ]*?\)?)\s"
    r"scatter\("
)
_REDUCE_COLLECTIVE_RE = re.compile(
    r"%(?P<name>[\w.\-]+)\s*=\s*(?P<type>\(?[a-z0-9\[\],\{\} ]*?\)?)\s"
    r"(?P<op>all-reduce|reduce-scatter)(?:-start)?\("
)


def _is_float_type(type_str: str) -> bool:
    return any(
        re.search(rf"\b{re.escape(d)}\[", type_str) for d in _FLOAT_DTYPES
    )


def extract_determinism_findings(
    hlo_text: str,
) -> List[DeterminismFinding]:
    """DET001 census over one optimized HLO module text.

    Flagged forms (each named with the instruction and why):

    - `rng-bit-generator` with a non-threefry algorithm: `rng_default`
      delegates the bit stream to the backend and `rng_philox` differs
      from the threefry stream the carried-key contract (and bitwise
      resume) is defined over. jax's partitionable threefry emits plain
      arithmetic (no rng instruction at all), so ANY rng-bit-generator
      is already a sign the program left the default path.
    - legacy `rng(...)`: implementation-defined per backend.
    - floating-point `scatter` without `unique_indices=true`: colliding
      indices combine in whatever order the backend schedules — float
      addition is not associative, so collisions are run-to-run noise
      on parallel backends. (`select-and-scatter` — pooling backward —
      has a defined selection order and is not flagged; integer
      scatters are order-free.)
    - floating-point `all-reduce`/`reduce-scatter` with no
      `channel_id`: the cross-replica form, whose participant grouping
      is resolved by the runtime per launch. SPMD-partitioned programs
      always carry channel ids; a channel-less float reduction means
      the program took a lowering path the determinism story never
      covered.
    """
    out: List[DeterminismFinding] = []
    for line in hlo_text.splitlines():
        m = _RNG_RE.search(line)
        if m is not None:
            am = _RNG_ALGO_RE.search(line)
            algo = am.group(1) if am else "rng_default"
            if algo != "rng_three_fry":
                out.append(
                    DeterminismFinding(
                        kind="rng-algorithm",
                        name=m.group("name"),
                        detail=f"rng-bit-generator algorithm={algo} "
                        "(backend-defined bit stream; the carried-key "
                        "contract is threefry)",
                    )
                )
            continue
        m = _LEGACY_RNG_RE.search(line)
        if m is not None and "rng-bit-generator" not in line:
            out.append(
                DeterminismFinding(
                    kind="rng-algorithm",
                    name=m.group("name"),
                    detail="legacy rng(...) instruction "
                    "(implementation-defined per backend)",
                )
            )
            continue
        m = _SCATTER_RE.search(line)
        if m is not None:
            if _is_float_type(m.group("type")) and (
                "unique_indices=true" not in line
            ):
                out.append(
                    DeterminismFinding(
                        kind="nonunique-scatter",
                        name=m.group("name"),
                        detail="floating-point scatter without "
                        "unique_indices=true: colliding updates combine "
                        "in schedule order",
                    )
                )
            continue
        m = _REDUCE_COLLECTIVE_RE.search(line)
        if m is not None:
            if _is_float_type(m.group("type")) and (
                "channel_id=" not in line
            ):
                out.append(
                    DeterminismFinding(
                        kind="unordered-reduction",
                        name=m.group("name"),
                        detail=f"cross-replica {m.group('op')} with no "
                        "channel_id: participant grouping is resolved "
                        "at run time",
                    )
                )
    return out


# -- donation / aliasing audit (DON001-DON002) ------------------------------


@dataclass
class DonationRecord:
    """One flattened argument leaf of the step program."""

    arg: str  # top-level argument name ("params", "opt_state", "cache")
    path: str  # keystr within the argument tree ("['n1']")
    flat_index: int  # position in the flattened argument list
    bytes: int  # global (unsharded) leaf bytes
    donated: bool  # the jit donates this leaf
    expected_inplace: bool  # the memory model prices it as aliased
    kept: bool = True  # False: jax pruned the (unused) argument
    aliased: bool = False  # an input_output_alias entry covers it

    @property
    def leaf(self) -> str:
        return f"{self.arg}{self.path}"

    def to_json(self) -> dict:
        return {
            "leaf": self.leaf,
            "bytes": int(self.bytes),
            "donated": self.donated,
            "expected_inplace": self.expected_inplace,
            "kept": self.kept,
            "aliased": self.aliased,
        }


def alias_param_numbers(hlo_text: str) -> Optional[frozenset]:
    """Entry-parameter numbers covered by the compiled module's
    `input_output_alias` table (None when the module declares none)."""
    head = hlo_text.split("\n", 1)[0]
    if "input_output_alias=" not in head:
        return None
    seg = head.split("input_output_alias=", 1)[1]
    # the table ends where the next module attribute begins; entries are
    # `{out_index}: (param_number, {param_index}, kind)`
    end = seg.find(", entry_computation_layout")
    if end >= 0:
        seg = seg[:end]
    return frozenset(int(n) for n in re.findall(r"\(\s*(\d+),\s*\{", seg))


def _leaf_bytes(info) -> int:
    import numpy as np

    shape = getattr(info, "shape", None)
    dtype = getattr(info, "dtype", None)
    if shape is None or dtype is None:
        aval = getattr(info, "aval", None)
        shape = getattr(aval, "shape", ())
        dtype = getattr(aval, "dtype", np.float32)
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(np.dtype(dtype).itemsize)


def _kept_var_idx(lowered) -> Optional[frozenset]:
    """The original flat-argument indices jax kept as entry parameters
    (unused arguments are pruned before XLA sees them). Private jax
    internals — a missing attribute degrades to count-based coverage
    rather than failing the pass."""
    try:
        kept = lowered._lowering.compile_args["kept_var_idx"]
        return frozenset(int(i) for i in kept)
    except Exception:
        return None


@dataclass
class ExecContractAnalysis:
    """One step program's execution contract."""

    hlo_fingerprint: Optional[str]
    program_fingerprint: Optional[str]
    program_key: str
    determinism: List[DeterminismFinding]
    donation: List[DonationRecord]
    num_partitions: int = 1
    state_bytes_floor: int = DEFAULT_STATE_BYTES_FLOOR
    # alias entries the module declares beyond what leaf matching could
    # attribute (None when kept_var_idx was unavailable and per-leaf
    # attribution degraded to counts)
    extra: Dict[str, object] = field(default_factory=dict)

    @property
    def donated(self) -> List[DonationRecord]:
        return [r for r in self.donation if r.donated]

    @property
    def donated_bytes(self) -> int:
        return sum(r.bytes for r in self.donated)

    @property
    def aliased_bytes(self) -> int:
        return sum(r.bytes for r in self.donated if r.aliased)

    @property
    def donation_coverage(self) -> Optional[float]:
        """Aliased fraction of donated bytes (None without donations or
        without a compiled module to read aliases from)."""
        if self.hlo_fingerprint is None or not self.donated:
            return None
        total = self.donated_bytes
        if total == 0:
            return 1.0
        return self.aliased_bytes / total

    @property
    def dropped_donations(self) -> List[DonationRecord]:
        return [r for r in self.donated if not r.aliased]

    @property
    def undonated_state(self) -> List[DonationRecord]:
        return [
            r
            for r in self.donation
            if r.expected_inplace
            and not r.donated
            and r.bytes >= self.state_bytes_floor
        ]


def analyze_step_program(
    lowered,
    compiled=None,
    arg_names: Optional[Sequence[str]] = None,
    expected_inplace: Sequence[int] = (0, 1),
    state_bytes_floor: int = DEFAULT_STATE_BYTES_FLOOR,
) -> ExecContractAnalysis:
    """The execution-contract pass over one lowered (and, when available,
    compiled) step program.

    `lowered` is the `jax.stages.Lowered`; `compiled` the
    `jax.stages.Compiled` (without it only the cheap program fingerprint
    and donation SPEC are recorded — no alias table to audit, no
    optimized HLO to census). `expected_inplace` names the top-level
    argument positions the memory accounting prices as updated in place
    (train step: params=0, opt_state=1; serving: cache=1)."""
    import jax

    args_tree, kwargs_tree = lowered.args_info
    records: List[DonationRecord] = []
    flat_index = 0
    sig_parts: List[str] = []
    for pos, sub in enumerate(args_tree):
        name = (
            arg_names[pos]
            if arg_names is not None and pos < len(arg_names)
            else f"arg{pos}"
        )
        leaves = jax.tree_util.tree_flatten_with_path(sub)[0]
        for path, info in leaves:
            donated = bool(getattr(info, "donated", False))
            records.append(
                DonationRecord(
                    arg=name,
                    path=jax.tree_util.keystr(path),
                    flat_index=flat_index,
                    bytes=_leaf_bytes(info),
                    donated=donated,
                    expected_inplace=pos in tuple(expected_inplace),
                )
            )
            aval = getattr(info, "aval", info)
            sig_parts.append(
                f"{pos}:{jax.tree_util.keystr(path)}:"
                f"{tuple(getattr(aval, 'shape', ()))}:"
                f"{getattr(aval, 'dtype', '?')}:{int(donated)}"
            )
            flat_index += 1
    if kwargs_tree:
        # the step programs this pass covers are all positional; flag
        # rather than silently misnumber
        raise ValueError(
            "analyze_step_program: keyword arguments are not supported "
            f"(got {sorted(kwargs_tree)})"
        )
    program_key = fingerprint_text("|".join(sig_parts))[:16]

    try:
        program_fingerprint = fingerprint_text(
            canonicalize_stablehlo(lowered.as_text())
        )
    except Exception:
        program_fingerprint = None

    hlo_fp = None
    num_partitions = 1
    extra: Dict[str, object] = {}
    if compiled is not None:
        hlo_text = compiled.as_text()
        hlo_fp = fingerprint_text(canonicalize_hlo(hlo_text))
        m = re.search(r"num_partitions=(\d+)", hlo_text.split("\n", 1)[0])
        if m:
            num_partitions = int(m.group(1))
        aliased = alias_param_numbers(hlo_text)
        kept = _kept_var_idx(lowered)
        if kept is not None:
            kept_sorted = sorted(kept)
            position_of = {fi: k for k, fi in enumerate(kept_sorted)}
            for r in records:
                r.kept = r.flat_index in kept
                if r.kept and aliased is not None:
                    r.aliased = position_of[r.flat_index] in aliased
        else:
            # count-based degradation: per-leaf attribution needs jax's
            # kept-argument map; without it, credit aliases to donated
            # leaves in order (exact when nothing was pruned)
            donated_records = [r for r in records if r.donated]
            n_alias = len(aliased or ())
            for k, r in enumerate(donated_records):
                r.aliased = k < n_alias
            extra["alias_attribution"] = "count-based"
        if aliased is not None:
            attributed = sum(1 for r in records if r.aliased)
            extra["unattributed_aliases"] = len(aliased) - attributed
        determinism = extract_determinism_findings(hlo_text)
    else:
        determinism = []

    return ExecContractAnalysis(
        hlo_fingerprint=hlo_fp,
        program_fingerprint=program_fingerprint,
        program_key=program_key,
        determinism=determinism,
        donation=records,
        num_partitions=num_partitions,
        state_bytes_floor=int(state_bytes_floor),
        extra=extra,
    )


def exec_diagnostics(
    analysis: ExecContractAnalysis,
) -> List[Diagnostic]:
    """DET001 + DON001/DON002 over a finished analysis (DET002 is the
    cross-compile fingerprint check — `compare_contract_records`)."""
    diags: List[Diagnostic] = []
    for f in analysis.determinism:
        diags.append(
            error(
                "DET001",
                f"nondeterministic instruction in the step program: "
                f"{f.detail}",
                tensor=f.name,
                hint="a step program with run-to-run noise cannot "
                "deliver bitwise resume or chaos-soak recovery — route "
                "randomness through the carried threefry key and keep "
                "float scatters unique-indexed",
            )
        )
    for r in analysis.dropped_donations:
        note = (
            "the argument is never consumed (jax pruned it)"
            if not r.kept
            else "XLA did not alias it (dtype/shape/layout mismatch, or "
            "the updated value is not returned)"
        )
        diags.append(
            error(
                "DON001",
                f"donated argument {r.leaf} ({_human_bytes(r.bytes)}) "
                f"was not aliased: {note} — the old buffer stays live "
                "beside its update, doubling this leaf's residency "
                "against the memory model's in-place assumption",
                tensor=r.leaf,
                hint="return the updated leaf with identical "
                "shape/dtype (or stop donating a buffer the step does "
                "not rewrite)",
            )
        )
    for r in analysis.undonated_state:
        diags.append(
            error(
                "DON002",
                f"state leaf {r.leaf} ({_human_bytes(r.bytes)}) is "
                "priced as updated in place by the memory model but the "
                "step program does not donate it — XLA keeps argument "
                "AND result buffers live exactly where the HBM budget "
                "binds",
                tensor=r.leaf,
                hint="pass donate_argnums for the state trees "
                "(LINT008 finds the jit site)",
            )
        )
    return diags


# -- contract records (DET002: compile/resume/recompile re-verification) ----


def contract_record(analysis: ExecContractAnalysis) -> dict:
    """The persistable fingerprint record (checkpoint-directory
    `exec_contract.json`, `search_provenance["exec"]` subset)."""
    import jax

    return {
        "schema": CONTRACT_SCHEMA,
        "program_fingerprint": analysis.program_fingerprint,
        "hlo_fingerprint": analysis.hlo_fingerprint,
        "program_key": analysis.program_key,
        "jax_version": jax.__version__,
    }


def compare_contract_records(
    stored: Optional[dict], current: Optional[dict]
) -> Tuple[dict, Optional[Diagnostic]]:
    """DET002: does the program about to run match the recorded one?

    Returns (check_record, diagnostic-or-None). A `program_key` change
    (different argument avals — e.g. a batch-growth recompile) is a
    LEGITIMATELY different program: recorded as `program_changed`, no
    DET002. Matching keys with drifting fingerprints is the lie DET002
    exists to catch."""
    if not stored or not current:
        return {"match": None, "reason": "no recorded contract"}, None
    if stored.get("program_key") != current.get("program_key"):
        return {
            "match": None,
            "program_changed": True,
            "stored_program_key": stored.get("program_key"),
            "program_key": current.get("program_key"),
        }, None
    # compare the strongest fingerprint BOTH sides carry: the optimized
    # HLO when both compiled, else the pre-optimization program
    for fp_field in ("hlo_fingerprint", "program_fingerprint"):
        a, b = stored.get(fp_field), current.get(fp_field)
        if a and b:
            match = a == b
            check = {
                "match": match,
                "fingerprint_field": fp_field,
                "stored": a,
                "current": b,
            }
            if stored.get("jax_version") != current.get("jax_version"):
                check["jax_version_changed"] = (
                    f"{stored.get('jax_version')} -> "
                    f"{current.get('jax_version')}"
                )
            if match:
                return check, None
            return check, error(
                "DET002",
                "step-program fingerprint drift: the compiled program "
                f"no longer matches the recorded contract ({fp_field} "
                f"{a[:12]} -> {b[:12]}) — bitwise resume is not "
                "guaranteed for this run",
                hint="the model/optimizer/loss definition, compile "
                "flags, or jax version changed since the contract was "
                "recorded; re-anchor deliberately (delete "
                f"{CONTRACT_FILENAME}) if the change is intended",
            )
    return {"match": None, "reason": "no comparable fingerprint"}, None


def write_contract_record(directory: str, record: dict) -> str:
    path = os.path.join(directory, CONTRACT_FILENAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def read_contract_record(directory: str) -> Optional[dict]:
    path = os.path.join(directory, CONTRACT_FILENAME)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


# -- drivers ----------------------------------------------------------------

# the train-step argument names every backend shares
# (`_step(params, opt_state, batch_inputs, label, rng)`)
STEP_ARG_NAMES = ("params", "opt_state", "batch", "label", "rng")


def analyze_lowered_step(
    lowered_step, state_bytes_floor: int = DEFAULT_STATE_BYTES_FLOOR
) -> ExecContractAnalysis:
    """The pass over a shared `LoweredStepProgram`
    (analysis/lowering.py)."""
    return analyze_step_program(
        lowered_step.lowered,
        lowered_step.compiled,
        arg_names=STEP_ARG_NAMES,
        expected_inplace=(0, 1),
        state_bytes_floor=state_bytes_floor,
    )


def verify_exec(
    pcg,
    mapping: Optional[dict] = None,
    machine_spec=None,
    lowered=None,
    state_bytes_floor: int = DEFAULT_STATE_BYTES_FLOOR,
) -> Tuple[ExecContractAnalysis, List[Diagnostic]]:
    """One-call driver (ffcheck --exec): lower the plan's donated train
    step (unless a shared `LoweredStepProgram` is supplied) and run the
    determinism + donation audit."""
    if lowered is None:
        from flexflow_tpu.analysis.lowering import lower_plan

        lowered = lower_plan(pcg, mapping, machine_spec=machine_spec)
    analysis = analyze_lowered_step(
        lowered, state_bytes_floor=state_bytes_floor
    )
    return analysis, exec_diagnostics(analysis)


def step_program_fingerprint(
    instance, loss_attrs, label_dtype=None, params=None, opt_state=None
) -> dict:
    """The cheap (trace-only, no XLA compile) contract record for ANY
    training backend — what the DP/local backends persist beside their
    checkpoints for the resume-time DET002 check. Lowers the instance's
    donated step against zero-filled example arguments; the canonical
    StableHLO hashes everything bitwise resume depends on (graph, loss,
    optimizer constants, dtypes, donation), without paying an XLA
    compile on backends whose compile path never lowers statically."""
    from flexflow_tpu.analysis.lowering import (
        lower_step_trace,
    )

    lowered = lower_step_trace(
        instance,
        loss_attrs,
        label_dtype=label_dtype,
        params=params,
        opt_state=opt_state,
    )
    analysis = analyze_step_program(
        lowered, None, arg_names=STEP_ARG_NAMES, expected_inplace=(0, 1)
    )
    return contract_record(analysis)


# -- rendering (ffcheck --exec) ---------------------------------------------


def format_exec_table(analysis: ExecContractAnalysis) -> str:
    """Human-readable contract report (`ffcheck --exec`)."""
    lines = [
        f"program fingerprint: {analysis.program_fingerprint}",
        f"optimized-HLO fingerprint: {analysis.hlo_fingerprint} "
        f"(num_partitions={analysis.num_partitions})",
        "leaf                                 bytes      donated  aliased",
    ]
    for r in analysis.donation:
        if not r.donated and not r.expected_inplace:
            continue
        note = "" if r.kept else "  (pruned)"
        lines.append(
            f"{r.leaf:<36} {_human_bytes(r.bytes):>9}  "
            f"{'yes' if r.donated else 'NO':>7}  "
            f"{'yes' if r.aliased else 'NO':>7}{note}"
        )
    cov = analysis.donation_coverage
    lines.append(
        "donation coverage: "
        + (f"{100.0 * cov:.1f}% of donated bytes aliased" if cov is not None
           else "n/a (no compiled module)")
    )
    if analysis.determinism:
        lines.append("nondeterministic instructions:")
        for f in analysis.determinism:
            lines.append(f"  {f.kind:<20} {f.name}: {f.detail}")
    else:
        lines.append("nondeterministic instructions: none")
    return "\n".join(lines)


def exec_summary_json(analysis: ExecContractAnalysis) -> dict:
    """The `ffcheck --exec --json` per-file summary object (one line per
    file beside the per-diagnostic lines, mirroring the --memory/--comm
    contract): stable schema v1 — the field tuple is pinned by
    tests/test_exec_contract.py."""
    cov = analysis.donation_coverage
    by_kind: Dict[str, int] = {}
    for f in analysis.determinism:
        by_kind[f.kind] = by_kind.get(f.kind, 0) + 1
    return {
        "exec": 1,  # schema version
        "hlo_fingerprint": analysis.hlo_fingerprint,
        "program_fingerprint": analysis.program_fingerprint,
        "program_key": analysis.program_key,
        "num_partitions": int(analysis.num_partitions),
        "donated_leaves": len(analysis.donated),
        "donated_bytes": int(analysis.donated_bytes),
        "aliased_leaves": sum(1 for r in analysis.donated if r.aliased),
        "aliased_bytes": int(analysis.aliased_bytes),
        "donation_coverage": None if cov is None else round(cov, 4),
        "dropped_donations": [
            r.to_json() for r in analysis.dropped_donations
        ],
        "undonated_state_leaves": [
            r.to_json() for r in analysis.undonated_state
        ],
        "determinism_findings": [
            f.to_json() for f in analysis.determinism
        ],
        "determinism_by_kind": by_kind,
        "state_bytes_floor": int(analysis.state_bytes_floor),
    }

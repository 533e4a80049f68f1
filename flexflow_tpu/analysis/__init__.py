"""Static verification layer (ISSUE 4; memory analysis added by ISSUE 10:
`memory_accounting` + `memory_analysis` — MEM001-MEM003, `ffcheck
--memory`, and the machine-mapping DPs' feasibility pruner all read one
shared accounting, and `FFModel.compile` records the winner's per-device
peaks in `search_provenance["memory"]`; communication analysis added by
ISSUE 11: `comm_analysis` + the shared `lowering` helper — COMM001-
COMM004, `ffcheck --comm`, the HLO collective census cross-checked
against the DP's movement-edge predictions, recorded in
`search_provenance["comm"]` and beside the plan audit).

The passes and a driver:

- `pcg_verify`: well-formedness verifier for any ParallelComputationGraph —
  shard-degree divisibility/conservation, escaped partial sums, dtype
  propagation, dead dataflow, SP-decomposability, machine-view legality.
- `rule_audit`: substitution soundness auditor — symbolically applies every
  registered rule to a host synthesized from its own pattern and checks the
  rewritten interface is shape/degree-equivalent.
- `source_lints`: AST lints over the package itself — host syncs inside
  jitted bodies, id()-keyed persistent caches, unordered-set iteration.

`tools/ffcheck.py` is the CLI driver; `FF_TPU_VERIFY=1` additionally
verifies every substitution candidate inside `apply_substitution`, and
`FFModel.compile` always verifies the searched winner (results land in
`search_provenance["verify"]`).
"""

from flexflow_tpu.analysis.diagnostics import (
    Diagnostic,
    Severity,
    errors_of,
    format_diagnostic,
    has_errors,
)
from flexflow_tpu.analysis.pcg_verify import (
    PCG_RULE_CATALOG,
    verify_machine_mapping,
    verify_pcg,
    verify_pcg_structure,
)
from flexflow_tpu.analysis.rule_audit import (
    RULE_AUDIT_CATALOG,
    audit_rules,
    audit_substitution,
    registered_rules_for_grid,
)
from flexflow_tpu.analysis.memory_accounting import (
    ServingMemorySpec,
    estimate_memory,
    kv_cache_piece_bytes,
    leaf_step_memory_bytes,
)
from flexflow_tpu.analysis.memory_analysis import (
    MEMORY_RULE_IDS,
    MemoryAnalysis,
    ServingVerdict,
    analyze_memory,
    format_memory_table,
    memory_summary_json,
    serving_verdict,
    verify_memory,
)
from flexflow_tpu.analysis.comm_analysis import (
    COMM_RULE_IDS,
    CommAnalysis,
    comm_summary_json,
    cross_check_comm,
    extract_collectives,
    format_comm_table,
    verify_comm,
)
from flexflow_tpu.analysis.exec_contract import (
    EXEC_RULE_IDS,
    ExecContractAnalysis,
    analyze_step_program,
    compare_contract_records,
    exec_summary_json,
    extract_determinism_findings,
    format_exec_table,
    verify_exec,
)
from flexflow_tpu.analysis.source_lints import (
    LINT_CATALOG,
    lint_package,
    lint_source,
)
from flexflow_tpu.analysis.transition_analysis import (
    TRANSITION_RULE_IDS,
    TransitionAnalysis,
    TransitionError,
    analyze_transition,
    format_transition_table,
    transition_summary_json,
    transition_verdict_record,
    verify_transition,
)

__all__ = [
    "TRANSITION_RULE_IDS",
    "TransitionAnalysis",
    "TransitionError",
    "analyze_transition",
    "format_transition_table",
    "transition_summary_json",
    "transition_verdict_record",
    "verify_transition",
    "EXEC_RULE_IDS",
    "ExecContractAnalysis",
    "analyze_step_program",
    "compare_contract_records",
    "exec_summary_json",
    "extract_determinism_findings",
    "format_exec_table",
    "verify_exec",
    "COMM_RULE_IDS",
    "CommAnalysis",
    "comm_summary_json",
    "cross_check_comm",
    "extract_collectives",
    "format_comm_table",
    "verify_comm",
    "MEMORY_RULE_IDS",
    "MemoryAnalysis",
    "ServingMemorySpec",
    "ServingVerdict",
    "analyze_memory",
    "estimate_memory",
    "format_memory_table",
    "kv_cache_piece_bytes",
    "leaf_step_memory_bytes",
    "memory_summary_json",
    "serving_verdict",
    "verify_memory",
    "Diagnostic",
    "Severity",
    "errors_of",
    "format_diagnostic",
    "has_errors",
    "PCG_RULE_CATALOG",
    "RULE_AUDIT_CATALOG",
    "LINT_CATALOG",
    "verify_pcg",
    "verify_pcg_structure",
    "verify_machine_mapping",
    "audit_rules",
    "audit_substitution",
    "registered_rules_for_grid",
    "lint_package",
    "lint_source",
    "assert_verifier_clean",
]


def assert_verifier_clean(pcg, machine_spec=None, mapping=None) -> None:
    """Raise AssertionError with formatted diagnostics if `pcg` has any
    error-severity verifier finding (tests' one-line gate for searched
    winners and seed templates)."""
    diags = verify_pcg(pcg, machine_spec=machine_spec, mapping=mapping)
    errs = errors_of(diags)
    assert not errs, "verifier found errors:\n" + "\n".join(
        format_diagnostic(d) for d in errs
    )

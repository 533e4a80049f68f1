"""Native machine-mapping DP: flatten the problem into arrays, solve in C++.

The pure-Python DP in get_optimal_machine_mapping.py is the semantic
reference and the FF_TPU_NO_NATIVE=1 fallback; this module lowers one
root-level DP call into contiguous arrays — the problem-tree structure,
per-(leaf, resources) allowed-view id lists, per-leaf (view -> cost)
tables, the get_machine_resource_splits enumeration, and per-series-split
movement-cost tables — and runs split enumeration + series/parallel
combining + the memo table in C++ (native/src/ffcore.cc: ffc_mm_dp). The
winning per-leaf views come back as a flat array and are reconstructed
into a MachineMappingResult. Exact cost parity with the Python DP is
pinned by tests/test_machine_mapping.py.

Everything that calls back into Python (allowed-view enumeration,
estimate_op_cost, estimate_movement_cost) happens HERE, at table-build
time, and is cached on the shared MachineMappingCache keyed by hash-consed
problem-tree nodes (problem_tree.intern_problem_tree_node) — successive
search candidates share most of their subtrees, so after the first few
evaluations a candidate's tables assemble almost entirely from cache hits
and the C++ call is the only real work.
"""

from __future__ import annotations

import itertools
import os
from typing import Dict, List, Tuple

from flexflow_tpu.compiler.machine_mapping.problem_tree import (
    MMProblemTreeParallelSplit,
    MMProblemTreeSeriesSplit,
    UnmappedOpCostEstimateKey,
    map_unmapped_op_cost_estimate_key,
    mm_problem_tree_get_subtree_at_path,
    mm_problem_tree_num_leaves,
)
from flexflow_tpu.compiler.machine_mapping.result import (
    INFEASIBLE,
    FeasibleMachineMappingResult,
)
from flexflow_tpu.observability.search_phases import search_phase

# sentinel: the caller must run the Python DP (INFEASIBLE is a legal
# native answer and is represented as None, so None cannot signal a miss)
NATIVE_MISS = object()

_MAX_SPLIT_TABLE = 1 << 16    # movement-table entries per series split
_MAX_TOTAL_TABLE = 1 << 21    # summed across one problem tree


class _Unsupported(Exception):
    """The problem shape exceeds what the native lowering handles."""


def _reachable_resources(resources, allow_splits):
    """The closure of `resources` under get_machine_resource_splits —
    every resource spec any subproblem can be solved under."""
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        get_machine_resource_splits,
    )

    order = [resources]
    seen = {resources}
    if allow_splits:
        i = 0
        while i < len(order):
            for pair in get_machine_resource_splits(order[i]):
                for r in pair:
                    if r not in seen:
                        seen.add(r)
                        order.append(r)
            i += 1
    return order


def _rel_leaf_index(tree, path) -> int:
    """Leaf ordinal of `path` within `tree` (leaves numbered left to
    right), so cached split tables — which are tree-relative — can be
    rebased onto any candidate's absolute ordinals."""
    idx = 0
    cur = tree
    for step in path:
        if step == "R":
            idx += mm_problem_tree_num_leaves(cur.left)
            cur = cur.right
        else:
            cur = cur.left
    if not isinstance(cur, UnmappedOpCostEstimateKey):
        raise _Unsupported("boundary path does not name a leaf")
    return idx


class _SplitTable:
    """Cached movement-cost table of one series split: boundary entries
    (side, tree-relative leaf index, path, candidate view ids — src entries
    first) plus the flat cost array, row-major with the last entry varying
    fastest (matching ffc_mm_dp's index computation). `ov` is the aligned
    overlapped-entry array (machine_mapping/overlap.py ramps); None when
    the split is not overlap-eligible — lowered to -1 sentinels, which
    ffc_mm_dp reads as "serial pricing only"."""

    __slots__ = ("entries", "costs", "ov")

    def __init__(self, entries, costs, ov=None):
        self.entries = entries
        self.costs = costs
        self.ov = ov


def _build_split_table(cache, context, split, res_order, allowed_ids):
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        _concretize_movement,
    )
    from flexflow_tpu.compiler.machine_mapping.overlap import (
        eligible_comm_ms,
        get_split_overlap,
        overlapped_exposure_ms,
    )

    movement = split.tensor_set_movement
    entries: List[Tuple[str, int, tuple, Tuple[int, ...]]] = []
    for side, child, paths in (
        ("L", split.left, sorted(movement.src_layers())),
        ("R", split.right, sorted(movement.dst_layers())),
    ):
        for path in paths:
            leaf = mm_problem_tree_get_subtree_at_path(child, path)
            if not isinstance(leaf, UnmappedOpCostEstimateKey):
                raise _Unsupported("boundary path is not a leaf")
            union: List[int] = []
            seen = set()
            for r in res_order:
                for vid in allowed_ids(leaf, r):
                    if vid not in seen:
                        seen.add(vid)
                        union.append(vid)
            entries.append(
                (side, _rel_leaf_index(child, path), path, tuple(union))
            )

    size = 1
    for e in entries:
        size *= len(e[3])
        if size > _MAX_SPLIT_TABLE:
            raise _Unsupported("movement table too large")

    # itertools.product of zero lists yields one empty combo, matching the
    # Python DP's single empty boundary assignment; an entry with an empty
    # candidate list yields no combos (the DP is infeasible through this
    # split before the table is ever read)
    ov_info = get_split_overlap(cache, context, split)
    costs: List[float] = []
    ov: List[float] = [] if ov_info is not None else None
    cand_views = [[cache.views[vid] for vid in e[3]] for e in entries]
    for combo in itertools.product(*cand_views):
        pre: Dict = {}
        post: Dict = {}
        for e, view in zip(entries, combo):
            (pre if e[0] == "L" else post)[e[2]] = view
        tsm = _concretize_movement(movement, pre, post)
        cost = cache.movement_costs.get(tsm)
        if cost is None:
            cost = context.cost_estimator.estimate_movement_cost(tsm)
            cache.movement_costs[tsm] = cost
        costs.append(float(cost))
        if ov is not None:
            ov.append(
                float(
                    overlapped_exposure_ms(
                        context.cost_estimator, ov_info, float(cost),
                        eligible_comm_ms(
                            context.cost_estimator, ov_info, pre, post
                        ),
                    )
                )
            )
    return _SplitTable(entries, costs, ov)


def try_native_dp(cache, context, tree, resources):
    """Solve the root-level DP natively; returns a MachineMappingResult
    (possibly INFEASIBLE, i.e. None) or NATIVE_MISS when the native path is
    unavailable/ineligible and the Python DP must run instead."""
    # FF_TPU_NO_NATIVE is read per call (tests toggle it in-process)
    if os.environ.get("FF_TPU_NO_NATIVE"):
        return NATIVE_MISS
    from flexflow_tpu import native_lib

    lib = native_lib.get_lib()
    if lib is None or not hasattr(lib, "ffc_mm_dp"):
        return NATIVE_MISS

    root_key = (tree, resources, frozenset())
    if root_key in cache._table:
        # deliberately NOT counted in native_served: the cached entry may
        # have been computed by the Python fallback under the same key
        cache.hits += 1
        return cache._table[root_key]

    try:
        out = _solve(cache, context, tree, resources)
    except _Unsupported:
        return NATIVE_MISS
    if out is NATIVE_MISS:
        return NATIVE_MISS
    cache.misses += 1
    cache.native_served += 1
    cache._table[root_key] = out
    return out


def _solve(cache, context, tree, resources):
    from flexflow_tpu import native_lib
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        get_machine_resource_splits,
    )

    res_order = _reachable_resources(resources, context.allow_resource_splits)
    res_id = {r: i for i, r in enumerate(res_order)}
    n_res = len(res_order)

    def view_id(v):
        vid = cache.view_ids.get(v)
        if vid is None:
            vid = len(cache.views)
            cache.view_ids[v] = vid
            cache.views.append(v)
        return vid

    def allowed_ids(leaf, r):
        ck = (leaf, r)
        ids = cache.allowed_ids.get(ck)
        if ids is None:
            ids = tuple(
                view_id(v) for v in context.allowed_machine_views(leaf, r)
            )
            cache.allowed_ids[ck] = ids
        return ids

    # -- tree structure -----------------------------------------------------
    kind: List[int] = []
    left: List[int] = []
    right: List[int] = []
    leaf_ord: List[int] = []
    leaf_lo: List[int] = []
    leaf_hi: List[int] = []
    leaf_keys: List = []          # ordinal -> leaf key object
    series_at: List[Tuple[int, object]] = []  # (node idx, split object)

    def walk(t) -> int:
        if isinstance(t, UnmappedOpCostEstimateKey):
            o = len(leaf_keys)
            leaf_keys.append(t)
            kind.append(0)
            left.append(-1)
            right.append(-1)
            leaf_ord.append(o)
            leaf_lo.append(o)
            leaf_hi.append(o + 1)
            return len(kind) - 1
        li = walk(t.left)
        ri = walk(t.right)
        kind.append(1 if isinstance(t, MMProblemTreeSeriesSplit) else 2)
        left.append(li)
        right.append(ri)
        leaf_ord.append(-1)
        leaf_lo.append(leaf_lo[li])
        leaf_hi.append(leaf_hi[ri])
        idx = len(kind) - 1
        if (
            isinstance(t, MMProblemTreeSeriesSplit)
            and t.tensor_set_movement.movements
        ):
            series_at.append((idx, t))
        return idx

    root = walk(tree)
    n_leaves = len(leaf_keys)

    # -- per-key view/cost tables -------------------------------------------
    key_ids: Dict = {}
    key_list: List = []
    for k in leaf_keys:
        if k not in key_ids:
            key_ids[k] = len(key_list)
            key_list.append(k)
    leaf_key_arr = [key_ids[k] for k in leaf_keys]

    # per-key piece step-residency for the memory pruner (view-independent,
    # so one double per key; analysis/memory_accounting). Capacity < 0
    # disables the check inside ffc_mm_dp — the arrays still ship so the
    # ABI stays one-shape.
    mem_capacity = -1.0
    km_bytes: List[float] = [0.0] * len(key_list)
    if context.memory_budget_bytes and context.memory_budget_bytes > 0:
        from flexflow_tpu.analysis.memory_accounting import (
            leaf_step_memory_bytes,
        )

        mem_capacity = float(context.memory_budget_bytes)
        for k, kid in key_ids.items():
            try:
                km_bytes[kid] = float(
                    leaf_step_memory_bytes(
                        k,
                        context.optimizer_state_slots,
                        serving=context.serving,
                    )
                )
            except (AssertionError, IndexError, KeyError, ValueError, TypeError):
                km_bytes[kid] = 0.0  # malformed shapes: never pruned (parity
                # with leaf_memory_infeasible's False on exception)

    # per-key pipeline-stage factor (ABI v9): the native solver multiplies
    # every leaf read by it — the same (M+S-1)/(M*S) double the Python
    # DP's _optimal_leaf applies, so parity stays exact
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        leaf_pipeline_factor,
    )

    k_pipe: List[float] = [leaf_pipeline_factor(k) for k in key_list]

    kr_ptr = [0]
    kr_view: List[int] = []
    kc_ptr = [0]
    kc_view: List[int] = []
    kc_cost: List[float] = []
    with search_phase("leaf_cost"):
        for k in key_list:
            union: List[int] = []
            seen = set()
            per_res = []
            for r in res_order:
                ids = allowed_ids(k, r)
                per_res.append(ids)
                for vid in ids:
                    if vid not in seen:
                        seen.add(vid)
                        union.append(vid)
            costs = cache.leaf_costs.get(k)
            if costs is None:
                costs = cache.leaf_costs[k] = {}
            missing = [vid for vid in union if vid not in costs]
            if missing:
                cache.misses += 1
                pruned = (
                    mem_capacity >= 0.0
                    and km_bytes[key_ids[k]] > mem_capacity
                )
                for vid in missing:
                    # a leaf the memory pruner rejects is never read by the
                    # solver — do not pay to measure it (inf placeholder
                    # keeps the table shape; parity is unaffected because
                    # the Python DP returns INFEASIBLE before pricing too)
                    costs[vid] = (
                        float("inf")
                        if pruned
                        else context.cost_estimator.estimate_op_cost(
                            map_unmapped_op_cost_estimate_key(
                                k, cache.views[vid]
                            )
                        )
                    )
            else:
                cache.hits += 1
            for ids in per_res:
                kr_view.extend(ids)
                kr_ptr.append(len(kr_view))
            for vid in union:
                kc_view.append(vid)
                kc_cost.append(float(costs[vid]))
            kc_ptr.append(len(kc_view))

    # -- resource splits ----------------------------------------------------
    rs_ptr = [0]
    rs_a: List[int] = []
    rs_b: List[int] = []
    for r in res_order:
        if context.allow_resource_splits:
            for a, b in get_machine_resource_splits(r):
                rs_a.append(res_id[a])
                rs_b.append(res_id[b])
        rs_ptr.append(len(rs_a))

    # -- series boundary + movement tables ----------------------------------
    n_nodes = len(kind)
    sb_ptr = [0] * (n_nodes + 1)
    sb_leaf: List[int] = []
    sb_is_dst: List[int] = []
    sb_cand_ptr = [0]
    sb_cand_view: List[int] = []
    mt_off = [-1] * n_nodes
    mt_cost: List[float] = []
    mt_ov: List[float] = []  # aligned with mt_cost; -1 = no overlapped entry

    tables: Dict[int, _SplitTable] = {}
    total_entries = 0
    for idx, split in series_at:
        ck = (split, resources, context.allow_resource_splits)
        tab = cache.split_tables.get(ck)
        if tab is None:
            cache.misses += 1
            tab = _build_split_table(cache, context, split, res_order, allowed_ids)
            cache.split_tables[ck] = tab
        else:
            cache.hits += 1
        total_entries += len(tab.costs)
        if total_entries > _MAX_TOTAL_TABLE:
            raise _Unsupported("movement tables too large in aggregate")
        tables[idx] = tab

    for idx in range(n_nodes):
        tab = tables.get(idx)
        if tab is not None:
            for side, rel, _path, cand in tab.entries:
                child = left[idx] if side == "L" else right[idx]
                sb_leaf.append(leaf_lo[child] + rel)
                sb_is_dst.append(0 if side == "L" else 1)
                sb_cand_view.extend(cand)
                sb_cand_ptr.append(len(sb_cand_view))
            mt_off[idx] = len(mt_cost)
            mt_cost.extend(tab.costs)
            mt_ov.extend(
                tab.ov if tab.ov is not None else [-1.0] * len(tab.costs)
            )
        sb_ptr[idx + 1] = len(sb_leaf)

    # -- multi-slice legality masks (ABI v10) -------------------------------
    # sized at call time: every view id the tables of THIS call reference
    # is already interned in cache.views
    slice_aware = bool(getattr(context, "slice_aware", False))
    if slice_aware:
        from flexflow_tpu.compiler.machine_mapping.slice_axes import (
            leaf_tensor_axis_mask,
            view_inter_axis_mask,
        )

        k_tmask = [leaf_tensor_axis_mask(k) for k in key_list]
        v_imask = [view_inter_axis_mask(v) for v in cache.views]
    else:
        k_tmask = [0] * len(key_list)
        v_imask = [0] * len(cache.views)

    out = native_lib.mm_dp(
        kind, left, right, leaf_ord, leaf_lo, leaf_hi, root, leaf_key_arr,
        len(key_list), n_res, kr_ptr, kr_view, kc_ptr, kc_view, kc_cost,
        rs_ptr, rs_a, rs_b, sb_ptr, sb_leaf, sb_is_dst, sb_cand_ptr,
        sb_cand_view, mt_off, mt_cost, mt_ov, km_bytes, mem_capacity,
        k_pipe,
        k_tmask, v_imask, slice_aware,
        context.overlap_fraction,
        context.allow_resource_splits, res_id[resources],
    )
    if out is None:
        return NATIVE_MISS
    feasible, runtime, views = out
    if not feasible:
        return INFEASIBLE

    # rebuild the nested MappingTree the Python combiners would have built
    pos = 0

    def mapping(t):
        nonlocal pos
        if isinstance(t, UnmappedOpCostEstimateKey):
            v = cache.views[views[pos]]
            pos += 1
            return (None, v)
        return (mapping(t.left), mapping(t.right))

    mapping_tree = mapping(tree)
    assert pos == n_leaves == mm_problem_tree_num_leaves(tree)
    return FeasibleMachineMappingResult(runtime, mapping_tree)

"""ctypes loader for the native C++ core (native/src/ffcore.cc).

The reference implements its graph machinery and pattern matcher natively in
C++17 (lib/utils, lib/substitutions); this build does the same, exposed over a
flat C ABI since pybind11 is not available. The library is compiled lazily
with g++ on first use and cached under native/build/ in a file named by the
hash of its sources, so a copied or checked-out tree never loads a library
built from other sources. Every algorithm has a pure-Python fallback so the
framework works without a toolchain (FF_TPU_NO_NATIVE=1 disables the native
path entirely); a build that fails says why once on stderr and in
`load_error()`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import List, Optional, Sequence, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "src", "ffcore.cc")
_HDR_DIR = os.path.join(_REPO_ROOT, "native", "include")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_ABI_VERSION = 10

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None


def _so_path() -> str:
    """The library path for the sources as they are now: staleness is a
    different file name, never an mtime comparison (a copy or a checkout
    resets mtimes)."""
    h = hashlib.sha256()
    for path in (_SRC, os.path.join(_HDR_DIR, "ffcore.h")):
        with open(path, "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"_ffcore_{h.hexdigest()[:16]}.so")


def _build(so: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build beside the target and rename: a concurrent process never
    # dlopens a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-I", _HDR_DIR, "-o", tmp, _SRC,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _configure(lib: ctypes.CDLL) -> None:
    i32p = ctypes.POINTER(ctypes.c_int32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ffc_abi_version.restype = ctypes.c_int
    lib.ffc_topo_sort.argtypes = [ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
    lib.ffc_reachability.argtypes = [ctypes.c_int32, ctypes.c_int32, i32p, i32p, u64p]
    lib.ffc_transitive_reduction.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i32p, i32p]
    lib.ffc_dominators.argtypes = [ctypes.c_int32, ctypes.c_int32, i32p, i32p, u64p]
    lib.ffc_weakly_connected_components.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
    lib.ffc_ttsp_decompose.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    lib.ffc_pattern_match.argtypes = [
        ctypes.c_int32, i32p, i32p, i32p,
        ctypes.c_int32, i32p, i32p, i32p, i32p,
        ctypes.c_int32, ctypes.c_int32, u8p, u8p,
        ctypes.c_int32, i32p, i32p]
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    lib.ffc_mm_dp.argtypes = [
        ctypes.c_int32, i32p, i32p, i32p, i32p, i32p, i32p,  # tree
        ctypes.c_int32, ctypes.c_int32, i32p,                # root, n_leaves, leaf_key
        ctypes.c_int32, ctypes.c_int32,                      # n_keys, n_res
        i32p, i32p, i32p, i32p, f64p,                        # kr/kc tables
        i32p, i32p, i32p,                                    # resource splits
        i32p, i32p, u8p, i32p, i32p,                         # series boundaries
        i64p, f64p, f64p,                                    # movement tables (+ov)
        f64p, ctypes.c_double,                               # leaf memory + capacity
        f64p,                                                # pipeline factors (v9)
        i32p, i32p, ctypes.c_int32,                          # slice masks + flag (v10)
        ctypes.c_double, ctypes.c_int32, ctypes.c_int32,     # overlap/splits/root res
        i32p, f64p, i32p]                                    # outputs
    for fn in (
        lib.ffc_topo_sort, lib.ffc_reachability, lib.ffc_transitive_reduction,
        lib.ffc_dominators, lib.ffc_weakly_connected_components,
        lib.ffc_pattern_match, lib.ffc_ttsp_decompose, lib.ffc_mm_dp,
    ):
        fn.restype = ctypes.c_int


def get_lib() -> Optional[ctypes.CDLL]:
    """Returns the loaded native library, building it if necessary.

    Returns None if disabled (FF_TPU_NO_NATIVE) or if the build or load
    failed; the failure is remembered, reported once on stderr, and kept
    for `load_error()`.
    """
    global _lib, _lib_error
    if _lib is not None:
        return _lib
    if _lib_error is not None or os.environ.get("FF_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
            _configure(lib)
            if lib.ffc_abi_version() != _ABI_VERSION:
                raise RuntimeError(
                    f"ffcore.cc reports ABI {lib.ffc_abi_version()}, "
                    f"native_lib.py expects {_ABI_VERSION}"
                )
            _lib = lib
        except subprocess.CalledProcessError as e:
            _lib_error = f"g++ exited {e.returncode}: {e.stderr[-2000:]}"
        except (OSError, RuntimeError, AttributeError) as e:
            # no g++ on PATH / unreadable sources / dlopen failure / ABI
            # mismatch / a symbol _configure expects is missing
            _lib_error = f"{type(e).__name__}: {e}"
        if _lib_error is not None:
            print(
                "[flexflow_tpu] native core unavailable, using the Python "
                f"fallbacks: {_lib_error}",
                file=sys.stderr,
            )
    return _lib


def load_error() -> Optional[str]:
    """Why `get_lib()` returned None after a failed build or load (None
    when the library loaded, was never asked for, or is disabled)."""
    return _lib_error


def native_available() -> bool:
    return get_lib() is not None


# -- convenience wrappers over the flat C ABI --------------------------------


def _i32(xs: Sequence[int]) -> "ctypes.Array":
    return (ctypes.c_int32 * len(xs))(*xs)


def topo_sort(n: int, edges: Sequence[Tuple[int, int]]) -> Optional[List[int]]:
    """Returns topological order of dense nodes 0..n-1, or None on cycle."""
    lib = get_lib()
    assert lib is not None
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    out = (ctypes.c_int32 * n)()
    rc = lib.ffc_topo_sort(n, len(edges), src, dst, out)
    if rc != 0:
        return None
    return list(out)


def _bitset_rows(buf, n: int) -> List[List[int]]:
    words = (n + 63) // 64
    rows: List[List[int]] = []
    for i in range(n):
        row = []
        for w in range(words):
            bits = buf[i * words + w]
            base = w * 64
            while bits:
                low = bits & (-bits)
                row.append(base + low.bit_length() - 1)
                bits ^= low
        rows.append(row)
    return rows


def reachability(n: int, edges: Sequence[Tuple[int, int]]) -> Optional[List[List[int]]]:
    lib = get_lib()
    assert lib is not None
    words = (n + 63) // 64
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    out = (ctypes.c_uint64 * (n * words))()
    rc = lib.ffc_reachability(n, len(edges), src, dst, out)
    if rc != 0:
        return None
    return _bitset_rows(out, n)


def transitive_reduction(
    n: int, edges: Sequence[Tuple[int, int]]
) -> Optional[List[Tuple[int, int]]]:
    lib = get_lib()
    assert lib is not None
    m = len(edges)
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    osrc = (ctypes.c_int32 * max(m, 1))()
    odst = (ctypes.c_int32 * max(m, 1))()
    om = ctypes.c_int32(0)
    rc = lib.ffc_transitive_reduction(
        n, m, src, dst, osrc, odst, ctypes.byref(om))
    if rc != 0:
        return None
    return [(osrc[i], odst[i]) for i in range(om.value)]


def dominators(n: int, edges: Sequence[Tuple[int, int]]) -> Optional[List[List[int]]]:
    lib = get_lib()
    assert lib is not None
    words = (n + 63) // 64
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    out = (ctypes.c_uint64 * (n * words))()
    rc = lib.ffc_dominators(n, len(edges), src, dst, out)
    if rc != 0:
        return None
    return _bitset_rows(out, n)


def weakly_connected_components(
    n: int, edges: Sequence[Tuple[int, int]]
) -> List[int]:
    lib = get_lib()
    assert lib is not None
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    out = (ctypes.c_int32 * n)()
    lib.ffc_weakly_connected_components(n, len(edges), src, dst, out)
    return list(out)


def pattern_match(
    p_slots: Sequence[Sequence[Tuple[int, int]]],
    h_slots: Sequence[Sequence[Tuple[int, int, int]]],
    n_gi: int,
    n_values: int,
    compat: Sequence[Sequence[bool]],
    gi_compat: Sequence[Sequence[bool]],
    max_matches: int = 256,
) -> Optional[List[Tuple[List[int], List[int]]]]:
    """Enumerate injective pattern->host node maps.

    p_slots[p] = list of (producer, idx): producer >= 0 is a pattern node
    output; producer == -1 means pattern graph input `idx`.
    h_slots[h] = list of (producer, idx, value_id) for the host node's inputs
    (producer == -1 for host external/graph-input values).
    Starts with a small output buffer and grows on truncation (rc -2);
    returns None only past the hard cap (caller falls back to Python).
    """
    lib = get_lib()
    assert lib is not None
    np_ = len(p_slots)
    ng = len(h_slots)

    p_ptr, p_src, p_idx = [0], [], []
    for slots in p_slots:
        for s, i in slots:
            p_src.append(s)
            p_idx.append(i)
        p_ptr.append(len(p_src))
    h_ptr, h_src, h_idx, h_val = [0], [], [], []
    for slots in h_slots:
        for s, i, v in slots:
            h_src.append(s)
            h_idx.append(i)
            h_val.append(v)
        h_ptr.append(len(h_src))

    compat_flat = (ctypes.c_uint8 * (np_ * ng))(
        *[1 if compat[p][h] else 0 for p in range(np_) for h in range(ng)])
    gi_flat = (ctypes.c_uint8 * max(n_gi * n_values, 1))(
        *([1 if gi_compat[g][v] else 0
           for g in range(n_gi) for v in range(n_values)] or [0]))

    row_len = np_ + n_gi
    pp_ptr, pp_src, pp_idx = _i32(p_ptr), _i32(p_src), _i32(p_idx)
    hh_ptr, hh_src, hh_idx, hh_val = (
        _i32(h_ptr), _i32(h_src), _i32(h_idx), _i32(h_val))
    hard_cap = 1 << 20
    cap = max_matches
    while True:
        out = (ctypes.c_int32 * (cap * max(row_len, 1)))()
        cnt = ctypes.c_int32(0)
        rc = lib.ffc_pattern_match(
            np_, pp_ptr, pp_src, pp_idx,
            ng, hh_ptr, hh_src, hh_idx, hh_val,
            n_gi, n_values, compat_flat, gi_flat,
            cap, out, ctypes.byref(cnt))
        if rc != -2:
            break
        if cap >= hard_cap:
            return None  # pathological match count; caller falls back
        cap *= 8
    results = []
    for r in range(cnt.value):
        row = out[r * row_len:(r + 1) * row_len]
        results.append((list(row[:np_]), list(row[np_:])))
    return results


def mm_dp(
    kind: Sequence[int], left: Sequence[int], right: Sequence[int],
    leaf_ord: Sequence[int], leaf_lo: Sequence[int], leaf_hi: Sequence[int],
    root: int, leaf_key: Sequence[int], n_keys: int, n_res: int,
    kr_ptr: Sequence[int], kr_view: Sequence[int],
    kc_ptr: Sequence[int], kc_view: Sequence[int], kc_cost: Sequence[float],
    rs_ptr: Sequence[int], rs_a: Sequence[int], rs_b: Sequence[int],
    sb_ptr: Sequence[int], sb_leaf: Sequence[int], sb_is_dst: Sequence[int],
    sb_cand_ptr: Sequence[int], sb_cand_view: Sequence[int],
    mt_off: Sequence[int], mt_cost: Sequence[float],
    mt_ov: Sequence[float],
    km_bytes: Sequence[float], mem_capacity: float,
    k_pipe: Sequence[float],
    k_tmask: Sequence[int], v_imask: Sequence[int], slice_aware: bool,
    overlap: float, allow_splits: bool, root_res: int,
) -> Optional[Tuple[bool, float, List[int]]]:
    """Run the machine-mapping DP natively (ffc_mm_dp). Returns
    (feasible, runtime, view id per leaf ordinal), or None on a malformed
    problem (caller falls back to the Python DP). km_bytes/mem_capacity
    drive the per-leaf memory pruner (capacity < 0 = off); k_pipe carries
    the per-key pipeline-stage 1F1B factor (ABI v9, 1.0 off-region);
    k_tmask/v_imask/slice_aware carry the multi-slice legality bitmasks
    (ABI v10 — slice-illegal leaf views are skipped, never inf-priced).
    See compiler/machine_mapping/native_dp.py for the array
    construction."""
    lib = get_lib()
    assert lib is not None
    n_nodes = len(kind)
    n_leaves = len(leaf_key)

    def _f64(xs):
        return (ctypes.c_double * max(len(xs), 1))(*xs)

    def _i64(xs):
        return (ctypes.c_int64 * max(len(xs), 1))(*xs)

    def _u8(xs):
        return (ctypes.c_uint8 * max(len(xs), 1))(*xs)

    def _i32nz(xs):
        return (ctypes.c_int32 * max(len(xs), 1))(*xs)

    out_feasible = ctypes.c_int32(0)
    out_runtime = ctypes.c_double(0.0)
    out_views = (ctypes.c_int32 * max(n_leaves, 1))()
    rc = lib.ffc_mm_dp(
        n_nodes, _i32nz(kind), _i32nz(left), _i32nz(right), _i32nz(leaf_ord),
        _i32nz(leaf_lo), _i32nz(leaf_hi), root, n_leaves, _i32nz(leaf_key),
        n_keys, n_res, _i32nz(kr_ptr), _i32nz(kr_view), _i32nz(kc_ptr),
        _i32nz(kc_view), _f64(kc_cost), _i32nz(rs_ptr), _i32nz(rs_a),
        _i32nz(rs_b), _i32nz(sb_ptr), _i32nz(sb_leaf), _u8(sb_is_dst),
        _i32nz(sb_cand_ptr), _i32nz(sb_cand_view), _i64(mt_off),
        _f64(mt_cost), _f64(mt_ov), _f64(km_bytes), mem_capacity,
        _f64(k_pipe),
        _i32nz(k_tmask), _i32nz(v_imask), 1 if slice_aware else 0,
        overlap, 1 if allow_splits else 0,
        root_res,
        ctypes.byref(out_feasible), ctypes.byref(out_runtime), out_views,
    )
    if rc != 0:
        return None
    return (
        bool(out_feasible.value),
        out_runtime.value,
        list(out_views[:n_leaves]),
    )


def ttsp_decompose(
    n: int, edges: Sequence[Tuple[int, int]]
) -> Optional[List[int]]:
    """TTSP decomposition over dense nodes 0..n-1. Returns the preorder
    token stream (0,id | 1,k | 2,k) or None if the DAG is not
    TTSP-reducible (caller falls back to module contraction / Python)."""
    lib = get_lib()
    assert lib is not None
    src = _i32([e[0] for e in edges])
    dst = _i32([e[1] for e in edges])
    # token stream is bounded by 4n-2 (each node emitted once as a leaf =
    # 2n tokens; every split has >= 2 children so internal nodes <= n-1)
    cap = 8 * max(n, 1) + 64
    out = (ctypes.c_int32 * cap)()
    out_len = ctypes.c_int32(0)
    rc = lib.ffc_ttsp_decompose(
        n, len(edges), src, dst, out, cap, ctypes.byref(out_len)
    )
    if rc != 0:
        return None
    return list(out[: out_len.value])

"""AST-level tracing-safety and determinism lints over flexflow_tpu itself.

LINT001 host-sync-in-jit    `.item()`, `np.asarray(...)`, or
                            `jax.device_get(...)` inside a jitted body — a
                            function named `_step`, a function passed to
                            `jax.jit`/`jit`/`pjit` (by name or decorator),
                            or a `*_kernel` function. Host syncs inside a
                            trace either fail at trace time or silently
                            force a device round-trip per step.
LINT002 id-keyed-cache      `id(...)` used as the key of a PERSISTENT store
                            (a `self.`/object attribute or a module-level
                            MODULE_CONSTANT name): ids are reused after GC,
                            so persistent id-keyed caches alias freed
                            objects and break determinism. Function-local
                            id-keyed dicts (keys outlive the dict) are
                            allowed.
LINT003 unordered-iteration a `for` statement or list comprehension
                            iterating a set literal / set comprehension /
                            `set(...)` / `frozenset(...)` directly: the
                            order feeds whatever the loop builds, so search
                            decisions become hash-seed dependent. Wrap in
                            `sorted(...)`.
LINT004 host-read-in-shard-map
                            `.item()`, `np.asarray(...)`, or
                            `jax.device_get(...)` inside a function passed
                            to `shard_map` / `shard_map_compat`. A shard_map
                            body runs per-device inside the partitioned
                            program; an unsynchronized host read there
                            either fails to trace or silently serializes
                            every device's ring step through the host —
                            exactly the overlap the collective-matmul
                            kernels exist to preserve.
LINT006 swallowed-exception   a bare `except:` handler, or an
                            `except Exception:` / `except BaseException:`
                            handler whose body only passes, inside
                            `flexflow_tpu/runtime/` or a `_fit_*`
                            training-loop driver. The supervision layer
                            (runtime/supervisor.py) only works if errors
                            REACH it: a swallow on the recovery path
                            converts a detectable fault into silent
                            corruption. Handlers that route the exception
                            somewhere (post to a FaultChannel, re-raise a
                            structured error, record and fall back) are
                            fine — only the discard is banned.
LINT005 host-transfer-in-fit-loop
                            `.item()`, `np.asarray(...)`, or
                            `jax.device_get(...)` lexically inside a
                            training-loop driver — a function named
                            `_fit_*`, the thread holding the step-dispatch
                            critical path. A blocking host transfer there
                            stalls async dispatch of the next donated step
                            every iteration. Nested function definitions
                            are exempt: background writer thread bodies
                            (the async checkpoint writer) are the
                            sanctioned home for
                            host transfers, as are named helpers outside
                            the drivers (each sync point then has a
                            reviewable name, e.g. `_read_losses_host`).

LINT007 unsupervised-thread   concurrency discipline for `flexflow_tpu/
                            runtime/` (the fault-domain supervision
                            package, PR-8 invariant), two checks on every
                            `threading.Thread` construction site:
                            (1) the thread's target method (or a Thread
                            subclass's `run`) must not assign shared
                            instance state (`self.attr = ...`) outside a
                            `with self.<lock>:` block guarding one of the
                            owning class's lock attributes
                            (`threading.Lock/RLock/Condition/Semaphore`)
                            — an unlocked cross-thread write is a data
                            race the chaos soak cannot reproduce
                            deterministically; nested defs are exempt
                            (they are their own linting context, like
                            LINT005). (2) the owning class (or, for a
                            bare function target, the target body) must
                            carry a fault ROUTE — a `FaultChannel`
                            reference (any `*channel*` name), a
                            `.post(...)` call, or one of the supervision
                            primitives (`on_hang`, `raise_pending`,
                            `_async_raise`) — so a thread that dies
                            surfaces at a window boundary instead of
                            silently leaving the run uncheckpointed
                            (the PR-8 silent-death class).

LINT008 undonated-step-jit  a `jax.jit`/`jit`/`pjit` call whose jitted
                            callable is a training/serving STEP (its
                            snake_case name carries a `step` token, e.g.
                            `_step`, `decode_step`) but
                            which passes neither `donate_argnums` nor
                            `donate_argnames`. Step programs rewrite the
                            largest trees in the system (params +
                            optimizer state) every call; undonated, XLA
                            keeps argument AND result buffers live, so
                            peak HBM doubles exactly where the MEM rules
                            bind. Read-only step-adjacent callables
                            (fwd/forward/eval/loss/stats tokens) are
                            exempt; lambdas carry no step identity and
                            are not judged.

LINT009 literal-rng-in-step   a literal `jax.random.PRNGKey(...)` /
                            `jax.random.key(...)` construction (constant
                            seed) inside a jitted step/kernel body or a
                            `lax.scan` body. The bitwise-resume contract
                            (PR 7, checked by DET002) carries ONE
                            threefry keystream through the fit loop —
                            RNG state restores exactly because every
                            consumed key derives from the carried key by
                            split/fold_in. A fresh literal key minted
                            mid-step restarts the stream at the same
                            constant every step (correlated dropout
                            masks) and is invisible to the carried-key
                            restore, so resume replays DIFFERENT
                            randomness than an uninterrupted run.
                            Literal keys outside traced step bodies
                            (initialization, example-argument builders,
                            host-side seeding) are fine.

LINT010 committed-state-reshard a direct `jax.device_put(x, y.sharding)` —
                            second positional argument or `device=` kwarg
                            reading another value's `.sharding` — outside
                            `runtime/recompile.py`. Resharding a COMMITTED
                            training-state leaf is the single most
                            bug-prone moment of the elastic runtime (the
                            PR-7 batch-growth failure class: a leaf
                            committed to the wrong mesh conflicts with
                            mesh-committed batches inside the next jitted
                            step), so the package routes every such
                            placement through recompile.py's
                            committed-aware `carry()`/`_place_like` path,
                            where the TRN001/TRN002 transition rules gate
                            it. A bare `device_put(x)` (uncommitted
                            default placement) and explicit device/mesh
                            targets are not judged — only the
                            template-sharding pull.

`lint_source` lints one source text (tests feed seeded snippets);
`lint_package` walks a package directory.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from flexflow_tpu.analysis.diagnostics import Diagnostic, error

LINT_CATALOG: Dict[str, str] = {
    "LINT001": "host-sync-in-jit: .item()/np.asarray/jax.device_get inside a jitted body",
    "LINT002": "id-keyed-cache: id(...) keys a persistent (attribute/module-level) store",
    "LINT003": "unordered-iteration: for/listcomp directly over a set",
    "LINT004": "host-read-in-shard-map: unsynchronized host read inside a shard_map body",
    "LINT005": "host-transfer-in-fit-loop: blocking host transfer on the training-loop critical path (a _fit_* driver)",
    "LINT006": "swallowed-exception: bare except / pass-only broad handler inside runtime/ or a fit-loop driver",
    "LINT007": "unsupervised-thread: runtime/ thread target mutating shared state without the class lock, or a Thread lacking a FaultChannel route",
    "LINT008": "undonated-step-jit: a jax.jit of a training/serving step callable without donate_argnums/donate_argnames",
    "LINT009": "literal-rng-in-step: a literal PRNGKey/key construction inside a jitted step/kernel or lax.scan body breaks the carried keystream bitwise resume depends on",
    "LINT010": "committed-state-reshard: direct jax.device_put(x, y.sharding) outside runtime/recompile.py's committed-aware carry()/_place_like path",
}

# training-loop drivers: functions holding the step-dispatch critical path
# (FFModel._fit_loop/_fit_epochs and kin)
_FIT_LOOP_PREFIX = "_fit_"

_SHARD_MAP_NAMES = ("shard_map", "shard_map_compat", "_shard_map")

_HOST_SYNC_ATTRS = {"item"}
_HOST_SYNC_CALLS = {
    ("np", "asarray"),
    ("numpy", "asarray"),
    ("jax", "device_get"),
}


def _dotted(node: ast.AST) -> Optional[tuple]:
    """('np', 'asarray') for np.asarray; ('jax', 'jit') for jax.jit; a
    1-tuple for bare names."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_jit_callable(node: ast.AST) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    return d[-1] in ("jit", "pjit")


def _jit_target_names(tree: ast.AST) -> Set[str]:
    """Names of functions passed to jax.jit/jit/pjit anywhere in the module
    (positionally or as self._x = jax.jit(self._step) attribute reads)."""
    targets: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_callable(node.func):
            for arg in node.args[:1]:
                d = _dotted(arg)
                if d is not None:
                    targets.add(d[-1])
    return targets


def _is_jitted_def(fn: ast.AST, jit_targets: Set[str]) -> bool:
    name = fn.name
    if name == "_step" or name.endswith("_kernel") or name in jit_targets:
        return True
    for dec in fn.decorator_list:
        if _is_jit_callable(dec):
            return True
        if (
            isinstance(dec, ast.Call)
            and _is_jit_callable(dec.func)
        ):
            return True
        # @partial(jax.jit, ...)
        if isinstance(dec, ast.Call) and dec.args and _is_jit_callable(
            dec.args[0]
        ):
            return True
    return False


def _shard_map_target_names(tree: ast.AST) -> Set[str]:
    """Names of functions passed (first positional arg) to shard_map /
    shard_map_compat anywhere in the module — including through local
    aliases like the executor's `_shard_map`."""
    targets: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None or d[-1] not in _SHARD_MAP_NAMES:
            continue
        for arg in node.args[:1]:
            dd = _dotted(arg)
            if dd is not None:
                targets.add(dd[-1])
    return targets


def _walk_excluding_nested_defs(fn: ast.AST):
    """The nodes of `fn`'s own body, NOT descending into nested function
    definitions (nested defs are background-thread bodies or helpers with
    their own linting context — LINT005 must judge only the code the
    driver itself executes)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _lint_jit_body(
    fn: ast.AST,
    path: str,
    diags: List[Diagnostic],
    rule: str = "LINT001",
    context: str = "jitted body",
    nodes=None,
) -> None:
    if rule == "LINT005":
        consequence = "stalls async dispatch of the next step"
        hint = (
            "move the transfer into a named helper outside the driver, or "
            "onto a background producer/writer thread"
        )
    else:
        consequence = "breaks tracing (host round-trip)"
        hint = "use jnp ops inside the trace"
    for node in nodes if nodes is not None else ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _HOST_SYNC_ATTRS:
            if not node.args and not node.keywords:  # x.item()
                diags.append(
                    error(
                        rule,
                        f".{func.attr}() inside {context} "
                        f"{fn.name!r} forces a host sync per step",
                        path=path,
                        line=node.lineno,
                        hint="keep device scalars on device; read them "
                        "back once outside the step"
                        if rule != "LINT005"
                        else hint,
                    )
                )
            continue
        d = _dotted(func)
        if d is not None and len(d) >= 2 and (d[-2], d[-1]) in _HOST_SYNC_CALLS:
            diags.append(
                error(
                    rule,
                    f"{'.'.join(d)}(...) inside {context} {fn.name!r} "
                    f"{consequence}",
                    path=path,
                    line=node.lineno,
                    hint=hint,
                )
            )


def _contains_id_call(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Name)
            and sub.func.id == "id"
        ):
            return True
    return False


def _is_persistent_store(node: ast.AST) -> bool:
    """self._cache / obj.attr / MODULE_CONSTANT — stores that outlive the
    local scope."""
    if isinstance(node, ast.Attribute):
        return True
    if isinstance(node, ast.Name):
        return node.id.isupper()
    return False


def _lint_id_keys(tree: ast.AST, path: str, diags: List[Diagnostic]) -> None:
    for node in ast.walk(tree):
        store = None
        key = None
        if isinstance(node, ast.Subscript):
            store, key = node.value, node.slice
        elif isinstance(node, ast.Compare) and any(
            isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
        ):
            store, key = node.comparators[0], node.left
        elif isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if node.func.attr in ("get", "setdefault", "add") and node.args:
                store, key = node.func.value, node.args[0]
        if (
            store is not None
            and key is not None
            and _is_persistent_store(store)
            and _contains_id_call(key)
        ):
            diags.append(
                error(
                    "LINT002",
                    "id(...) keys a persistent store: ids are recycled "
                    "after GC, so the cache can alias a dead object",
                    path=path,
                    line=node.lineno,
                    hint="key by a stable identity (index, name, or the "
                    "object itself if hashable)",
                )
            )


def _is_unordered_iterable(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("set", "frozenset")
    return False


def _lint_unordered_iteration(
    tree: ast.AST, path: str, diags: List[Diagnostic]
) -> None:
    def flag(node):
        diags.append(
            error(
                "LINT003",
                "iteration order over a set is hash-seed dependent; "
                "anything built from it is nondeterministic",
                path=path,
                line=node.lineno,
                hint="iterate sorted(...) instead",
            )
        )

    for node in ast.walk(tree):
        if isinstance(node, ast.For) and _is_unordered_iterable(node.iter):
            flag(node.iter)
        elif isinstance(node, ast.ListComp):
            for gen in node.generators:
                if _is_unordered_iterable(gen.iter):
                    flag(gen.iter)


_BROAD_EXC_NAMES = ("Exception", "BaseException")


def _is_runtime_path(path: str) -> bool:
    """True for files under flexflow_tpu/runtime/ — the fault-domain
    supervision package LINT006 keeps swallow-free."""
    parts = path.replace("\\", "/").split("/")
    return "runtime" in parts


def _is_broad_handler_type(node: ast.AST) -> bool:
    if node is None:
        return False
    if isinstance(node, ast.Tuple):
        return any(_is_broad_handler_type(e) for e in node.elts)
    d = _dotted(node)
    return d is not None and d[-1] in _BROAD_EXC_NAMES


def _is_swallow_body(body: List[ast.stmt]) -> bool:
    """A handler body that discards the exception without routing it
    anywhere: only pass/continue/constant-expression statements."""
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue  # docstring / bare `...`
        return False
    return True


def _lint_swallows_in(nodes, path: str, context: str, diags: List[Diagnostic]) -> None:
    for node in nodes:
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            diags.append(
                error(
                    "LINT006",
                    f"bare `except:` inside {context}: catches "
                    "KeyboardInterrupt/SystemExit and hides the fault "
                    "from the supervision layer",
                    path=path,
                    line=node.lineno,
                    hint="name the exception types, and route the error "
                    "(FaultChannel.post, structured re-raise) instead of "
                    "discarding it",
                )
            )
        elif _is_broad_handler_type(node.type) and _is_swallow_body(node.body):
            diags.append(
                error(
                    "LINT006",
                    f"`except {ast.unparse(node.type)}` with a pass-only "
                    f"body inside {context}: the error never reaches the "
                    "supervision layer",
                    path=path,
                    line=node.lineno,
                    hint="narrow the exception type or route the error "
                    "(post to the FaultChannel, raise a structured "
                    "error, record-and-fall-back)",
                )
            )


def _lint_swallows(tree: ast.AST, path: str, diags: List[Diagnostic]) -> None:
    """LINT006: swallowed exceptions where the supervision layer needs
    errors to propagate — everywhere in runtime/ modules, and inside the
    `_fit_*` training-loop drivers of any module."""
    if _is_runtime_path(path):
        _lint_swallows_in(
            ast.walk(tree), path, "a runtime/ module", diags
        )
        return
    for node in ast.walk(tree):
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)
        ) and node.name.startswith(_FIT_LOOP_PREFIX):
            _lint_swallows_in(
                ast.walk(node),
                path,
                f"training-loop driver {node.name!r}",
                diags,
            )


# -- LINT007: concurrency discipline for runtime/ ---------------------------

_LOCK_FACTORIES = (
    "Lock",
    "RLock",
    "Condition",
    "Semaphore",
    "BoundedSemaphore",
)
# the supervision layer's routing primitives (see module docstring): a
# thread with access to any of these can surface its death/failure
_ROUTE_PRIMITIVES = ("on_hang", "raise_pending", "_async_raise")


def _is_lock_factory_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    return d is not None and d[-1] in _LOCK_FACTORIES


def _self_attr_name(node: ast.AST) -> Optional[str]:
    """'x' for a `self.x` attribute node, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _has_fault_route(nodes) -> bool:
    """A FaultChannel reference (any *channel* identifier), a .post(...)
    call, or a supervision primitive anywhere in `nodes`."""
    for node in nodes:
        if isinstance(node, ast.Attribute):
            ident = node.attr
        elif isinstance(node, ast.Name):
            ident = node.id
        else:
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "post"
            ):
                return True
            continue
        low = ident.lower()
        if "channel" in low or ident in _ROUTE_PRIMITIVES:
            return True
    return False


def _thread_target_attr(call: ast.Call) -> Optional[str]:
    """'_run' for threading.Thread(target=self._run, ...) / Thread(...);
    the bare name for Thread(target=worker). None otherwise."""
    d = _dotted(call.func)
    if d is None or d[-1] != "Thread":
        return None
    for kw in call.keywords:
        if kw.arg == "target":
            td = _dotted(kw.value)
            if td is not None:
                return td[-1]
    return None


def _lint_unlocked_mutations(
    fn: ast.AST, lock_attrs, path: str, diags: List[Diagnostic]
) -> None:
    """Flag `self.attr = ...` in the thread target's OWN body outside a
    `with self.<lock>:` block (nested defs are their own context)."""

    def visit(node, locked: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        if isinstance(node, ast.With):
            holds = locked or any(
                _self_attr_name(item.context_expr) in lock_attrs
                for item in node.items
            )
            for child in ast.iter_child_nodes(node):
                visit(child, holds)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign)) and not locked:
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                attr = _self_attr_name(t)
                if attr is not None and attr not in lock_attrs:
                    diags.append(
                        error(
                            "LINT007",
                            f"thread target {fn.name!r} assigns shared "
                            f"instance state `self.{attr}` without "
                            "holding the owning class's lock — a "
                            "cross-thread data race",
                            path=path,
                            line=node.lineno,
                            hint="wrap the mutation in `with self.<lock>:`"
                            " (Lock/RLock/Condition) or hand the value "
                            "over through a queue/FaultChannel",
                        )
                    )
        for child in ast.iter_child_nodes(node):
            visit(child, locked)

    for stmt in fn.body:
        visit(stmt, False)


def _lint_thread_discipline(
    tree: ast.AST, path: str, diags: List[Diagnostic]
) -> None:
    """LINT007 over one runtime/ module (see module docstring)."""
    if not _is_runtime_path(path):
        return
    # TOP-LEVEL functions only: a class method sharing a module function's
    # name must not shadow it (ast.walk order would let it), or a bare
    # `Thread(target=module_fn)` silently escapes the route check
    module_funcs = {
        n.name: n
        for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    classes = [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]
    for cls in classes:
        methods = {
            n.name: n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        lock_attrs = {
            _self_attr_name(t)
            for m in methods.values()
            for node in ast.walk(m)
            if isinstance(node, ast.Assign)
            and _is_lock_factory_call(node.value)
            for t in node.targets
            if _self_attr_name(t)
        }
        thread_sites: List[Tuple[str, int]] = []  # (target name, lineno)
        for m in methods.values():
            for node in ast.walk(m):
                if isinstance(node, ast.Call):
                    target = _thread_target_attr(node)
                    if target is not None:
                        thread_sites.append((target, node.lineno))
        if any(
            _dotted(b) is not None and _dotted(b)[-1] == "Thread"
            for b in cls.bases
        ) and "run" in methods:
            thread_sites.append(("run", methods["run"].lineno))
        if not thread_sites:
            continue
        for target, _lineno in thread_sites:
            fn = methods.get(target)
            if fn is not None:
                _lint_unlocked_mutations(fn, lock_attrs, path, diags)
        # the route is a CLASS-level property: check once, not per site
        if not _has_fault_route(ast.walk(cls)):
            targets = ", ".join(repr(t) for t, _ in thread_sites)
            diags.append(
                error(
                    "LINT007",
                    f"class {cls.name!r} starts thread(s) "
                    f"(target {targets}) with no fault route: a "
                    "failure in them never reaches the supervision "
                    "layer (the run keeps going silently "
                    "uncheckpointed/unfed)",
                    path=path,
                    line=thread_sites[0][1],
                    hint="post failures to a FaultChannel (or invoke "
                    "a supervision primitive) so the fit loop's next "
                    "window boundary surfaces them",
                )
            )
    # bare-function thread targets (no owning class): the route must live
    # in the target body itself. Construction sites inside classes were
    # handled above — a class's `Thread(target=self._run)` must not be
    # re-attributed to a same-named top-level function.
    class_calls = {
        id(node)
        for cls in classes
        for node in ast.walk(cls)
        if isinstance(node, ast.Call)
    }
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in class_calls:
            continue
        target = _thread_target_attr(node)
        if target is None:
            continue
        fn = module_funcs.get(target)
        if fn is None:
            continue
        _lint_unlocked_mutations(fn, frozenset(), path, diags)
        if not _has_fault_route(ast.walk(fn)):
            diags.append(
                error(
                    "LINT007",
                    f"thread target {target!r} has no fault route: a "
                    "failure in it never reaches the supervision layer",
                    path=path,
                    line=node.lineno,
                    hint="post failures to a FaultChannel so the fit "
                    "loop's next window boundary surfaces them",
                )
            )


# -- LINT008: undonated step-path jit ---------------------------------------

# snake_case tokens marking a jitted callable as a training/serving STEP
# (the params/opt-state trees it closes over are donation-eligible: the
# old values are dead after the update, and an undonated step doubles
# peak HBM for the largest trees in the program)
_STEP_TOKENS = {"step"}
# ...unless the name also says it's a read-only path (no donated update)
_STEP_EXEMPT_TOKENS = {
    "fwd", "forward", "eval", "loss", "stats", "statistics", "metric",
    "metrics",
}


def _lint_undonated_step_jit(
    tree: ast.AST, path: str, diags: List[Diagnostic]
) -> None:
    """LINT008: a `jax.jit`/`jit`/`pjit` call whose jitted callable is a
    step function (name carries a `step` token) but which passes neither
    `donate_argnums` nor `donate_argnames`. Training/serving step paths
    update large params/opt-state trees in place; without donation XLA
    must keep both the argument and result buffers live, doubling peak
    HBM exactly where it binds (the MEM rules then blame the model, not
    the missing flag). Read-only step-adjacent paths (forward/eval/loss)
    are exempt by name token."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _is_jit_callable(node.func):
            continue
        if not node.args:
            continue
        d = _dotted(node.args[0])
        if d is None:
            continue  # lambdas/calls: no step identity to judge
        name = d[-1]
        tokens = set(name.lower().split("_"))
        if not (_STEP_TOKENS & tokens) or (_STEP_EXEMPT_TOKENS & tokens):
            continue
        kwargs = {kw.arg for kw in node.keywords}
        if kwargs & {"donate_argnums", "donate_argnames"}:
            continue
        diags.append(
            error(
                "LINT008",
                f"jax.jit({name}, ...) jits a step callable without "
                "donating its argument trees: the params/opt-state "
                "buffers stay live beside their updated copies, doubling "
                "peak HBM on the training/serving critical path",
                path=path,
                line=node.lineno,
                hint="pass donate_argnums=(0, 1) (params, opt_state) — "
                "or rename the callable if it is genuinely read-only "
                "(fwd/eval/loss tokens are exempt)",
            )
        )


# -- LINT009: literal PRNGKey construction inside step/scan bodies ----------


def _scan_body_target_names(tree: ast.AST) -> Set[str]:
    """Names of functions passed (first positional arg) to `lax.scan` /
    `jax.lax.scan` anywhere in the module — scan bodies run inside the
    step trace even when defined at module scope."""
    targets: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None or d[-1] != "scan":
            continue
        if len(d) >= 2 and d[-2] not in ("lax", "jax"):
            continue  # somebody else's scan
        for arg in node.args[:1]:
            dd = _dotted(arg)
            if dd is not None:
                targets.add(dd[-1])
    return targets


def _is_rng_factory(func: ast.AST) -> bool:
    d = _dotted(func)
    if d is None:
        return False
    if d[-1] == "PRNGKey":
        return True  # jax.random.PRNGKey / random.PRNGKey / bare import
    # jax.random.key (the typed-key constructor); a bare `key(...)` is
    # too generic a name to judge
    return d[-1] == "key" and len(d) >= 2 and d[-2] == "random"


def _lint_literal_rng(
    fn: ast.AST, path: str, context: str, seen: Set[int],
    diags: List[Diagnostic],
) -> None:
    """Flag literal (constant-seed) PRNGKey construction anywhere inside
    `fn` — the whole lexical body runs under the trace, nested scan
    bodies included, so unlike LINT005 nested defs are NOT exempt."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call) or not _is_rng_factory(node.func):
            continue
        seeds = list(node.args) + [kw.value for kw in node.keywords]
        if not seeds or not all(
            isinstance(a, ast.Constant) for a in seeds
        ):
            continue  # a traced/derived seed is a different discussion
        if node.lineno in seen:
            continue  # a scan body nested in a jitted def: flag once
        seen.add(node.lineno)
        diags.append(
            error(
                "LINT009",
                f"literal {ast.unparse(node.func)}(...) constructed "
                f"inside {context} {fn.name!r}: a fresh constant key "
                "mid-step restarts the keystream every step and is "
                "invisible to the carried-key restore — bitwise resume "
                "replays different randomness",
                path=path,
                line=node.lineno,
                hint="derive per-step keys from the CARRIED rng argument "
                "(jax.random.split / fold_in); mint literal keys only "
                "outside traced step bodies",
            )
        )


# the ONE sanctioned home of committed-state resharding (LINT010)
_RESHARD_HOME = ("runtime", "recompile.py")


def _lint_committed_reshard(
    tree: ast.AST, path: str, diags: List[Diagnostic]
) -> None:
    """LINT010: `device_put(x, y.sharding)` — pulling a value onto another
    value's sharding — anywhere but runtime/recompile.py's committed-aware
    `carry()`/`_place_like` path."""
    norm = tuple(path.replace(os.sep, "/").split("/"))
    if norm[-2:] == _RESHARD_HOME:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None or d[-1] != "device_put":
            continue
        target = None
        if len(node.args) >= 2:
            target = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "device":
                    target = kw.value
                    break
        if isinstance(target, ast.Attribute) and target.attr == "sharding":
            diags.append(
                error(
                    "LINT010",
                    "committed-state reshard outside runtime/recompile.py: "
                    "device_put onto another value's .sharding re-places "
                    "training state without the committed-aware "
                    "carry()/_place_like rules (and without the "
                    "TRN001/TRN002 transition gate)",
                    path=path,
                    line=node.lineno,
                    hint="route the placement through "
                    "flexflow_tpu.runtime.recompile._place_like (per "
                    "leaf) or carry() (whole state)",
                )
            )


def lint_source(text: str, path: str = "<string>") -> List[Diagnostic]:
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return [
            error(
                "LINT000",
                f"syntax error: {e.msg}",
                path=path,
                line=e.lineno,
            )
        ]
    diags: List[Diagnostic] = []
    jit_targets = _jit_target_names(tree)
    shard_map_targets = _shard_map_target_names(tree)
    scan_targets = _scan_body_target_names(tree)
    rng_seen: Set[int] = set()
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_jitted_def(node, jit_targets):
            _lint_jit_body(node, path, diags)
            _lint_literal_rng(node, path, "jitted body", rng_seen, diags)
        elif node.name in scan_targets:
            _lint_literal_rng(node, path, "scan body", rng_seen, diags)
        if node.name in shard_map_targets:
            _lint_jit_body(
                node, path, diags, rule="LINT004", context="shard_map body"
            )
            # shard_map kernel bodies run inside the step trace too —
            # same carried-keystream contract as jitted/scan bodies
            _lint_literal_rng(
                node, path, "shard_map body", rng_seen, diags
            )
        if node.name.startswith(_FIT_LOOP_PREFIX):
            _lint_jit_body(
                node, path, diags, rule="LINT005",
                context="training-loop driver",
                nodes=_walk_excluding_nested_defs(node),
            )
    _lint_id_keys(tree, path, diags)
    _lint_unordered_iteration(tree, path, diags)
    _lint_swallows(tree, path, diags)
    _lint_thread_discipline(tree, path, diags)
    _lint_undonated_step_jit(tree, path, diags)
    _lint_committed_reshard(tree, path, diags)
    return diags


def lint_file(path: str) -> List[Diagnostic]:
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return [error("LINT000", f"cannot read file: {e}", path=path)]
    return lint_source(text, path)


def lint_package(root: Optional[str] = None) -> List[Diagnostic]:
    """Lint every .py file under `root` (default: the flexflow_tpu package
    this module lives in)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    diags: List[Diagnostic] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d != "__pycache__" and not d.startswith(".")
        )
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                diags.extend(lint_file(os.path.join(dirpath, fn)))
    return diags

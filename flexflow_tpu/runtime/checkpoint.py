"""Training-state checkpointing: params + optimizer state + step, with an
async background writer for the elastic runtime.

New capability relative to the reference (SURVEY.md §5 "Checkpoint/resume":
the reference round-trips weights only and has no optimizer-state
checkpointing). Two interchangeable backends:

- "npz": portable flat-file numpy layout (no deps, host-local). Trees are
  flattened to '/'-joined key paths written as one raw .npy per leaf plus
  a keys.json manifest (legacy single-archive state.npz checkpoints still
  restore); raw .npy keeps writer-thread serialization at C speed under a
  saturated XLA thread pool, where np.savez's zip bookkeeping starves.
- "orbax": orbax.checkpoint PyTree round-trip — the production path on pods
  (async, sharded, multi-host); used when available unless overridden.

Three layers:

1. `CheckpointManager` — step-indexed directory with retention and atomic
   commits. `save` starts the device→host transfer for EVERY leaf before
   any gather (one batched `jax.device_get` of the whole tree, not a
   per-leaf `np.asarray` walk that serializes N round-trips), and directory
   I/O criticals retry with jittered backoff (runtime/retry.py).
2. `AsyncCheckpointWriter` — a background writer thread: `submit` makes a
   cheap device-side copy of the state (donated step buffers cannot
   invalidate it), kicks off the D2H transfer non-blocking, and returns;
   the gather + serialization + atomic rename run on the writer thread,
   overlapped with the next steps and visible as a `checkpoint` span on
   the Chrome trace.
3. `TrainingCheckpointer` — the fit()-loop session: interval policy
   (`checkpoint_every_n_steps`), full-resume snapshots (params, opt state,
   RNG stream position, dataloader epoch + within-epoch cursor), and
   `resume_state()` for `fit(resume=True)`'s bitwise-deterministic restart.

On restore, arrays are placed back onto devices with `jax.device_put` using
the shardings of a template tree when one is provided (the analogue of the
reference re-attaching weights to logical regions) — the same path that
re-shards a restored checkpoint onto a DEGRADED grid after
`recover_from_grid_change` (runtime/recompile.py).
"""

from __future__ import annotations

import itertools
import json
import os
import queue
import re
import shutil
import sys
import threading
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from flexflow_tpu.runtime.integrity import (
    IntegrityViolation,
    build_manifest,
    verify_and_load_leaves,
    warn_legacy_once,
)
from flexflow_tpu.runtime.retry import with_retry


class CheckpointError(RuntimeError):
    """Structured checkpoint failure: carries the directory, the step asked
    for, and the steps actually available, so recovery tooling can decide
    (retry, fall back to an older step, cold-start) without parsing text."""

    def __init__(
        self,
        message: str,
        *,
        directory: Optional[str] = None,
        step: Optional[int] = None,
        available_steps: Optional[List[int]] = None,
    ) -> None:
        parts = [message]
        if directory is not None:
            parts.append(f"directory={directory!r}")
        if step is not None:
            parts.append(f"step={step}")
        if available_steps is not None:
            parts.append(f"available_steps={available_steps}")
        super().__init__("; ".join(parts))
        self.directory = directory
        self.step = step
        self.available_steps = available_steps


class CheckpointCorruptError(CheckpointError):
    """A checkpoint step failed integrity verification (truncated leaf,
    checksum/dtype/shape mismatch, unreadable manifest). `leaf` names the
    first bad leaf when one was identified; `reason` is the verifier's
    diagnosis. restore(step=None) QUARANTINES the corrupt step as
    `step_N.corrupt` and falls back to the newest step that verifies;
    an explicitly requested step raises this instead (asking for step N
    and silently getting step N-8 would be worse than failing)."""

    def __init__(
        self,
        message: str,
        *,
        reason: str = "",
        leaf: Optional[str] = None,
        directory: Optional[str] = None,
        step: Optional[int] = None,
        available_steps: Optional[List[int]] = None,
    ) -> None:
        super().__init__(
            message,
            directory=directory,
            step=step,
            available_steps=available_steps,
        )
        self.reason = reason or message
        self.leaf = leaf


def _flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            assert "/" not in str(k), f"checkpoint keys may not contain '/': {k}"
            out.update(_flatten(v, f"{prefix}{k}/"))
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Any:
    if list(flat.keys()) == [""]:
        return flat[""]
    root: Dict[str, Any] = {}
    for path, v in flat.items():
        parts = path.split("/")
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


def _tree_paths(tree: Any, prefix: str = "") -> Iterator[str]:
    """Leaf key paths of a (possibly nested) dict tree — the structural
    identity `restore` validates against the template."""
    if isinstance(tree, dict):
        for k in tree:
            yield from _tree_paths(tree[k], f"{prefix}{k}/")
    else:
        yield prefix[:-1]


def _place_like(t: Any, v: Any) -> Any:
    """Restore leaf `v` with template `t`'s dtype and placement: cast on
    the host, then place through the ONE committed-aware placement rule
    (runtime/recompile._place_like — LINT010 keeps the raw
    `device_put(x, y.sharding)` reshard out of everywhere else). Committed
    templates (mesh-placed weights — incl. a NEW, smaller mesh after
    degraded-grid recovery) pull the value onto their sharding; uncommitted
    templates (DP params, optimizer step scalars) stay uncommitted, since
    committing them to the default device would conflict with
    mesh-committed batches inside the next jitted step."""
    from flexflow_tpu.runtime.recompile import _place_like as _committed_place

    host = np.asarray(v).astype(t.dtype) if hasattr(t, "dtype") else np.asarray(v)
    return _committed_place(host, t) if isinstance(t, jax.Array) else host


def _start_host_transfer(tree: Any) -> None:
    """Kick off the device→host copy of every array leaf WITHOUT blocking:
    by the time the batched gather walks the tree, the transfers are
    already in flight instead of being issued one blocking leaf at a
    time."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()


_COPY_PROGRAM = None


def _device_snapshot(tree: Any) -> Any:
    """Device-side defensive copy of a state tree. The train step donates
    its params/opt-state buffers, so an async writer holding the ORIGINAL
    arrays would read invalidated memory once the next window dispatches;
    the copy is enqueued on the device stream before that dispatch and its
    buffers are never donated (no donate_argnums here, so XLA cannot alias
    them back onto the inputs). ONE jitted program for the whole tree: a
    per-leaf jnp.copy walk costs a dispatch per leaf on the training
    thread — measured ~10 ms per snapshot on the busy fused proxy vs ~1 ms
    fused."""
    import jax.numpy as jnp

    global _COPY_PROGRAM
    if _COPY_PROGRAM is None:
        _COPY_PROGRAM = jax.jit(
            lambda t: jax.tree_util.tree_map(jnp.copy, t)
        )
    return _COPY_PROGRAM(tree)


_TMP_SEQ = itertools.count()

# tmp dirs with a write IN FLIGHT in this process: another writer's _gc
# must not reap them mid-serialization (two managers snapshotting the
# same step — e.g. a recovery path racing the interval writer — would
# otherwise FileNotFound each other's commits). Cross-process writers are
# covered by the pid baked into the tmp suffix: _gc only reaps a suffixed
# tmp whose owning pid is dead (see _tmp_owner_alive).
_LIVE_TMPS: set = set()
_LIVE_TMPS_LOCK = threading.Lock()

_TMP_SUFFIX_RE = re.compile(r"step_\d+\.tmp\.(\d+)_\d+$")


def _tmp_owner_alive(name: str) -> bool:
    """True when a suffixed tmp dir's owning PROCESS still exists — its
    write may be in flight, so GC must leave it alone (a zombie job
    checkpointing beside a restarted one must not eat the restart's
    commit). Legacy bare `step_N.tmp` names carry no owner and are
    always reapable; a dead/unparseable pid means crashed — reap."""
    m = _TMP_SUFFIX_RE.search(name)
    if m is None:
        return False
    pid = int(m.group(1))
    if pid == os.getpid():
        # our own process: liveness is the _LIVE_TMPS registry (a stale
        # same-pid tmp with no registered write is a crashed thread's
        # leftover and reapable)
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else
    return True


def _commit_rename(src: str, dst: str) -> None:
    """The atomic commit: clear any previously-committed dst (a losing
    concurrent writer must replace, not ENOTEMPTY-fail), then rename.
    Runs INSIDE the retry so a racing writer's freshly-committed dst is
    re-cleared on the retried attempt."""
    shutil.rmtree(dst, ignore_errors=True)
    os.replace(src, dst)


def _maybe_faulted_commit(step: int):
    """_commit_rename, optionally wrapped with the chaos schedule's
    `ckpt_write` site: the FIRST commit attempt for a firing step raises
    a transient InjectedFault (an OSError the retry backoff absorbs);
    subsequent attempts go straight through."""
    from flexflow_tpu.runtime.fault import active_schedule

    sched = active_schedule()
    if sched is None or not sched.fire_once("ckpt_write", step):
        return _commit_rename
    state = {"armed": True}

    def commit(src, dst):
        if state.pop("armed", False):
            from flexflow_tpu.runtime.fault import InjectedFault

            raise InjectedFault("ckpt_write", step)
        return _commit_rename(src, dst)

    return commit


class CheckpointManager:
    """Step-indexed checkpoint directory with retention.

    Layout: <dir>/step_<N>/{state.npz|orbax tree}, meta.json. Commits are
    atomic (write to step_<N>.tmp, `os.replace` rename): a crash mid-save
    leaves a `.tmp` directory that never counts as a checkpoint
    (`all_steps` requires the committed name + meta.json) and is GC'd by
    the next save.
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        backend: Optional[str] = None,
    ) -> None:
        self.directory = os.path.abspath(directory)
        self.max_to_keep = max_to_keep
        if backend is None:
            try:
                import orbax.checkpoint  # noqa: F401

                backend = "orbax"
            except ImportError:
                backend = "npz"
        assert backend in ("npz", "orbax"), backend
        self.backend = backend
        # the most recent restore's integrity/fallback record (see
        # restore()); None until a restore ran
        self.last_restore_report: Optional[Dict[str, Any]] = None
        os.makedirs(self.directory, exist_ok=True)

    # -- bookkeeping -------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}")

    def all_steps(self):
        steps = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m and os.path.exists(
                os.path.join(self.directory, name, "meta.json")
            ):
                steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _gc(self) -> None:
        # crash-during-save leftovers first: a partial step_<N>.tmp[.*]
        # (concurrent writers get unique suffixes) or a committed dir that
        # lost its meta.json is not a checkpoint and must not shadow one
        corrupt = []
        with _LIVE_TMPS_LOCK:
            live = set(_LIVE_TMPS)
        for name in os.listdir(self.directory):
            if re.fullmatch(r"step_\d+\.tmp(\..+)?", name):
                path = os.path.join(self.directory, name)
                if path in live or _tmp_owner_alive(name):
                    continue  # a writer is mid-commit: not stale
                shutil.rmtree(path, ignore_errors=True)
            m = re.fullmatch(r"step_(\d+)\.corrupt", name)
            if m:
                corrupt.append(int(m.group(1)))
        # quarantined steps are kept as evidence, but bounded by the same
        # retention knob so a flaky filesystem cannot fill the disk
        corrupt.sort()
        while len(corrupt) > self.max_to_keep:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{corrupt.pop(0)}.corrupt"),
                ignore_errors=True,
            )
        steps = self.all_steps()
        while len(steps) > self.max_to_keep:
            shutil.rmtree(self._step_dir(steps.pop(0)), ignore_errors=True)

    # -- save / restore ----------------------------------------------------

    def save(
        self,
        step: int,
        params: Any,
        opt_state: Any = None,
        extra: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Synchronous save: batched device→host gather (transfers for all
        leaves start before any blocks), then serialize + atomic commit."""
        from flexflow_tpu.observability.trace import record_span

        state = {"params": params}
        if opt_state is not None:
            state["opt_state"] = opt_state
        with record_span(
            "checkpoint", step=step, backend=self.backend, mode="sync"
        ):
            _start_host_transfer(state)
            state_host = jax.tree_util.tree_map(
                np.asarray, jax.device_get(state)
            )
            return self._write_host_state(step, state_host, extra)

    def _write_host_state(
        self, step: int, state_host: Any, extra: Optional[Dict[str, Any]]
    ) -> str:
        """Serialization + atomic rename commit of an already-host-resident
        state tree (the async writer's thread-side half)."""
        d = self._step_dir(step)
        # unique tmp per writer: two writers racing the same step (two
        # managers, a crashed-and-restarted job beside a zombie) must not
        # interleave files inside ONE tmp dir — each commits its own
        # complete tree and the last rename wins
        tmp = f"{d}.tmp.{os.getpid()}_{next(_TMP_SEQ)}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        with _LIVE_TMPS_LOCK:
            _LIVE_TMPS.add(tmp)
        try:
            return self._serialize_and_commit(step, state_host, extra, d, tmp)
        finally:
            with _LIVE_TMPS_LOCK:
                _LIVE_TMPS.discard(tmp)

    def _serialize_and_commit(
        self, step: int, state_host: Any, extra, d: str, tmp: str
    ) -> str:
        if self.backend == "orbax":
            import orbax.checkpoint as ocp

            with ocp.PyTreeCheckpointer() as ckptr:
                ckptr.save(os.path.join(tmp, "tree"), state_host)
        else:
            # one raw .npy per leaf + a key manifest, NOT np.savez: the
            # zip container's pure-Python member bookkeeping starves under
            # a saturated XLA thread pool (measured 200-500 ms per ~1 MB
            # save DURING training vs ~1 ms idle), which backs the async
            # writer up past the inter-snapshot gap and blocks submit;
            # np.save's C-level buffer writes stay cheap under load.
            # keys.json carries the integrity manifest: per-leaf CRC32 +
            # dtype/shape, verified on restore (runtime/integrity.py)
            flat = _flatten(state_host)
            order = sorted(flat)
            for i, key in enumerate(order):
                np.save(os.path.join(tmp, f"arr_{i}.npy"), flat[key])
            with open(os.path.join(tmp, "keys.json"), "w") as f:
                json.dump(build_manifest(order, flat), f)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(
                {
                    "step": step,
                    "backend": self.backend,
                    "extra": extra or {},
                },
                f,
            )
        # the commit rename is the one critical the whole save hangs on:
        # transient errors on network filesystems get the backoff (the
        # stale-dst clear lives inside the retried callable — see
        # _commit_rename). The chaos schedule's `ckpt_write` site injects
        # exactly one such transient here (runtime/fault.py) to prove the
        # backoff absorbs it.
        commit = _maybe_faulted_commit(step)
        with_retry(commit, tmp, d, description="checkpoint commit")
        self._gc()
        return d

    def _read_meta(self, d: str) -> dict:
        def read():
            with open(os.path.join(d, "meta.json")) as f:
                return json.load(f)

        return with_retry(read, description="checkpoint meta read")

    def restore(
        self,
        step: Optional[int] = None,
        template: Any = None,
        verify_integrity: bool = True,
    ) -> Tuple[int, Any, Any, Dict[str, Any]]:
        """Returns (step, params, opt_state, extra). `template` (a
        {"params":..., "opt_state":...} pytree of arrays) re-applies each
        leaf's sharding/dtype via device_put and VALIDATES the restored
        tree structure (missing/extra key paths raise CheckpointError
        naming them).

        With `verify_integrity` (the default) every leaf is checked
        against the keys.json manifest (CRC32 + dtype/shape,
        runtime/integrity.py). A corrupt/truncated step: raises
        CheckpointCorruptError when it was EXPLICITLY requested;
        otherwise (step=None, "give me the latest") it is quarantined as
        `step_N.corrupt` and the walk falls back to the newest step that
        verifies. The fallback decision is recorded in
        `self.last_restore_report` ({"restored_step", "quarantined":
        [{"step","reason","leaf"}...], "legacy", "verified"}) so callers
        (TrainingCheckpointer → FFModel) can log it to provenance and the
        metrics stream."""
        self.last_restore_report = None
        available = self.all_steps()
        if not available:
            raise CheckpointError(
                "no checkpoints found",
                directory=self.directory,
                available_steps=available,
            )
        requested = step
        quarantined: List[Dict[str, Any]] = []
        while True:
            s = requested if requested is not None else available[-1]
            if s not in available:
                raise CheckpointError(
                    "checkpoint step not found",
                    directory=self.directory,
                    step=s,
                    available_steps=available,
                )
            try:
                state, meta, integrity_mode = self._load_step(
                    s, verify_integrity=verify_integrity
                )
                break
            except CheckpointCorruptError as e:
                if requested is not None or not verify_integrity:
                    raise
                quarantined.append(
                    {"step": s, "reason": e.reason, "leaf": e.leaf}
                )
                self._quarantine(s, e)
                available = self.all_steps()
                if s in available:
                    # quarantine could not move OR remove the dir (e.g. a
                    # read-only snapshot mount): the walk cannot make
                    # progress — surface the corruption instead of
                    # re-verifying the same step forever
                    raise CheckpointError(
                        "corrupt checkpoint could not be quarantined "
                        f"(directory not writable?): {e.reason}",
                        directory=self.directory,
                        step=s,
                        available_steps=available,
                    ) from e
                if not available:
                    raise CheckpointError(
                        "no checkpoint survived integrity verification "
                        f"(quarantined steps: {[q['step'] for q in quarantined]})",
                        directory=self.directory,
                        step=requested,
                        available_steps=available,
                    ) from e
        if not isinstance(state, dict) or "params" not in state:
            raise CheckpointError(
                "checkpoint archive lacks a 'params' tree "
                f"(found keys: {sorted(state) if isinstance(state, dict) else type(state).__name__})",
                directory=self.directory,
                step=s,
                available_steps=available,
            )
        if template is not None:
            state = self._apply_template(template, state, s, available)
        params = state.get("params")
        opt_state = state.get("opt_state")
        self.last_restore_report = {
            "restored_step": s,
            "requested_step": requested,
            "quarantined": quarantined,
            # integrity: "verified" (manifest checksums checked),
            # "legacy" (pre-manifest layout, no checksums to check),
            # "unverified" (caller passed verify_integrity=False),
            # "orbax-managed" (orbax's own metadata, not ours)
            "integrity": integrity_mode,
            "legacy": integrity_mode == "legacy",
            "verified": integrity_mode == "verified",
        }
        return s, params, opt_state, meta.get("extra", {})

    def _load_step(
        self, step: int, verify_integrity: bool = True
    ) -> Tuple[Any, dict, str]:
        """One step directory → (state tree, meta, integrity mode) with
        every truncation/corruption failure mode normalized to
        CheckpointCorruptError (a restore path that dies with a raw
        EOFError deep in np.load cannot drive a fallback)."""
        d = self._step_dir(step)
        available = self.all_steps()

        def corrupt(reason: str, leaf: Optional[str] = None, cause=None):
            err = CheckpointCorruptError(
                f"checkpoint failed integrity verification: {reason}",
                reason=reason,
                leaf=leaf,
                directory=self.directory,
                step=step,
                available_steps=available,
            )
            err.__cause__ = cause
            return err

        try:
            meta = self._read_meta(d)
        except (OSError, ValueError) as e:
            raise corrupt(f"unreadable meta.json: {e}", cause=e)
        if meta.get("backend") == "orbax":
            import orbax.checkpoint as ocp

            try:
                with ocp.PyTreeCheckpointer() as ckptr:
                    state = ckptr.restore(os.path.join(d, "tree"))
            except Exception as e:
                # orbax carries its own integrity metadata; normalize its
                # failure so the quarantine/fallback walk applies to this
                # backend too
                raise corrupt(f"orbax restore failed: {e}", cause=e)
            return state, meta, "orbax-managed"
        if os.path.exists(os.path.join(d, "state.npz")):
            # legacy single-archive layout (pre-elastic checkpoints):
            # no manifest — verified-as-legacy, warned once per directory
            try:
                with np.load(os.path.join(d, "state.npz")) as z:
                    state = _unflatten({k: z[k] for k in z.files})
            except Exception as e:
                raise corrupt(f"unreadable state.npz: {e}", cause=e)
            if verify_integrity:
                warn_legacy_once(self.directory, "state.npz archive")
            return state, meta, "legacy"
        try:
            flat, verified = verify_and_load_leaves(
                d, verify=verify_integrity
            )
        except IntegrityViolation as e:
            raise corrupt(e.reason, leaf=e.leaf, cause=e)
        if verified:
            mode = "verified"
        elif verify_integrity:
            mode = "legacy"  # manifest absent (warned once)
        else:
            mode = "unverified"  # caller opted out of checking
        return _unflatten(flat), meta, mode

    def _quarantine(self, step: int, err: CheckpointCorruptError) -> None:
        """Move a corrupt step aside as step_N.corrupt: it stops counting
        (all_steps/latest_step/GC stay honest) but the evidence survives
        for a post-mortem, bounded by the retention knob."""
        d = self._step_dir(step)
        dst = d + ".corrupt"
        shutil.rmtree(dst, ignore_errors=True)
        try:
            os.rename(d, dst)
        except OSError:
            # cross-writer race or a filesystem that cannot rename the
            # damaged dir: removing it is the only way to stop it
            # shadowing good checkpoints
            shutil.rmtree(d, ignore_errors=True)
        print(
            f"[flexflow_tpu] checkpoint step {step} quarantined as "
            f"{os.path.basename(dst)}: {err.reason}",
            file=sys.stderr,
        )

    def _apply_template(
        self, template: Any, state: Any, step: int, available: List[int]
    ) -> Any:
        """Per-top-key structural validation + device placement. Keys the
        template does not mention pass through untouched; keys it does
        mention must exist in the archive with the identical leaf path
        set."""
        out = dict(state)
        for key, tmpl in template.items():
            if key not in state:
                raise CheckpointError(
                    f"archive is missing the {key!r} tree the template "
                    "expects",
                    directory=self.directory,
                    step=step,
                    available_steps=available,
                )
            tpaths = set(_tree_paths(tmpl))
            spaths = set(_tree_paths(state[key]))
            if tpaths != spaths:
                missing = sorted(tpaths - spaths)[:8]
                extra_paths = sorted(spaths - tpaths)[:8]
                raise CheckpointError(
                    f"restored {key!r} tree does not match the template: "
                    f"missing paths {missing}, unexpected paths "
                    f"{extra_paths}",
                    directory=self.directory,
                    step=step,
                    available_steps=available,
                )
            out[key] = jax.tree_util.tree_map(_place_like, tmpl, state[key])
        return out


_SHUTDOWN = object()


class AsyncCheckpointWriter:
    """Background checkpoint writer: device-side snapshot + non-blocking
    D2H kick-off on the caller's thread, gather/serialize/commit on a
    daemon writer thread. One save in flight at a time (`submit` blocks if
    the previous save has not committed — bounded memory, ordered
    commits). Writer-side exceptions surface on the NEXT
    check()/submit/wait — with a FaultChannel attached (the fit loop's
    supervision bundle) they are posted there and the loop's next window
    boundary / `due()` call raises them as a `BackgroundFault` naming the
    `checkpoint_writer` site, so the training loop is never silently
    uncheckpointed."""

    SITE = "checkpoint_writer"

    def __init__(
        self, manager: CheckpointManager, fault_channel=None
    ) -> None:
        self.manager = manager
        self.fault_channel = fault_channel
        self._queue: queue.Queue = queue.Queue(maxsize=1)
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="ff-checkpoint-writer", daemon=True
        )
        self._thread.start()

    def _post_failure(self, exc: BaseException) -> None:
        if self.fault_channel is not None:
            self.fault_channel.post(self.SITE, exc)
        else:
            self._exc = exc

    def _raise_pending(self) -> None:
        if self.fault_channel is not None:
            self.fault_channel.raise_pending(site=self.SITE)
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def check(self) -> None:
        """Raise any writer-side failure NOW (TrainingCheckpointer calls
        this from every `due()` — a commit that died at step N surfaces
        at the N+1 boundary, not at final wait())."""
        self._raise_pending()

    def submit(
        self,
        step: int,
        params: Any,
        opt_state: Any = None,
        extra: Optional[Dict[str, Any]] = None,
        rng: Any = None,
    ) -> None:
        """`rng` (the fit loop's carry key) rides the DEVICE snapshot and
        is materialized into extra["rng"] on the writer thread: a
        device_get of the key on the caller's thread would block until the
        in-flight window computes it — the one sync that measurably
        dominated the async path's overhead."""
        self._raise_pending()
        state = {"params": params}
        if opt_state is not None:
            state["opt_state"] = opt_state
        if rng is not None:
            state["__rng__"] = rng
        snap = _device_snapshot(state)
        # the D2H kick-off happens on the WRITER thread (_run): on backends
        # where copy_to_host_async waits for a not-yet-computed source (the
        # copy program just enqueued behind the in-flight window), calling
        # it here would stall the training thread for a full window
        self._queue.put((step, snap, extra))

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            try:
                if item is _SHUTDOWN:
                    return
                step, snap, extra = item
                try:
                    from flexflow_tpu.observability.trace import record_span

                    # the span lands on the writer thread's timeline row,
                    # BESIDE the fit thread's step spans — the overlap with
                    # the next steps is directly visible
                    with record_span(
                        "checkpoint",
                        step=step,
                        backend=self.manager.backend,
                        mode="async",
                    ):
                        _start_host_transfer(snap)
                        host = jax.tree_util.tree_map(
                            np.asarray, jax.device_get(snap)
                        )
                        rng_host = host.pop("__rng__", None)
                        if rng_host is not None:
                            extra = dict(extra or {})
                            extra["rng"] = np.asarray(rng_host).tolist()
                        self.manager._write_host_state(step, host, extra)
                except BaseException as e:  # surfaces at next check/due
                    self._post_failure(e)
            finally:
                self._queue.task_done()

    def wait(self) -> None:
        """Block until every submitted save has committed (fit() calls this
        before returning / re-raising, so the last checkpoint is durable)."""
        self._queue.join()
        self._raise_pending()

    def close(self) -> None:
        if not self._thread.is_alive():
            return
        self._queue.join()
        self._queue.put(_SHUTDOWN)
        self._thread.join(timeout=30.0)
        self._raise_pending()


@dataclass
class ResumeState:
    """Everything `fit(resume=True)` needs for a bitwise-identical restart:
    training progress, live state, the RNG stream position, and the
    dataloader's shuffle position (epoch + within-epoch batch cursor)."""

    step: int
    params: Any
    opt_state: Any
    rng: Any
    epoch: int
    batch_in_epoch: int
    epoch_offset: int
    # the restore's integrity record (CheckpointManager.last_restore_report):
    # carries any quarantine/fallback decision for provenance logging
    restore_report: Optional[Dict[str, Any]] = None


class TrainingCheckpointer:
    """The fit()-loop checkpoint session (`checkpoint_dir` +
    `checkpoint_every_n_steps`): interval policy, full-resume snapshots,
    async by default with an explicit sync mode for A/B measurement
    (`checkpoint_sync`)."""

    def __init__(
        self,
        directory: str,
        every_n_steps: int = 0,
        max_to_keep: int = 3,
        sync: bool = False,
        backend: Optional[str] = None,
        fault_channel=None,
    ) -> None:
        self.manager = CheckpointManager(
            directory, max_to_keep=max_to_keep, backend=backend
        )
        self.every = int(every_n_steps)
        self.sync = bool(sync)
        self._writer = (
            None
            if sync
            else AsyncCheckpointWriter(
                self.manager, fault_channel=fault_channel
            )
        )

    def due(self, prev_step: int, step: int) -> bool:
        """True when [prev_step, step] crossed an interval boundary. Also
        the async writer's surfacing point: a commit that failed (retries
        exhausted) since the last boundary raises HERE, one step later,
        instead of hiding until final wait()."""
        if self._writer is not None:
            self._writer.check()
        if self.every <= 0:
            return False
        return prev_step // self.every < step // self.every

    def snapshot(
        self,
        step: int,
        params: Any,
        opt_state: Any,
        rng,
        epoch: int,
        batch_in_epoch: int,
        epoch_offset: int = 0,
    ) -> None:
        """Snapshot at a step boundary. `rng` is the fit loop's
        POST-step carry key (the exact stream position the next step will
        split from); the dataloader cursor pins the shuffle position. On
        the async path the key is materialized on the WRITER thread — a
        host readback here would block the training thread until the
        in-flight window finishes."""
        extra = {
            "epoch": int(epoch),
            "batch_in_epoch": int(batch_in_epoch),
            "epoch_offset": int(epoch_offset),
        }
        if self._writer is not None:
            self._writer.submit(step, params, opt_state, extra, rng=rng)
        else:
            extra["rng"] = np.asarray(jax.device_get(rng)).tolist()
            self.manager.save(step, params, opt_state, extra=extra)

    def resume_state(self, template: Any = None) -> Optional[ResumeState]:
        """Latest full-resume snapshot, or None when the directory is empty
        (cold start). Raises CheckpointError when a checkpoint exists but
        lacks the resume extras (it was written by save_checkpoint, not a
        fit-loop snapshot — resuming from it would silently replay data)."""
        if self.manager.latest_step() is None:
            return None
        import jax.numpy as jnp

        step, params, opt_state, extra = self.manager.restore(
            template=template
        )
        if "rng" not in extra:
            raise CheckpointError(
                "checkpoint has no resume metadata (rng/dataloader cursor) "
                "— it was not written by a fit-loop snapshot",
                directory=self.manager.directory,
                step=step,
                available_steps=self.manager.all_steps(),
            )
        rng = jnp.asarray(np.asarray(extra["rng"], dtype=np.uint32))
        return ResumeState(
            step=step,
            params=params,
            opt_state=opt_state,
            rng=rng,
            epoch=int(extra.get("epoch", 0)),
            batch_in_epoch=int(extra.get("batch_in_epoch", 0)),
            epoch_offset=int(extra.get("epoch_offset", 0)),
            restore_report=self.manager.last_restore_report,
        )

    def finalize(self) -> None:
        """Drain and retire the writer (fit exit — normal or fault): every
        submitted snapshot is durable before control leaves fit()."""
        if self._writer is not None:
            self._writer.close()

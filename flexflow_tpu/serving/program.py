"""Forward-only serving programs over a (searched) PCG (ISSUE 12).

Two donated XLA programs per plan, both driven by ONE graph interpreter
that mirrors the executor's global-view lowering
(parallel/executor.py) with the attention ops swapped for KV-cached
causal attention:

- **prefill**: the whole prompt in one forward pass (causal-masked), its
  K/V written into the slots being admitted; the last valid position's
  logits seed generation. One donated jit — the cache buffer is reused
  in place.
- **decode window**: `lax.scan` over W single-token steps: W kernel
  launches collapse into one dispatch, the cache and
  the per-slot length/token state ride the scan carry, and greedy
  (argmax) sampling feeds each step's token to the next.

Non-attention ops lower exactly like training forward: kernel_forward
under the plan's sharding constraints, with the PR-6 collective-matmul
kernels active on decode/prefill matmuls when overlap lowering is on
(the same `collect_overlap_sites` map the training executor consults).

Parameters are keyed by WEIGHT ORDINAL ("w0", "w1", ... in topological
order), not node index: the prefill- and decode-shaped PCGs of one model
renumber nodes differently under rewrites, and the ordinal keying is
what lets both programs share one placed parameter set.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from flexflow_tpu.analysis.memory_accounting import ServingMemorySpec
from flexflow_tpu.serving.kv_cache import (
    CacheLayer,
    attention_layers,
    bind_cache_axes,
    cache_shardings,
    init_cache,
)

__all__ = ["ServingProgram", "init_serving_params"]


def init_serving_params(pcg, rng) -> Dict[str, jnp.ndarray]:
    """Weight values keyed by ordinal ("w0", "w1", ...): stable across
    the prefill/decode PCG pair of one model (rewrites renumber nodes but
    preserve the weight sequence), so one parameter set serves both
    programs."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape
    from flexflow_tpu.pcg.initializer import initialize

    params: Dict[str, jnp.ndarray] = {}
    i = 0
    for n in pcg.topological_ordering():
        if isinstance(pcg.op_attrs(n), WeightAttrs):
            (out,) = pcg.outputs_of(n)
            ta = pcg.tensor_attrs(out)
            assert ta.initializer is not None, f"weight {n} missing initializer"
            ts = get_reduced_shape(ta.shape)
            params[f"w{i}"] = initialize(
                ta.initializer, jax.random.fold_in(rng, i),
                ts.dims, ts.dtype.to_jnp(),
            )
            i += 1
    return params


def _weight_ordinals(pcg) -> Dict[object, str]:
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    out = {}
    for n in pcg.topological_ordering():
        if isinstance(pcg.op_attrs(n), WeightAttrs):
            out[n] = f"w{len(out)}"
    return out


def _as_pcg(graph):
    from flexflow_tpu.pcg.computation_graph import ComputationGraph
    from flexflow_tpu.pcg.parallel_computation_graph import (
        ParallelComputationGraph,
        pcg_from_computation_graph,
    )

    if isinstance(graph, ComputationGraph):
        return pcg_from_computation_graph(graph)
    assert isinstance(graph, ParallelComputationGraph)
    return graph


def _sink_logit(pcg):
    """The plan's logit tensor: the unique sink value, read through any
    trailing reshard chain exactly like the training executor
    (_pre_reshard_value) so a searched plan's final Combine never forces
    a full-logit gather per decode step."""
    from flexflow_tpu.parallel.executor import _pre_reshard_value

    sinks = [
        o
        for n in pcg.topological_ordering()
        for o in pcg.outputs_of(n)
        if not pcg.uses_of(o)
    ]
    assert len(sinks) == 1, (
        f"serving expects a single-output model, found {len(sinks)} sinks"
    )
    return _pre_reshard_value(pcg, sinks[0])


class ServingProgram:
    """One serving plan, lowered: prefill + fused decode over a shared
    parameter set and KV cache. `machine_mesh=None` is the single-device
    reference lowering (no constraints) the parity tests compare searched
    plans against."""

    def __init__(
        self,
        graph,
        serving: ServingMemorySpec,
        *,
        mapping: Optional[dict] = None,
        machine_mesh=None,
        overlap: Optional[bool] = None,
        params_seed: int = 0,
        params: Optional[Dict[str, jnp.ndarray]] = None,
    ) -> None:
        from flexflow_tpu.local_execution.config import (
            configure_compilation_cache,
        )
        from flexflow_tpu.op_attrs.ops import InputAttrs
        from flexflow_tpu.parallel.executor import (
            collect_overlap_sites,
            overlap_lowering_active,
        )
        from flexflow_tpu.parallel.sharding import pcg_shardings

        # before this program's first compile (param init below)
        configure_compilation_cache()
        self.pcg = _as_pcg(graph)
        self.serving = serving
        self.machine_mesh = machine_mesh
        self.mesh = None if machine_mesh is None else machine_mesh.mesh
        self.shardings = (
            pcg_shardings(self.pcg, machine_mesh, mapping)
            if machine_mesh is not None
            else {}
        )
        inputs = [
            n
            for n in self.pcg.topological_ordering()
            if isinstance(self.pcg.op_attrs(n), InputAttrs)
        ]
        assert len(inputs) == 1, (
            "serving expects a single-input (decoder-only) model, found "
            f"{len(inputs)} input layers"
        )
        self._input_node = inputs[0]
        self.logit_tensor = _sink_logit(self.pcg)
        self.layers: List[CacheLayer] = attention_layers(self.pcg)
        self._layer_of = {layer.node: layer for layer in self.layers}
        bind_cache_axes(self.pcg, self.layers, self.shardings)
        self._cache_shardings = cache_shardings(self.layers, self.mesh)
        self._weight_key = _weight_ordinals(self.pcg)
        self.overlap_sites = (
            collect_overlap_sites(self.pcg, self.shardings, self.mesh)
            if self.mesh is not None and overlap_lowering_active(overlap)
            else {}
        )
        self.params = (
            params
            if params is not None
            else init_serving_params(self.pcg, jax.random.PRNGKey(params_seed))
        )
        self._place_params()
        self._jit_prefill = None
        self._jit_decode = None

    # -- placement ---------------------------------------------------------

    def _place_params(self) -> None:
        if self.machine_mesh is None:
            return
        from flexflow_tpu.runtime.distributed import device_put_global

        for n, key in self._weight_key.items():
            (out,) = self.pcg.outputs_of(n)
            s = self.shardings.get(out)
            if s is not None:
                self.params[key] = device_put_global(self.params[key], s)

    def init_cache(self):
        """The zeroed per-layer K/V cache, placed under the partition-rule
        shardings bound to this plan."""
        return init_cache(self.layers, self.serving, self.mesh)

    # -- the shared forward interpreter ------------------------------------

    def _constrain(self, v, o):
        s = self.shardings.get(o)
        if s is None:
            return v
        return jax.lax.with_sharding_constraint(v, s)

    def _constrain_cache(self, layer: CacheLayer, k, v):
        sk = self._cache_shardings.get(f"{layer.name}/k")
        sv = self._cache_shardings.get(f"{layer.name}/v")
        if sk is not None:
            k = jax.lax.with_sharding_constraint(k, sk)
        if sv is not None:
            v = jax.lax.with_sharding_constraint(v, sv)
        return k, v

    def _forward(self, params, x, cache, lengths, active, mode):
        """One forward pass of the PCG with KV-cached attention. Returns
        (logits, new_cache). `active` masks the slots this call may touch
        (freshly admitted slots in prefill, generating slots in decode);
        every other slot's cache rides through bit-identically."""
        from flexflow_tpu.kernels import forward as kernel_forward
        from flexflow_tpu.local_execution.training_backing import (
            split_slot_values,
        )
        from flexflow_tpu.op_attrs.core import is_parallel_op
        from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
        from flexflow_tpu.parallel.executor import (
            _try_overlap_ag_matmul,
            _try_pinned_reduction,
        )

        env: Dict = {}
        new_cache = {name: dict(v) for name, v in cache.items()}
        for n in self.pcg.topological_ordering():
            attrs = self.pcg.op_attrs(n)
            outs = self.pcg.outputs_of(n)
            if isinstance(attrs, InputAttrs):
                env[outs[0]] = self._constrain(x, outs[0])
            elif isinstance(attrs, WeightAttrs):
                env[outs[0]] = self._constrain(
                    params[self._weight_key[n]], outs[0]
                )
            elif is_parallel_op(attrs):
                (src,) = self.pcg.inputs_of(n)
                env[outs[0]] = self._constrain(env[src], outs[0])
            elif n in self._layer_of:
                layer = self._layer_of[n]
                in_tensors = self.pcg.inputs_of(n)
                slot_vals = [env[v] for v in in_tensors]
                data_vals, weight_vals = split_slot_values(attrs, slot_vals)
                out, k_new, v_new = self._cached_attention(
                    layer, attrs, data_vals, weight_vals,
                    new_cache[layer.name]["k"], new_cache[layer.name]["v"],
                    lengths, active, mode,
                )
                k_new, v_new = self._constrain_cache(layer, k_new, v_new)
                new_cache[layer.name] = {"k": k_new, "v": v_new}
                env[outs[0]] = self._constrain(out, outs[0])
            else:
                in_tensors = self.pcg.inputs_of(n)
                slot_vals = [env[v] for v in in_tensors]
                data_vals, weight_vals = split_slot_values(attrs, slot_vals)
                fused_kind = self.overlap_sites.get(n)
                if fused_kind == "ag_matmul":
                    fused = _try_overlap_ag_matmul(
                        self.pcg, n, attrs, in_tensors, self.shardings,
                        self.mesh, env,
                    )
                    if fused is not None:
                        env[outs[0]] = fused
                        continue
                pinned = _try_pinned_reduction(
                    self.pcg, n, attrs, slot_vals, in_tensors,
                    self.shardings, self.mesh,
                    ring_overlap=(fused_kind == "matmul_rs"),
                )
                if pinned is not None:
                    env[outs[0]] = pinned
                    continue
                results = kernel_forward(
                    attrs, data_vals, weight_vals, train=False
                )
                for o, r in zip(outs, results):
                    env[o] = r
        return env[self.logit_tensor], new_cache

    def _cached_attention(
        self, layer, attrs, data_vals, weight_vals, cache_k, cache_v,
        lengths, active, mode,
    ):
        """Causal attention over the persistent cache — the serving
        lowering of a MultiHeadAttention node. Prefill writes the whole
        (length-masked) prompt's K/V; decode writes one position per slot
        and attends over everything admitted so far. Math mirrors the
        training kernel's dense path (kernels/ops._mha_forward): scaled
        scores, -1e30 mask, softmax, wo einsum."""
        from flexflow_tpu.kernels.ops import mha_project_qkv

        assert not attrs.qk_norm and attrs.rope_theta is None, (
            "the serving programs do not rotate by cache position or apply "
            "QK-norm yet (ROADMAP R5): training only"
        )
        assert attrs.softmax_scale is None, (
            "the serving programs scale their scores by sqrt(kdim) and carry "
            "no stated softmax_scale yet: training only"
        )
        q, k, v = data_vals
        input_bias = weight_vals[1] if attrs.bias else None
        qp, kp, vp, wo = mha_project_qkv(
            attrs, q, k, v, weight_vals[0], input_bias
        )
        kd = attrs.q_proj_size
        scale = jnp.sqrt(jnp.asarray(kd, qp.dtype))
        big_neg = jnp.asarray(-1e30, qp.dtype)
        seq_cap = self.serving.max_seq_len
        write = active[:, None, None, None]
        if mode == "prefill":
            s = qp.shape[2]
            pos = jnp.arange(s)
            causal = pos[:, None] >= pos[None, :]
            valid_k = pos[None, :] < lengths[:, None]
            mask = causal[None, None, :, :] & valid_k[:, None, None, :]
            scores = jnp.einsum("bhsk,bhtk->bhst", qp, kp) / scale
            attn = jax.nn.softmax(jnp.where(mask, scores, big_neg), axis=-1)
            ctx = jnp.einsum("bhst,bhtv->bhsv", attn, vp)
            pad = seq_cap - s
            assert pad >= 0, (
                f"prompt length {s} exceeds max_seq_len {seq_cap}"
            )
            k_full = jnp.pad(kp, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_full = jnp.pad(vp, ((0, 0), (0, 0), (0, pad), (0, 0)))
            new_k = jnp.where(write, k_full, cache_k)
            new_v = jnp.where(write, v_full, cache_v)
        else:
            # decode: write this token's K/V at each active slot's current
            # length, then attend over positions <= that length
            oh = (
                jnp.arange(seq_cap)[None, :] == lengths[:, None]
            ) & active[:, None]
            ohf = oh[:, None, :, None].astype(cache_k.dtype)
            new_k = cache_k * (1 - ohf) + ohf * kp
            new_v = cache_v * (1 - ohf) + ohf * vp
            limit = jnp.where(active, lengths, 0)
            valid = jnp.arange(seq_cap)[None, :] <= limit[:, None]
            scores = jnp.einsum("bhqd,bhtd->bhqt", qp, new_k) / scale
            attn = jax.nn.softmax(
                jnp.where(valid[:, None, None, :], scores, big_neg), axis=-1
            )
            ctx = jnp.einsum("bhqt,bhtv->bhqv", attn, new_v)
        out = jnp.einsum("bhsv,veh->bse", ctx, wo)
        if attrs.bias:
            out = out + weight_vals[2]
        return out, new_k, new_v

    # -- the two donated programs ------------------------------------------

    def _make_jit_prefill(self):
        """ONE jit configuration for the prefill program — the serving
        path and the exec-contract audit must compile the SAME thing."""
        return jax.jit(self._prefill_impl, donate_argnums=(1,))

    def _make_jit_decode(self):
        """ONE jit configuration for the fused decode window (cache
        donated, step count static), shared with the audit."""
        return jax.jit(
            self._decode_impl, donate_argnums=(1,), static_argnums=(5,)
        )

    def _prefill_impl(self, params, cache, tokens, lengths, fresh):
        logits, new_cache = self._forward(
            params, tokens, cache, lengths, fresh, "prefill"
        )
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1
        )[:, 0, :]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return new_cache, nxt, last

    def _decode_impl(self, params, cache, token, lengths, active, steps):
        def body(carry, _):
            cache, token, lengths = carry
            logits, cache = self._forward(
                params, token[:, None], cache, lengths, active, "decode"
            )
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            token = jnp.where(active, nxt, token)
            lengths = jnp.where(active, lengths + 1, lengths)
            return (cache, token, lengths), nxt

        (cache, token, lengths), toks = jax.lax.scan(
            body, (cache, token, lengths), None, length=steps
        )
        return cache, token, lengths, jnp.swapaxes(toks, 0, 1)

    def prefill(self, cache, tokens, lengths, fresh):
        """Admit prompts: run the donated prefill program. `tokens` is the
        full slot batch (stale slots carry arbitrary values), `lengths`
        the per-slot prompt lengths, `fresh` the admission mask. Returns
        (cache, first generated token per slot, last-position logits)."""
        if self._jit_prefill is None:
            self._jit_prefill = self._make_jit_prefill()
        args = (self.params, cache, tokens, lengths, fresh)
        if self.mesh is None:
            return self._jit_prefill(*args)
        with self.mesh:
            return self._jit_prefill(*args)

    def exec_contract(self, window_steps: int = 4):
        """Execution-contract verification of BOTH donated serving
        programs (ISSUE 14, `analysis/exec_contract.py`): AOT-lower +
        compile the prefill program and a `window_steps` decode window
        against zero-filled example arguments (never executed), census
        nondeterministic instructions, and audit donated-buffer aliasing
        with the KV cache as the expected-in-place state (the MEM005
        serving verdict prices the cache as updated in place — an
        unaliased cache donation doubles exactly the residency the
        admission cap is computed from). Returns
        `{"prefill": (analysis, diags), "decode": (analysis, diags)}`."""
        from flexflow_tpu.analysis.exec_contract import (
            analyze_step_program,
            exec_diagnostics,
        )
        from flexflow_tpu.op_attrs.parallel_tensor_shape import (
            get_reduced_shape,
        )

        (inp,) = self.pcg.outputs_of(self._input_node)
        ts = get_reduced_shape(self.pcg.tensor_shape(inp))
        slots = ts.dims[0]
        cache = self.init_cache()
        tokens = jnp.zeros(tuple(ts.dims), ts.dtype.to_jnp())
        token = jnp.zeros((slots,), jnp.int32)
        lengths = jnp.ones((slots,), jnp.int32)
        mask = jnp.ones((slots,), bool)

        def lower(jitted, *args):
            if self.mesh is None:
                return jitted.lower(*args)
            with self.mesh:
                return jitted.lower(*args)

        out = {}
        lo = lower(
            self._make_jit_prefill(),
            self.params, cache, tokens, lengths, mask,
        )
        a = analyze_step_program(
            lo,
            lo.compile(),
            arg_names=("params", "cache", "tokens", "lengths", "fresh"),
            expected_inplace=(1,),
        )
        out["prefill"] = (a, exec_diagnostics(a))
        lo = lower(
            self._make_jit_decode(),
            self.params, cache, token, lengths, mask, int(window_steps),
        )
        a = analyze_step_program(
            lo,
            lo.compile(),
            arg_names=("params", "cache", "token", "lengths", "active"),
            expected_inplace=(1,),
        )
        out["decode"] = (a, exec_diagnostics(a))
        return out

    def decode_window(self, cache, token, lengths, active, steps: int):
        """One fused decode window: `steps` greedy decode steps in ONE
        donated dispatch (lax.scan). Returns (cache, token, lengths,
        generated tokens [slots, steps])."""
        if self._jit_decode is None:
            self._jit_decode = self._make_jit_decode()
        args = (self.params, cache, token, lengths, active, int(steps))
        if self.mesh is None:
            return self._jit_decode(*args)
        with self.mesh:
            return self._jit_decode(*args)

"""Mixture-of-Experts operators: GroupBy, Aggregate, Experts.

Reference: examples/cpp/mixture_of_experts/moe.cc builds MoE from the legacy
composition gating-dense -> softmax -> TopK -> GroupBy -> expert towers ->
Aggregate (ff.moe(input, num_exp, num_select, hidden_size, alpha, lambda);
legacy Group_by/Aggregate ops, SURVEY.md §2.12 expert-parallelism row).

TPU-native design: GroupBy/Aggregate are kept for composition parity (one-hot
dispatch masks, frozen) but the centerpiece is the fused `ExpertsAttrs` op:
router -> top-k -> dispatch -> expert MLP -> combine in one node, whose expert
dimension shards over a mesh axis. Dispatch is by sorted index
(`kernels/moe.py`): the N*k routing decisions are sorted by expert, the token
rows gathered in that order and multiplied by the experts' matrices as grouped
(ragged) matmuls in the compute dtype. Shapes stay static because the row
count is always N*k; only the group sizes are data. A finite
`capacity_factor` bounds per-expert work exactly like the reference's `alpha`
argument to GroupBy (moe.cc `moeConfig.alpha`) by zeroing the decisions whose
rank within their expert is past the capacity; `capacity_factor=None` is
dropless. The earlier one-hot `[N*k, E, capacity]` formulation could not run a
published model: at 16,384 tokens, 8 of 64 experts and capacity factor 1 the
dispatch tensor alone is 131,072 x 64 x 2,048 x 4 B = 68.7 GB on a 16 GB chip.

Expert parallelism in PCG terms (mirrors the Linear reduction-parallel rule,
linear_ops.py): the input is REPLICATED over the expert axes
(discard_copy_degree = ep) while expert weights are SHARDED on their leading
expert dim; each expert group routes at the full router width and contributes
the combined output of its own experts (tokens routed to remote experts
contribute zero locally), so the op's output carries sum_degree = ep, a
pending partial sum the lowering resolves with psum: the exact Unity
"attribute parallelism" pattern.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

from math import prod as _prod


def expert_capacity(num_tokens: int, num_experts: int, num_select: int, alpha: float) -> int:
    """Static per-expert token capacity (reference GroupBy's alpha arg)."""
    return max(1, math.ceil(alpha * num_select * num_tokens / num_experts))


@dataclass(frozen=True)
class GroupByAttrs:
    """Route tokens to per-expert buffers (legacy Group_by op).

    inputs: data [B, D] float, assign [B, k] int (expert indices from TopK)
    outputs: n_experts tensors [capacity, D] with capacity = ceil(alpha*k*B/E).
    """

    n_experts: int
    alpha: float = 1.0

    def capacity(self, data: TensorShape, assign: TensorShape) -> int:
        return expert_capacity(
            data.dims[0], self.n_experts, assign.dims[-1], self.alpha
        )

    def output_shapes(
        self, data: TensorShape, assign: TensorShape
    ) -> List[TensorShape]:
        assert data.num_dims == 2 and assign.num_dims == 2
        assert data.dims[0] == assign.dims[0]
        assert not assign.dtype.is_floating, "assignment must be integral"
        cap = self.capacity(data, assign)
        return [
            TensorShape((cap, data.dims[1]), data.dtype)
            for _ in range(self.n_experts)
        ]

    def parallel_output_shapes(
        self, data: ParallelTensorShape, assign: ParallelTensorShape
    ) -> List[ParallelTensorShape]:
        """Dispatch positions are a global cumsum over tokens, so the parity
        op requires unsharded inputs (expert parallelism goes through the
        fused ExpertsAttrs instead)."""
        assert all(d == 1 for d in data.shard_degrees()) and data.sum_degree == 1
        assert all(d == 1 for d in assign.shard_degrees())
        outs = self.output_shapes(
            get_reduced_shape(data), get_reduced_shape(assign)
        )
        return [
            lift_to_parallel_with_degrees(
                o, 1, data.discard_copy_degree, (1,) * o.num_dims
            )
            for o in outs
        ]


@dataclass(frozen=True)
class AggregateAttrs:
    """Combine per-expert outputs back into token order, weighted by the
    gate values (legacy Aggregate op; simplified to the data-bearing slots —
    the reference additionally passes duplicate assignment/gradient slots its
    CUDA bwd kernel wants, which autodiff makes unnecessary here).

    inputs: gate_preds [B, k], gate_assign [B, k] int, then n exp_preds
    [capacity, D]; output [B, D].
    """

    n: int

    def output_shape(self, *inputs: TensorShape) -> TensorShape:
        gate_preds, gate_assign = inputs[0], inputs[1]
        exp_preds = inputs[2:]
        assert len(exp_preds) == self.n, (len(exp_preds), self.n)
        assert gate_preds.dims == gate_assign.dims
        d = exp_preds[0].dims[-1]
        return TensorShape((gate_preds.dims[0], d), exp_preds[0].dtype)

    def parallel_output_shape(
        self, *inputs: ParallelTensorShape
    ) -> ParallelTensorShape:
        for s in inputs:
            assert all(d == 1 for d in s.shard_degrees()) and s.sum_degree == 1
        unpar = self.output_shape(*[get_reduced_shape(s) for s in inputs])
        return lift_to_parallel_with_degrees(
            unpar, 1, inputs[0].discard_copy_degree, (1, 1)
        )


@dataclass(frozen=True)
class ExpertsAttrs:
    """Fused MoE FFN: router -> top-k -> dispatch -> expert MLP -> combine
    (+ optional auxiliary router losses).

    weights (slot order), legacy two-matrix form: gate [D, E]; w1 [E, D, H];
    b1 [E, H]; w2 [E, H, out]; b2 [E, out]  (biases present iff use_bias).
    `gated=True` is the three-matrix form of the published sparse decoders,
    `(act(x w1) * (x w3)) w2`, without biases: gate [D, E]; w1 [E, D, H];
    w3 [E, D, H]; w2 [E, H, out].

    capacity_factor: a float bounds every expert at
    ceil(factor * k * N / E) decisions, earlier tokens first, the rest
    dropped; None is dropless (every decision is computed).
    renormalize: divide the k selected router probabilities by their sum
    (True, the GShard/Switch habit and this op's behaviour before the flag
    existed) or use them as they are (False: `norm_topk_prob` false).
    The router and its softmax are computed in float32 in either form.

    scoring: "softmax" (the above) or "sigmoid", the router of the
    bias-balanced sparse decoders: s = sigmoid(logits); the k experts of
    largest s + b are chosen, b the selection bias [E] (`selection_bias`: one
    more weight slot after the gate, a buffer: no gradient reaches it and it
    enters nothing but the choice); their weights are s, divided by their
    sum + 1e-20 when `renormalize`, times `routed_scale`. No auxiliary loss
    is defined for it here.
    shared_hidden_size: a dense expert of that width beside the routed ones,
    applied to every token and added to their sum; it has the routed
    experts' form (activation, gated or not) and no bias: ws1 [D, Hs]
    (gated: ws3 [D, Hs]) and ws2 [Hs, out], the last weight slots.
    shared_gate: the shared expert's output is multiplied, token by token,
    by sigmoid(x w_sg), w_sg [D, 1]: one more weight slot after ws2.
    held_experts: None, or (first, count): this op HOLDS that range of the
    `num_experts` the router chooses among, and its expert tensors have
    `count` as their leading dim. The router keeps its width and its k; the
    op computes its own experts' part of the result for the rows routed to
    them, and what the absent experts would add is left out (the share of a
    layer that expert parallelism gives one chip, without the exchange).
    The shared expert is whole in every share.
    held_window_factor: None, or f >= 1: a held share takes its rows in
    passes of f times what a uniform router would send it, the shard's
    static row buffer (None: a quarter over, `kernels.moe.held_window_rows`).
    Nothing is dropped either way: rows past the first pass take further
    ones, each at a whole pass's cost, so f says how far a router may lean
    towards the share before a step costs a pass more. It changes no value.
    latent_size: None, or L: the routed experts live in a latent space of
    that width. Two more weights, `w_down` [D, L] in front of the dispatch
    and `w_up` [L, out] after the combine (slot order: gate[, selection
    bias], w_down, the expert tensors, w_up, the shared expert's), no bias,
    norm or activation between them; the expert matrices are [E, L, H] and
    [E, H, L]. The router and the shared expert read the D-wide row:
        z = x w_down;  u = sum_{e chosen} weight_e expert_e(z)   (float32)
        out = u w_up + shared(x)
    `w_up` meets the combined rows once, whatever share of the experts the
    op has: it is linear, so the shares' parts still add up.

    outputs: [.., out] and, when lambda_bal > 0 or lambda_z > 0, one
    float32 scalar [1] to be added to the training loss (reference: MoE
    lambda argument, moe.cc), whatever the compute dtype:
        lambda_bal * LB + lambda_z * Z
        LB = E * sum_e f_e P_e,  P_e = mean_n p[n, e],  f_e without gradient
        Z  = mean_n (logsumexp_e logits[n, e])^2     (ST-MoE's z-loss)
    In the gated form f_e = (tokens that chose e) / N, so sum_e f_e = k: the
    published load-balancing loss of the Switch / Mixtral / OLMoE line. The
    legacy form keeps what it always computed, f_e = (decisions routed to e)
    / (N k), k times smaller, so that models trained with it keep their
    lambda. N is the tokens this op sees: under a batch-sharded plan each
    shard takes f_e, P_e and Z over its own tokens and the shards' scalars
    are averaged, which is what data-parallel MoE training does.
    """

    num_experts: int
    num_select: int
    hidden_size: int
    out_channels: Optional[int] = None
    activation: Optional[Activation] = Activation.RELU
    capacity_factor: Optional[float] = 2.0
    use_bias: bool = True
    lambda_bal: float = 0.0
    gated: bool = False
    renormalize: bool = True
    lambda_z: float = 0.0
    scoring: str = "softmax"
    selection_bias: bool = False
    routed_scale: float = 1.0
    shared_hidden_size: int = 0
    held_experts: Optional[Tuple[int, int]] = None
    latent_size: Optional[int] = None
    shared_gate: bool = False
    held_window_factor: Optional[float] = None

    def __post_init__(self):
        assert not self.shared_gate or self.shared_hidden_size, (
            "shared_gate gates the shared expert: it needs one"
        )
        assert not (self.gated and self.use_bias), (
            "the gated expert form has no biases: pass use_bias=False"
        )
        assert self.scoring in ("softmax", "sigmoid"), self.scoring
        assert not (self.scoring == "sigmoid" and self.has_aux), (
            "no auxiliary router loss is defined for sigmoid scoring"
        )
        assert not (self.selection_bias and self.scoring != "sigmoid"), (
            "the selection bias belongs to sigmoid scoring"
        )
        if self.held_experts is not None:
            first, count = self.held_experts
            assert 0 <= first and count > 0 and first + count <= self.num_experts, (
                f"held experts {self.held_experts} outside 0..{self.num_experts}"
            )
            assert self.capacity_factor is None and not self.use_bias, (
                "a held share of the experts is dropless and has no biases"
            )
        assert self.held_window_factor is None or (
            self.held_experts is not None and self.held_window_factor >= 1
        ), "held_window_factor sizes a held share's passes: f >= 1 of one"
        assert self.latent_size is None or self.latent_size > 0, self.latent_size

    @property
    def has_aux(self) -> bool:
        return self.lambda_bal > 0 or self.lambda_z > 0

    @property
    def num_local_experts(self) -> int:
        """Experts whose matrices this op has."""
        return self.held_experts[1] if self.held_experts else self.num_experts

    def weight_roles(self) -> List[str]:
        """What each weight slot is, in slot order: "router" (the gate and
        the selection bias: whole wherever the op runs), "latent" (the
        projections into and out of the experts' latent space: whole
        wherever the op runs, never sharded over the expert axes), "expert"
        (leading dim the local experts) or "shared" (the shared expert's
        matrices)."""
        latent = ["latent"] if self.latent_size else []
        roles = ["router"] * (2 if self.selection_bias else 1) + latent
        roles += ["expert"] * (
            3 if self.gated else (4 if self.use_bias else 2)
        )
        roles += latent
        if self.shared_hidden_size:
            roles += ["shared"] * (3 if self.gated else 2)
        if self.shared_gate:
            roles.append("shared")
        return roles

    @property
    def num_weights(self) -> int:
        return len(self.weight_roles())

    def _out_dim(self, input: TensorShape) -> int:
        return self.out_channels or input.dims[-1]

    def capacity(self, input: TensorShape) -> Optional[int]:
        """Decisions one expert keeps, or None where nothing is dropped."""
        if self.capacity_factor is None:
            return None
        tokens = _prod(input.dims[:-1])
        return expert_capacity(
            tokens, self.num_experts, self.num_select, self.capacity_factor
        )

    def output_shapes(self, input: TensorShape) -> List[TensorShape]:
        out = TensorShape(
            input.dims[:-1] + (self._out_dim(input),), input.dtype
        )
        if self.has_aux:
            return [out, TensorShape((1,), input.dtype)]
        return [out]

    def weight_shapes(self, input: TensorShape) -> List[TensorShape]:
        d = input.dims[-1]
        e, h, o = self.num_local_experts, self.hidden_size, self._out_dim(input)
        # the width the experts read and write: the latent one where there is
        latent = self.latent_size
        ed, eo = latent or d, latent or o
        ws = [TensorShape((d, self.num_experts), input.dtype)]
        if self.selection_bias:
            ws.append(TensorShape((self.num_experts,), input.dtype))
        if latent:
            ws.append(TensorShape((d, latent), input.dtype))
        ws.append(TensorShape((e, ed, h), input.dtype))
        if self.gated:
            ws.append(TensorShape((e, ed, h), input.dtype))
        if self.use_bias:
            ws.append(TensorShape((e, h), input.dtype))
        ws.append(TensorShape((e, h, eo), input.dtype))
        if self.use_bias:
            ws.append(TensorShape((e, eo), input.dtype))
        if latent:
            ws.append(TensorShape((latent, o), input.dtype))
        if self.shared_hidden_size:
            hs = self.shared_hidden_size
            ws += [TensorShape((d, hs), input.dtype)] * (2 if self.gated else 1)
            ws.append(TensorShape((hs, o), input.dtype))
        if self.shared_gate:
            ws.append(TensorShape((d, 1), input.dtype))
        return ws

    # -- parallel (expert parallelism; see module docstring) ---------------

    def parallel_output_shapes(
        self, input: ParallelTensorShape
    ) -> List[ParallelTensorShape]:
        assert input.shard_degrees()[-1] == 1, "feature dim must be unsharded"
        # softmax gating over a pending partial sum is numerically wrong —
        # the input must be fully reduced before expert dispatch
        assert input.sum_degree == 1, "experts input must not be a partial sum"
        ep = input.discard_copy_degree
        assert ep == 1 or (
            self.held_experts is None and not self.shared_hidden_size
        ), (
            "a held share is already one expert-parallel shard, and a shared "
            "expert would be summed once per shard"
        )
        unpars = self.output_shapes(get_reduced_shape(input))
        in_degrees = input.shard_degrees()
        out = lift_to_parallel_with_degrees(unpars[0], ep, 1, in_degrees)
        if self.has_aux:
            # each batch shard gates a different token slice, so its local
            # scalar is a partial value (averaged by the lowering, see the
            # class docstring); across ep the gating is replicated
            batch = _prod(in_degrees)
            aux = lift_to_parallel_with_degrees(unpars[1], batch, ep, (1,))
            return [out, aux]
        return [out]

    def parallel_weight_shapes(
        self, input: ParallelTensorShape
    ) -> List[ParallelTensorShape]:
        ep = input.discard_copy_degree
        batch = _prod(input.shard_degrees())
        unpars = self.weight_shapes(get_reduced_shape(input))
        out: List[ParallelTensorShape] = []
        for role, w in zip(self.weight_roles(), unpars):
            if role != "expert":  # whole everywhere (every shard gates)
                out.append(
                    lift_to_parallel_with_degrees(
                        w, 1, ep * batch, (1,) * w.num_dims
                    )
                )
            else:  # expert tensors: shard the expert dim over the ep axes
                degrees = (ep,) + (1,) * (w.num_dims - 1)
                out.append(
                    lift_to_parallel_with_degrees(w, 1, batch, degrees)
                )
        return out

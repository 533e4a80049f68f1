"""ONE state-space node (`kernels/ssm.state_space_forward`), forward and
backward in one program, compiled at the two `nemotron_h` cells' shapes for a
DESCRIBED TPU v5e (the TPU's compiler is installed here; no chip is attached
and nothing runs), and what XLA made of its two elementwise stages, read from
the compiled program's ENTRY computation, where every instruction's result is
a buffer (what a fusion keeps inside its body is not):

- no float32 tensor of the convolution's width: `conv_silu` converts tap by
  tap inside its fusions and keeps x in the step's dtype for the backward
  (before PR 41 a padded float32 copy of x was written, kept, and four more
  float32 tensors a node came back as the taps' gradients);
- no `dynamic-update-slice` and no `concatenate`: `gated_group_norm` never
  glues its runs back together (eight in-place updates of a float32
  [rows, inner] tensor before);
- at most one float32 [rows, inner] tensor (none today; the bound leaves the
  compiler one): the norm's statistics come from masked sums that XLA fuses
  the gate's product into, and its result is written once in the step's dtype.

The same child also compiles what the `kimi_linear` cell added (PR 43), at its
published shape, 32 heads of 128 over 4,096 positions: ONE gated delta-rule
node (`kernels/kda.gated_delta_forward`) forward and backward, whose
chunk-to-chunk pass must come out as its three Pallas kernels and whose
chunks' operands as theirs (PR 44: the scores forward, rematerialised and
backward, the triangular inverse forward only; PR 54: the inverse's products
with K exp(G) and V, `kda_corrected_fwd`, forward and rematerialised, and the
triangular system's written backward `kda_corrected_bwd` once, both under the
VMEM limit they state; each under the node's `prep` part by the name the
profile will carry; the cell's whole step, compiled for the described chip,
holds 11,843,911,680 bytes with them, 11,830,603,264 at PR 53), and which
must hold no `[heads, positions, 128, 128]` state per position; and the causal
flash kernels on a 256-wide padded key beside a 128-wide value
(`flash_attention_bshf` with the true width's scale), forward and backward.

And what the `lfm2_moe` cell added (PR 47), at its shape, two sequences of
8,192 positions: ONE double-gated short-convolution node
(`kernels/short_conv.gated_short_conv`) forward and backward, which must write
no float32 tensor a position (its chain converts inside its fusions, as
`conv_silu` does) and the projection's row at most twice (forward, and
recomputed in the backward: kept nowhere); and the causal core of 32 heads of
64 over 16 tiles, each head padded to 128 lanes
(`kernels/ops._padded_heads`), forward and backward on the d % 128 causal
tile kernels (the `[b, h, s, d]` rows kernels are refused at this length: 16 MB
of scoped VMEM).

And the held experts of both those cells (PR 48): ONE expert node
(`kernels/moe.experts_forward`, 8 held of the router's width) forward and
backward, which must compile and whose every `gmm` / `tgmm` block must tile
its operand's sides whole: no 1,024-wide block on a 1,536 or a 2,304 side,
which the kernels would run as two or three whole blocks.

And the held rows' way back to their tokens (PR 50), at all four held cells'
shapes: ONE expert node forward and backward, which must lower with the
kernel `held_rows_sum` in it twice, the forward's sum in float32 and the
gradient of the node's input in bf16, each behind its `held_rows_lanes` (two
bf16 columns a word), with
no scatter-add of float32 rows left (a later window's gradient of the input
keeps its bf16 one, in the loop's body), and compile under the VMEM limit the
kernel states (k slots a token of a tile, the accumulator and the out block's
two buffers).

And what the `qwen3_next` cell added (PR 51), at its shape, one sequence of
8,192 positions: ONE gated delta-rule node with one decay a head, 32 value
heads over 16 key heads of 128 | 128, forward and backward, whose
chunk-to-chunk pass must come out as the same three Pallas kernels, and which
must hold no state per position either; since PR 52 its `prep` part must
hold the scalar-decay operands' kernel `gdn_prep_fwd` twice (forward,
rematerialised), its written backward `gdn_prep_bwd` once and
`kda_prep_inverse` once, by the names the profile will carry, with no
float32 [.., 64, 64] buffer of a mask or of Q K^T / K K^T left between
ENTRY instructions (A, the inverse and the triangular system's cotangent dn
are), and since PR 54 `kda_corrected_fwd` twice and `kda_corrected_bwd` once
where XLA's `dot_general`s a value head were (none is left under `prep`),
all four kernels under the VMEM limit they state (the cell's whole step,
compiled for the described chip, holds 13,646,528,000 bytes with them;
13,635,138,048 at PR 52, 13,705,657,344 at PR 51); and ONE output-gated
grouped-query
node of 16 query heads over 2 key/value heads of 256, forward and backward,
whose core must be the three `*_grouped` kernels (forward, delta, backward):
the entries that keep k and v as whole rows under the default scope do not
compile at this length and head size.

And what the `joyai_llm_flash` cell added (PR 53), at its shape, one sequence
of 8,192 positions: ONE latent-attention node with a query rank of 1,536 and a
rotary on the 64 shared key columns, 32 heads of 192 | 128, forward and
backward, whose core must be the wide-key forward that names its own limit
(`flash_fwd_causal_wide_key`: the whole-row forward under the default scope
does not compile at this length, "Scoped allocation with size 48.08M and limit
48.00M exceeded") beside the wide-key entry's own backward and delta kernels;
and the fused loss read TWICE through one head (the main loss and a masked
second one), which must lower and keep no float32 `[8192, 16160]` logit
tensor between ENTRY instructions. `python
tests/test_ssm_node_compiles_for_v5e.py joyai_step <held experts>` compiles
the cell's WHOLE step for the described chip and prints its bytes
(15,103,823,872 at 16 held, 12,162,398,208 at 8, 60-100 s each, PR 53).

And what the `phi4flash` cell added (PR 57), at its published sizes and 4,096
positions (`check_phi4flash`): ONE selective-scan node (5,120 channels, a
16-wide state), whose recurrence must come out as `s6_scan_fwd` and
`s6_scan_bwd` with no state a position between ENTRY instructions; ONE
differential window node, whose core must be the banded causal kernels
(`flash_*_causal_bshf_window`); and the full node with the cross node that
reads its keys and values, on the unbanded ones. `python
tests/test_ssm_node_compiles_for_v5e.py phi4flash_step` compiles that cell's
WHOLE step (12,021,627,392 bytes, 46 s, PR 57).

And what the `mellum2` cell added (PR 60), at its published sizes and 8,192
positions (`check_mellum2`): ONE plain grouped-query window node (32 query
over 4 key/value heads of 128, per-head QK-norm, the default rotary, a
1,024-key window), whose core must be the banded causal kernels
(`flash_*_causal_bshf_window`, the folded form, its keys and values read in
place for the group of 8 since PR 63), and the full node with its YaRN
rotary on the unbanded ones. `python tests/test_ssm_node_compiles_for_v5e.py mellum2_step` compiles
that cell's WHOLE step.

And what PR 58 gave both delta-rule nodes: the heads' norm under its gate
as the kernels `head_norm_gate_fwd` (forward once, never rematerialised) and
`head_norm_gate_bwd` under `<name>/norm`, with no float32 buffer of the
rows' width between ENTRY instructions there (`_gated_norm_part`; the whole
steps hold 13,185,440,768 and 11,808,708,096 bytes with them, 13,646,528,000
and 11,843,911,680 before).

And what PR 59 gave every node with a `conv_silu` (the two state-space
nodes, both delta-rule nodes, the selective-scan node; `_conv_part`): the
convolution as ONE `conv_silu_fwd` and ONE `conv_silu_bwd` custom call, the
forward's only `[rows, .]` operand the projection's row itself (no copy, no
slice and no float32 tensor of x is made for it: the `BlockSpec` starts at
the convolution's first column), the backward's that row and the cotangent
in the pieces its producers left, and the two calls' results no more than y, dx and the taps' partial sums
(ds is no buffer).

And what the `granite-4.0-h-micro` cell added (PR 68), at its published
sizes and 4,096 positions (`check_granite`): ONE state-space node of 64
heads of 64 in ONE group (4,096 columns, more than a program holds) at
chunks of 256, forward and backward, whose scan must come out as the same
three Pallas kernels, each once, in COLUMN BLOCKS (`kernels/ssm._column_blocks`:
4 programs of 1,024 columns a chunk, seen in the states `[1, 4, 16, 1024, 128]`
and in dB's and dC's float32 partials `[1, 4096, 4 * 128]`, which one fusion
each adds up), compiled inside the default 16 MB of scoped VMEM (the kernels
state no limit of their own), with no `[chunks, heads, 256, 256]` decay mask
between ENTRY instructions (the "xla" route's largest tensor) and the
convolution over 4,352 channels as its two kernels. `python
tests/test_ssm_node_compiles_for_v5e.py granite_step` compiles that cell's
WHOLE step, the number its recomputation choice rests on.

A compile that passes is not a chip run and says nothing of speed; the
chip's numbers are in PERF.md. In the pattern of
`test_pair_kernels_compile_for_v5e.py`: every compile in ONE child process
pinned to the CPU, skipped only where the TPU's library is not installed.

    python tests/test_ssm_node_compiles_for_v5e.py            # the JSON the tests read
    python tests/test_ssm_node_compiles_for_v5e.py twotower   # (or super, kimi, qwen3next) the node's ENTRY
        instructions of 4 MB or more, in schedule order, with operand and result
        bytes (7 s; `--root <checkout>` lists another checkout's node)
    python tests/test_ssm_node_compiles_for_v5e.py qwen3next_account [--root <checkout>]
        # that node's compiled program by part and phase: MB made, read, copied
        # (PR 69: parent 5,350 made / 1,363 copied, change 4,063 / 280)
    python tests/test_ssm_node_compiles_for_v5e.py qwen3next_step [--root <checkout>]
        # the cell's WHOLE step: `step_hbm_gb` and its account (65 s)
"""

import functools
import importlib.util
import json
import math
import os
import re
import subprocess
import sys

import pytest

ROWS = 4096
# hidden size and `StateSpaceAttrs` (heads, head size, state, groups, taps,
# chunk) of a node of each cell, batch 1 x 4,096 positions in bf16
SHAPES = {
    "twotower": (2688, (64, 64, 128, 8, 4, 128)),
    "super": (4096, (16, 64, 128, 1, 4, 128)),
}
INVARIANTS = [
    "compiles_with_the_scan_kernels",
    "no_float32_of_the_convolutions_width",
    "no_run_glued_back",
    "at_most_one_float32_rows_by_inner",
    "convolution_is_two_kernels_on_the_projections_row",
]


def _bind_parser():
    """The ENTRY parser has one home, the program's own
    `observability/step_account.py` (PR 64: `entry_instructions`, `shapes_of`,
    `_nbytes`, `_NO_BUFFER`, a listing's row, and `account`, which books the
    same instructions by scope); bound once the checkout whose program is
    compiled is on the path (`--root` of a script run)."""
    from flexflow_tpu.observability import step_account

    for name in ("entry_instructions", "shapes_of", "_nbytes", "_NO_BUFFER",
                 "listing_row", "account"):
        globals()[name] = getattr(step_account, name)


if __name__ != "__main__":
    _bind_parser()


def compiled_node(name):
    """The compiled HLO text of one node's forward and backward at a cell's
    shape, for the described chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from flexflow_tpu.kernels import ssm
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    hidden, sizes = dict(SHAPES, granite=GRANITE_SHAPE)[name]
    attrs = StateSpaceAttrs(*sizes, 1e-5)

    def on_chip(dims):
        return jax.ShapeDtypeStruct(tuple(dims), jnp.bfloat16, sharding=chip)

    u = on_chip((1, ROWS, hidden))
    weights = [
        on_chip(w.dims)
        for w in attrs.weight_shapes(TensorShape((1, ROWS, hidden), DataType.FLOAT))
    ]

    def node(u, weights, cot):
        y, vjp = jax.vjp(
            lambda u, weights: ssm.state_space_forward(attrs, u, weights),
            u, weights,
        )
        return y, vjp(cot)

    return attrs, jax.jit(node).lower(u, weights, u).compile().as_text()


def _conv_part(text, rows, width):
    """"ok" where the node's convolution with SiLU (PR 59) is the kernels
    `conv_silu_fwd` once and `conv_silu_bwd` once; the forward's operands of
    `rows` positions are the projection's row alone, wider than the
    convolution's `width` columns (a `[rows, width]` operand would be a
    slice or a copy of x made for the kernel), in the step's dtype; the
    backward's are that row and the cotangent, whole or in the column pieces
    the caller cut y into (no `[rows, width]` sum of them); and the two
    calls write y, dx and the taps' partial sums and nothing else of the
    rows' size (ds, a float32 x)."""
    entry = entry_instructions(text)
    result_of = {name: result for name, result, *_ in entry}
    calls = {}
    for name, result, opcode, operands, line in entry:
        kernel = re.search(r"/(conv_silu_\w+)/pallas_call", line)
        if opcode == "custom-call" and kernel:
            calls.setdefault(kernel.group(1), []).append((result, operands))
    if sorted(calls) != ["conv_silu_bwd", "conv_silu_fwd"] or any(
        len(found) != 1 for found in calls.values()
    ):
        return f"kernels { {k: len(v) for k, v in calls.items()} }"
    complaints = []
    for kernel, [(result, operands)] in calls.items():
        big = {
            o: shape for o in dict.fromkeys(operands)
            for shape in shapes_of(result_of.get(o, ""))
            if len(shape[1]) >= 2 and shape[1][-2] == rows
        }
        complaints += [
            f"{kernel} reads {o} {dtype}{list(dims)}"
            for o, (dtype, dims) in big.items() if dtype == "f32"
        ]
        # the row is wider than the convolution; the cotangent's pieces (one
        # where the caller cut none) are as wide as it together
        rows_read = [o for o, (_, dims) in big.items() if dims[-1] > width]
        pieces = sum(dims[-1] for _, dims in big.values() if dims[-1] <= width)
        if len(rows_read) != 1 or pieces != width * (kernel == "conv_silu_bwd"):
            complaints.append(
                f"{kernel} reads { {o: list(d) for o, (_, d) in big.items()} }"
            )
    written = sum(_nbytes(result) for [(result, _)] in calls.values())
    # y and dx in bf16, eight float32 sublanes a tap (and the bias) a column
    if written > 2 * rows * width * 2 + 8 * 8 * width * 4:
        complaints.append(f"the two calls write {written} bytes")
    return ", ".join(complaints) or "ok"


def check(name):
    """{invariant: "ok" or what was found} for one cell's node."""
    try:
        attrs, text = compiled_node(name)
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        return dict.fromkeys(INVARIANTS, f"{type(e).__name__}: {e}"[:2000])
    rows = [r for r in entry_instructions(text) if r[2] not in _NO_BUFFER]
    kernels = text.count("tpu_custom_call")
    wide, inner_f32 = [], []
    for row_name, result, *_ in rows:
        for dtype, dims in shapes_of(result):
            if dtype != "f32" or len(dims) < 2 or dims[-2] < ROWS:
                continue
            if dims[-1] == attrs.conv_width:
                wide.append(f"{row_name} f32{list(dims)}")
            if dims[-1] == attrs.inner and dims[-2] == ROWS:
                inner_f32.append(f"{row_name} f32{list(dims)}")
    glued = [
        r[0] for r in rows
        if "dynamic-update-slice" in r[0] or "concatenate" in r[0]
        or r[2] in ("dynamic-update-slice", "concatenate")
    ]
    return {
        "compiles_with_the_scan_kernels": (
            "ok" if kernels >= 3 else f"{kernels} kernels, want 3"
        ),
        "no_float32_of_the_convolutions_width": "ok" if not wide else ", ".join(wide),
        "no_run_glued_back": "ok" if not glued else ", ".join(glued),
        "at_most_one_float32_rows_by_inner": (
            "ok" if len(inner_f32) <= 1 else ", ".join(inner_f32)
        ),
        INVARIANTS[4]: _conv_part(text, ROWS, attrs.conv_width),
    }


# hidden size and `StateSpaceAttrs` of a `granite-4.0-h-micro` node: ONE
# group of 64 heads of 64 (4,096 columns) at chunks of 256
GRANITE_SHAPE = (2048, (64, 64, 128, 1, 4, 256))
GRANITE_INVARIANTS = [
    "granite_scan_is_the_three_kernels_once_each_under_the_default_vmem_scope",
    "granite_group_goes_as_column_blocks",
    "granite_holds_no_chunks_by_heads_decay_mask",
    "granite_convolution_is_two_kernels_on_the_projections_row",
]


def check_granite():
    """{invariant: "ok" or what was found} for the wide-group node."""
    from flexflow_tpu.kernels import ssm

    try:
        attrs, text = compiled_node("granite")
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        return dict.fromkeys(GRANITE_INVARIANTS, f"{type(e).__name__}: {e}"[:2000])
    heads, p, n, groups, _, q = GRANITE_SHAPE[1]
    blocks = ssm._column_blocks(heads * p // groups)
    entry = [r for r in entry_instructions(text) if r[2] not in _NO_BUFFER]
    calls = {}
    for _name, result, opcode, _operands, line in entry:
        kernel = re.search(r"/(ssd_\w+)/pallas_call", line)
        if opcode == "custom-call" and kernel:
            calls.setdefault(kernel.group(1), []).append((result, line))
    counts = {k: len(v) for k, v in calls.items()}
    limits = [
        k for k, found in calls.items()
        if any("vmem_limit_bytes" in line for _, line in found)
    ]
    want = {"ssd_fwd_chunk": 1, "ssd_states_chunk": 1, "ssd_bwd_chunk": 1}
    shapes = {
        k: [dims for result, _ in found for _, dims in shapes_of(result)]
        for k, found in calls.items()
    }
    states = (1, groups * blocks, ROWS // q, heads * p // (groups * blocks), n)
    partial = (1, ROWS, groups * blocks * n)
    in_blocks = (
        blocks > 1 and states in [tuple(d) for d in shapes.get("ssd_states_chunk", [])]
        and [tuple(d) for d in shapes.get("ssd_bwd_chunk", [])].count(partial) == 2
    )
    masks = [
        f"{name} {dtype}{list(dims)}"
        for name, result, *_ in entry for dtype, dims in shapes_of(result)
        if len(dims) >= 2 and tuple(dims[-2:]) == (q, q)
        and math.prod(dims) >= (ROWS // q) * heads * q * q
    ]
    return {
        GRANITE_INVARIANTS[0]: (
            "ok" if counts == want and not limits
            else f"kernels {counts}, limits stated by {limits}"
        ),
        GRANITE_INVARIANTS[1]: (
            "ok" if in_blocks else f"{blocks} blocks, results {shapes}"
        ),
        GRANITE_INVARIANTS[2]: "ok" if not masks else ", ".join(masks),
        GRANITE_INVARIANTS[3]: _conv_part(text, ROWS, attrs.conv_width),
    }


KIMI_INVARIANTS = [
    "kda_node_compiles_with_its_kernels",
    "kda_operand_kernels_are_the_nodes_prep_part",
    "kda_holds_no_state_per_position",
    "kda_scores_read_the_models_layout",
    "kda_triangular_product_kernels_compile_under_the_vmem_limit_they_state",
    "wide_key_flash_compiles_forward_and_backward",
    "kda_gated_norm_is_two_kernels_and_no_float32_of_the_rows_width",
    "kda_convolution_is_two_kernels_on_the_projections_row",
    "kda_triangular_system_crosses_hbm_in_pairs",
]


@functools.lru_cache(maxsize=None)
def _described_chip():
    """(a bf16 `ShapeDtypeStruct` on the described v5e for `dims`). The gates
    ask the backend and nothing runs here: the process says a TPU is there
    (`context.described_tpu`, `__main__`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims: jax.ShapeDtypeStruct(
        tuple(dims), jnp.bfloat16, sharding=chip
    )


KDA_HIDDEN, KDA_HEADS = 2304, 32


def compiled_kda_node():
    """The compiled HLO text of one gated delta-rule node's forward and
    backward at the `kimi_linear` cell's shape, for the described chip."""
    import jax

    from flexflow_tpu.kernels import kda
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    on_chip = _described_chip()
    attrs = GatedDeltaAttrs(KDA_HEADS, 128, 128, 4, 128, 64, 1e-5)
    u = on_chip((1, ROWS, KDA_HIDDEN))
    weights = [
        on_chip(w.dims) for w in attrs.weight_shapes(
            TensorShape((1, ROWS, KDA_HIDDEN), DataType.FLOAT)
        )
    ]

    def scoped(u, weights):
        with jax.named_scope("ff.kda.kda2"):
            return kda.gated_delta_forward(attrs, u, weights)

    def node(u, weights, cot):
        y, vjp = jax.vjp(scoped, u, weights)
        return y, vjp(cot)

    return jax.jit(node).lower(u, weights, u).compile().as_text()


def _corrected_kernels_limit(chunk_heads, in_place=None):
    """"ok" where the limit `kda_corrected_fwd` / `kda_corrected_bwd` carry
    over `chunk_heads` chunks of 64 at heads of 128 | 128 in a bf16 step is
    within the chip's default scope (the node's compile is Mosaic's of both
    under that limit), or the bytes they state; `in_place` = (heads, chunks
    a head) where they read v in the model's layout."""
    from flexflow_tpu.kernels import kda

    limit = kda._CorrectedBlocks(
        chunk_heads, 64, 128, 128, 2, in_place
    ).params.vmem_limit_bytes
    return "ok" if limit <= V5E_SCOPED_VMEM else f"{limit} bytes stated"


_TRIANGULAR_KERNELS = ("kda_prep_inverse", "kda_corrected_fwd", "kda_corrected_bwd")


def _triangular_system_in_pairs(text):
    """"ok" where the triangular system's three kernels (PR 71) have no
    float32 operand or result with 64-lane rows ([.., 64]: half a lane tile,
    twice its bytes in HBM and a fifth of the stream's rate; A, the inverse,
    dn and dA were that) and each takes or gives a float32 [rows, 128] pair
    array: A, X and dA go from kernel to kernel two chunk-heads to a row,
    read from the compiled custom calls' operand layouts and results."""
    seen, half_rows = set(), []
    for name, result, opcode, _, line in entry_instructions(text):
        kernel = re.search(r"/(\w+)/pallas_call", line)
        if opcode != "custom-call" or not kernel:
            continue
        kernel = kernel.group(1)
        if kernel not in _TRIANGULAR_KERNELS:
            continue
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=", line)
        shapes = list(shapes_of(result)) + list(shapes_of(operands.group(1)))
        half_rows += [
            f"{name} f32{list(dims)}" for dtype, dims in shapes
            if dtype == "f32" and dims[-1] == 64
        ]
        if any(dtype == "f32" and len(dims) == 2 and dims[1] == 128
               and dims[0] % 64 == 0 for dtype, dims in shapes):
            seen.add(kernel)
    if not half_rows and seen == set(_TRIANGULAR_KERNELS):
        return "ok"
    return ", ".join(half_rows) or f"pairs on {sorted(seen)} alone"


def _gated_norm_part(text, elements):
    """"ok" where the delta-rule node's `norm` part (PR 58) is the kernels
    `head_norm_gate_fwd` once and `head_norm_gate_bwd` once, nothing
    rematerialised, and no float32 buffer of `elements` (rows x width) or
    more lies under it between ENTRY instructions: the roots, the gate and
    every product stay in VMEM."""
    kernels = sorted(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*/norm/[^"]*?'
        r"(head_norm_gate_\w+)/pallas_call", text
    ))
    wide = [
        f"{name} f32{list(dims)}"
        for name, result, opcode, _, line in entry_instructions(text)
        if opcode not in _NO_BUFFER and "/norm/" in line
        for dtype, dims in shapes_of(result)
        if dtype == "f32" and math.prod(dims) >= elements
    ]
    if kernels == ["head_norm_gate_bwd", "head_norm_gate_fwd"] and not wide:
        return "ok"
    return ", ".join(wide) or f"kernels {kernels}"


def check_kimi():
    """{invariant: "ok" or what was found} for the `kimi_linear` cell's two
    new kernel paths at the published shape."""
    from flexflow_tpu.kernels import flash_attention as fa
    from flexflow_tpu.observability.trace import parse_scope

    found = {}
    heads = KDA_HEADS
    try:
        text = compiled_kda_node()
        # a kernel's name as the profile has it: the chunk-to-chunk pass
        # forward, the states and the backward; the scores' kernel forward
        # and rematerialised, and its backward; the triangular inverse's
        # kernel forward only, because the node's checkpoint keeps the inverse;
        # since PR 54 the products around it (`kda_corrected_fwd`) forward
        # and rematerialised from the kept inverse, and the triangular
        # system's written backward (`kda_corrected_bwd`) once
        # (the pass's rematerialised forward is dead code: its backward reads
        # the operands alone)
        calls = sorted(
            parse_scope(op_name) + (op_name.split("/")[-2],)
            for op_name in re.findall(
                r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text
            )
        )
        want = sorted(
            [("fwd", "kda", "kda2/scan", "kda_fwd_chunk"),
             ("bwd", "kda", "kda2/scan", "kda_states_chunk"),
             ("bwd", "kda", "kda2/scan", "kda_bwd_chunk"),
             ("fwd", "kda", "kda2/prep", "kda_prep_fwd"),
             ("fwd", "kda", "kda2/prep", "kda_prep_inverse"),
             ("fwd", "kda", "kda2/prep", "kda_corrected_fwd"),
             ("bwd", "kda", "kda2/prep", "kda_prep_fwd"),
             ("bwd", "kda", "kda2/prep", "kda_corrected_fwd"),
             ("bwd", "kda", "kda2/prep", "kda_prep_bwd"),
             ("bwd", "kda", "kda2/prep", "kda_corrected_bwd"),
             # since PR 58 the heads' norm under its gate, each way once
             ("fwd", "kda", "kda2/norm", "head_norm_gate_fwd"),
             ("bwd", "kda", "kda2/norm", "head_norm_gate_bwd"),
             # since PR 59 the convolution with SiLU, each way once
             ("fwd", "kda", "kda2/conv", "conv_silu_fwd"),
             ("bwd", "kda", "kda2/conv", "conv_silu_bwd")]
        )
        kernels = sorted(c[3] for c in calls)
        found["kda_node_compiles_with_its_kernels"] = (
            "ok" if kernels == sorted(w[3] for w in want) else f"{kernels}"
        )
        # `kda_ms` and `kda_scan_roofline` read the kernels by these names: one
        # that fell out of the part's scope would leave the roofline reading
        # the `scan` rows alone
        found["kda_operand_kernels_are_the_nodes_prep_part"] = (
            "ok" if calls == want else f"{calls}"
        )
        states = [
            f"{dtype}{list(dims)}" for dtype, dims in shapes_of(text)
            if len(dims) >= 4 and dims[-2:] == (128, 128) and ROWS in dims
        ]
        found["kda_holds_no_state_per_position"] = (
            "ok" if not states else ", ".join(sorted(set(states)))
        )
        # since PR 45 the scores' kernels read q, k and the decay's
        # pre-activation where the convolution and the projection left them
        # and do the gates in VMEM: no float32 tensor a head and position
        # (the log-decays, q and k on their way to a norm) lies under `gates`,
        # forward, recomputed or backward
        rows = entry_instructions(text)
        by_head = [
            f"{name} f32{list(dims)}" for name, result, _, _, line in rows
            if "/gates/" in line
            for dtype, dims in shapes_of(result)
            if dtype == "f32" and math.prod(dims) >= heads * ROWS * 128
        ]
        model_layout = f"bf16[1,{ROWS},{3 * heads * 128}]{{2,1,0}}"
        elsewhere = [
            name for name, _, opcode, _, line in rows
            if opcode == "custom-call" and "kda_prep_fwd" in line
            and line.split("operand_layout_constraints=")[1].count(model_layout) != 2
        ]
        found["kda_scores_read_the_models_layout"] = (
            "ok" if not by_head and not elsewhere
            else ", ".join(by_head + elsewhere)
        )
        found[KIMI_INVARIANTS[4]] = _corrected_kernels_limit(heads * ROWS // 64)
        found[KIMI_INVARIANTS[6]] = _gated_norm_part(text, ROWS * heads * 128)
        found[KIMI_INVARIANTS[7]] = _conv_part(text, ROWS, 3 * heads * 128)
        found[KIMI_INVARIANTS[8]] = _triangular_system_in_pairs(text)
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        complaint = f"{type(e).__name__}: {e}"[:2000]
        for invariant in KIMI_INVARIANTS[:5] + KIMI_INVARIANTS[6:]:
            found.setdefault(invariant, complaint)
    on_chip = _described_chip()
    q = on_chip((1, ROWS, heads * 256))
    v = on_chip((1, ROWS, heads * 128))
    found["wide_key_flash_compiles_forward_and_backward"] = _causal_core_kernels(
        lambda q, k, v: fa.flash_attention_bshf(
            q, k, v, heads, causal=True, scale=192 ** -0.5
        ), q, q, v,
    )
    return found


def _causal_core_kernels(core, q, k, v, cot=None):
    """"ok" where `core(q, k, v)`, forward and backward (the cotangent
    shaped as `cot`, else as v), compiles for the described chip into the
    causal tile schedule's three kernels (forward, delta, backward); else
    what was found."""
    import jax

    def both(q, k, v, cot):
        o, vjp = jax.vjp(core, q, k, v)
        return o, vjp(cot)

    try:
        text = jax.jit(both).lower(
            q, k, v, v if cot is None else cot
        ).compile().as_text()
        kernels = text.count("tpu_custom_call")
        return "ok" if kernels == 3 else f"{kernels} kernels, want 3"
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        return f"{type(e).__name__}: {e}"[:2000]


LFM2_INVARIANTS = [
    "short_conv_node_writes_no_float32_row_and_keeps_no_projection",
    "padded_heads_core_compiles_on_the_causal_tile_kernels",
]
LFM2_SHAPE = (2, 8192, 2048)  # two sequences, hidden 2048; 32 heads of 64


def check_lfm2():
    """{invariant: "ok" or what was found} for the `lfm2_moe` cell's node and
    its attention core."""
    import jax

    from flexflow_tpu.kernels.flash_attention import flash_attention_bshf
    from flexflow_tpu.kernels.ops import _own_columns, _padded_heads
    from flexflow_tpu.kernels.short_conv import gated_short_conv

    found = {}
    on_chip = _described_chip()
    x = on_chip(LFM2_SHAPE)
    hidden = LFM2_SHAPE[-1]
    try:
        def node(x, w_in, w, w_out, cot):
            y, vjp = jax.vjp(gated_short_conv, x, w_in, w, w_out)
            return y, vjp(cot)

        text = jax.jit(node).lower(
            x, on_chip((hidden, 3 * hidden)), on_chip((3, hidden)),
            on_chip((hidden, hidden)), x,
        ).compile().as_text()
        rows = entry_instructions(text)
        per_position = math.prod(LFM2_SHAPE[:2])
        float32 = [
            f"{name}: {result[:60]}" for name, result, opcode, _, _ in rows
            if opcode not in _NO_BUFFER and any(
                dtype == "f32" and math.prod(dims) >= per_position
                for dtype, dims in shapes_of(result)
            )
        ]
        projections = [
            name for name, result, opcode, _, _ in rows
            if opcode not in _NO_BUFFER
            and ("bf16", LFM2_SHAPE[:2] + (3 * hidden,)) in shapes_of(result)
        ]
        found[LFM2_INVARIANTS[0]] = (
            "ok" if not float32 and len(projections) <= 2
            else ", ".join(float32 + projections)
        )
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        found[LFM2_INVARIANTS[0]] = f"{type(e).__name__}: {e}"[:2000]

    def core(q, k, v):
        # as `_mha_forward` calls it: the 8 key/value heads padded where
        # they lie and read in place by their 4 query heads
        q, k, v = (_padded_heads(t, 64) for t in (q, k, v))
        return _own_columns(flash_attention_bshf(
            q, k, v, 32, causal=True, scale=64 ** -0.5, num_kv_heads=8
        ), 64)

    kv = on_chip(LFM2_SHAPE[:2] + (8 * 64,))
    found[LFM2_INVARIANTS[1]] = _causal_core_kernels(core, x, kv, kv, x)
    return found


EXPERTS_INVARIANTS = [
    "lfm2_held_experts_compile_and_no_block_pads_a_side",
    "kimi_held_experts_compile_and_no_block_pads_a_side",
]
# input, then `ExpertsAttrs`: router width, experts a token, expert width,
# the shared expert's width; 8 experts held
EXPERTS_SHAPES = {
    "lfm2": (LFM2_SHAPE, 64, 4, 1536, 0),
    "kimi": ((1, ROWS, KDA_HIDDEN), 256, 8, 1024, 1024),
}
_KERNEL_CALL = re.compile(
    r'@tpu_custom_call\(.*?\\22body\\22: \\22([A-Za-z0-9+/=]+).*? : \((.*)\) -> (.*)'
)


def kernel_blocks(lowered_text):
    """[[(array dims, block dims) of each blocked operand, then of the
    result]] of every Pallas call of a lowered program: the `window_bounds`
    its serialized Mosaic body states, beside the shapes the call is given."""
    import base64

    import jax._src.interpreters.mlir as jax_mlir
    from jaxlib.mlir import ir

    found = []
    for body, operands, result in _KERNEL_CALL.findall(lowered_text):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm()
        blocks = [
            tuple(int(d) for d in bounds.split(", "))
            for bounds in re.findall(r"window_bounds = array<i64: ([0-9, ]+)>", asm)
        ]
        arrays = [
            tuple(int(d) for d in dims.split("x")[:-1])
            for dims in re.findall(r"tensor<([0-9x]+x\w+)>", operands + ", " + result)
        ]
        # the blocked arrays are the call's last: operands, then the result
        found.append(list(zip(arrays[-len(blocks):], blocks)))
    return found


def _experts_node(attrs, shape):
    """(the jitted forward-and-backward of one expert node, its arguments on
    the described chip)."""
    import jax

    from flexflow_tpu.kernels import moe
    from flexflow_tpu.op_attrs.core import get_weight_shapes
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    on_chip = _described_chip()
    x = on_chip(shape)
    weights = [
        on_chip(w.dims)
        for w in get_weight_shapes(attrs, [TensorShape(shape, DataType.FLOAT)])
    ]

    def node(x, weights, cot):
        y, vjp = jax.vjp(
            lambda x, weights: moe.experts_forward(attrs, x, weights)[0],
            x, weights,
        )
        return y, vjp(cot)

    return jax.jit(node), (x, weights, x)


def check_experts():
    """{invariant: "ok" or what was found} for one held-expert node of the
    `lfm2_moe` and the `kimi_linear` cells, forward and backward."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.ops import ExpertsAttrs

    found = {}
    for invariant, (shape, experts, select, width, shared) in zip(
        EXPERTS_INVARIANTS, EXPERTS_SHAPES.values()
    ):
        try:
            attrs = ExpertsAttrs(
                experts, select, width, activation=Activation.SILU,
                capacity_factor=None, use_bias=False, gated=True,
                renormalize=True, scoring="sigmoid", selection_bias=True,
                shared_hidden_size=shared, held_experts=(0, 8),
            )
            node, args = _experts_node(attrs, shape)
            lowered = node.lower(*args)
            calls = kernel_blocks(lowered.as_text())
            lowered.compile()
            padded = [
                f"{block} of {array}" for call in calls for array, block in call
                if any(
                    -(-size // b) * b > -(-size // 128) * 128
                    for size, b in zip(array, block)
                )
            ]
            # a weight gradient's result is the held matrices themselves
            tgmm = [call for call in calls if len(call[-1][0]) == 3]
            found[invariant] = (
                "ok" if tgmm and len(calls) > len(tgmm) and not padded
                else f"{len(calls)} kernels, {len(tgmm)} tgmm; " + ", ".join(padded)
            )
        except Exception as e:  # noqa: BLE001 - the complaint is the result
            found[invariant] = f"{type(e).__name__}: {e}"[:2000]
    return found


HELD_SUM_INVARIANTS = [
    f"{cell}_rows_reach_their_tokens_through_held_rows_sum_and_compile"
    for cell in ("lfm2", "kimi", "twotower", "super")
] + [
    # PR 61: the passes whose row stages stop at the share's last row
    # (`kernels/moe._window_stages`), at both ends of their sizes
    "mellum2_generous_pass_compiles_with_its_row_stages_live",
    "a_pass_clamped_to_one_tile_compiles_with_its_row_stages_live",
]
# input, then `ExpertsAttrs` of a node of each held cell (8 experts held
# unless it says so)
HELD_SUM_NODES = {
    "lfm2": (LFM2_SHAPE, dict(
        num_experts=64, num_select=4, hidden_size=1536, gated=True)),
    "kimi": ((1, ROWS, KDA_HIDDEN), dict(
        num_experts=256, num_select=8, hidden_size=1024, gated=True,
        shared_hidden_size=1024)),
    "twotower": ((1, ROWS, 2688), dict(
        num_experts=128, num_select=6, hidden_size=1856, gated=False,
        shared_hidden_size=3712)),
    "super": ((1, ROWS, 4096), dict(
        num_experts=512, num_select=22, hidden_size=2688, gated=False,
        shared_hidden_size=5376, latent_size=1024)),
    # 16 of 64 held in passes of 2.25 times the uniform share: 36,864 rows
    "mellum2": ((1, 8192, 2304), dict(
        num_experts=64, num_select=8, hidden_size=896, gated=True,
        scoring="softmax", selection_bias=False, held_experts=(0, 16),
        held_window_factor=2.25)),
    # 2 of 64 held, 512 tokens: 64 rows if uniform, a pass of one 128-row tile
    "clamped": ((1, 512, 2048), dict(
        num_experts=64, num_select=4, hidden_size=1536, gated=True,
        held_experts=(0, 2))),
}
LIVE_STAGE_KERNELS = ("experts_hidden_fwd", "experts_hidden_bwd", "experts_cotangent")


def check_held_sums():
    """{invariant: "ok" or what was found} for one expert node of each of
    the four held cells and of the two passes whose row stages are live
    (their kernels in the lowered text, and in no other node's), forward and
    backward."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.ops import ExpertsAttrs

    found = {}
    for invariant, (shape, sizes) in zip(HELD_SUM_INVARIANTS, HELD_SUM_NODES.values()):
        try:
            attrs = ExpertsAttrs(**dict(
                dict(
                    activation=Activation.SILU if sizes["gated"] else Activation.RELU2,
                    capacity_factor=None, use_bias=False, renormalize=True,
                    scoring="sigmoid", selection_bias=True, held_experts=(0, 8),
                ),
                **sizes,
            ))
            node, args = _experts_node(attrs, shape)
            lowered = node.lower(*args)
            text = lowered.as_text()
            sums = text.count('kernel_name = "held_rows_sum"')
            lanes = text.count('kernel_name = "held_rows_lanes"')
            stages = [text.count(f'kernel_name = "{k}"') for k in LIVE_STAGE_KERNELS]
            live = "row_stages_live" in invariant
            # a scatter of rows closes `}) : (tensor<tokens x width>, ...)`;
            # megablox's own scatters are of integers, and the one of bf16
            # rows is a later window's gradient of x2, in the loop's body
            scatters = len(re.findall(
                r"\}\) : \(tensor<\d+x\d+xf32>, tensor<\d+x1xi32>, ", text
            ))
            lowered.compile()
            # JAX lowers a window function once for the straight-line site
            # and once for the loop's: two sites, each kernel two or four times
            found[invariant] = (
                "ok" if sums == lanes >= 2 and not scatters
                and all(stages) == live == any(stages) else
                f"{sums} held_rows_sum, {lanes} held_rows_lanes, "
                f"{scatters} scatters of float32 rows, stage kernels {stages}"
            )
        except Exception as e:  # noqa: BLE001 - the complaint is the result
            found[invariant] = f"{type(e).__name__}: {e}"[:2000]
    return found


QWEN3NEXT_INVARIANTS = [
    "head_decay_node_compiles_with_the_pass_and_inverse_kernels",
    "head_decay_node_keeps_no_state_per_position",
    "gated_d256_attention_compiles_on_the_grouped_kernels",
    "head_decay_operand_kernels_are_the_nodes_prep_part",
    "head_decay_prep_leaves_no_float32_mask_or_score_tile",
    "head_decay_operand_kernels_compile_under_the_vmem_limit_they_state",
    "triangular_product_kernels_compile_under_the_vmem_limit_they_state",
    "head_decay_gated_norm_is_two_kernels_and_no_float32_of_the_rows_width",
    "head_decay_convolution_is_two_kernels_on_the_projections_row",
    "head_decay_recurrence_reads_the_convolutions_pieces_where_they_lie",
    "head_decay_triangular_system_crosses_hbm_in_pairs",
]
# the chip's default for a kernel's scoped VMEM
V5E_SCOPED_VMEM = 16 * 1024 * 1024
QWEN3NEXT_SHAPE = (1, 8192, 2048)


def compiled_gdn_node():
    """(attrs, the compiled HLO text) of one gated delta-rule node with one
    decay a head, forward and backward, at the `qwen3_next` cell's shape for
    the described chip."""
    import jax

    from flexflow_tpu.kernels import kda
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    on_chip = _described_chip()
    attrs = GatedDeltaAttrs(
        32, 128, 128, 4, chunk_size=64, norm_eps=1e-6, num_key_heads=16,
        decay="head",
    )
    x = on_chip(QWEN3NEXT_SHAPE)
    weights = [
        on_chip(w.dims) for w in attrs.weight_shapes(
            TensorShape(QWEN3NEXT_SHAPE, DataType.FLOAT)
        )
    ]

    def scoped(u, weights):
        with jax.named_scope("ff.kda.gdn0"):
            return kda.gated_delta_forward(attrs, u, weights)

    def node(u, weights, cot):
        y, vjp = jax.vjp(scoped, u, weights)
        return y, vjp(cot)

    return attrs, jax.jit(node).lower(x, weights, x).compile().as_text()


def check_qwen3next():
    """{invariant: "ok" or what was found} for the `qwen3_next` cell's two
    new nodes at the published shape."""
    import jax

    from flexflow_tpu.kernels import kda, ops
    from flexflow_tpu.observability.trace import parse_scope
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    found = {}
    on_chip = _described_chip()
    x = on_chip(QWEN3NEXT_SHAPE)
    shape = TensorShape(QWEN3NEXT_SHAPE, DataType.FLOAT)
    try:
        attrs, text = compiled_gdn_node()
        names = sorted(set(re.findall(r"/((?:kda|gdn)_\w+)/pallas_call", text)))
        want = ["gdn_prep_bwd", "gdn_prep_fwd", "kda_bwd_chunk",
                "kda_corrected_bwd", "kda_corrected_fwd", "kda_fwd_chunk",
                "kda_prep_inverse", "kda_states_chunk"]
        found[QWEN3NEXT_INVARIANTS[0]] = (
            "ok" if names == want and text.count("tpu_custom_call") >= 10
            else f"kernels {names}, want {want}"
        )
        # as `check_kimi` reads them: `gdn_ms` and `gdn_scan_roofline` find
        # the kernels by these scopes, the operands' forward once more where
        # the node's checkpoint recomputes it, the inverse kept by it
        calls = sorted(
            parse_scope(op_name) + (op_name.split("/")[-2],)
            for op_name in re.findall(
                r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text
            )
        )
        want = sorted(
            [("fwd", "kda", "gdn0/scan", "kda_fwd_chunk"),
             ("bwd", "kda", "gdn0/scan", "kda_states_chunk"),
             ("bwd", "kda", "gdn0/scan", "kda_bwd_chunk"),
             ("fwd", "kda", "gdn0/prep", "gdn_prep_fwd"),
             ("fwd", "kda", "gdn0/prep", "kda_prep_inverse"),
             ("fwd", "kda", "gdn0/prep", "kda_corrected_fwd"),
             ("bwd", "kda", "gdn0/prep", "gdn_prep_fwd"),
             ("bwd", "kda", "gdn0/prep", "kda_corrected_fwd"),
             ("bwd", "kda", "gdn0/prep", "gdn_prep_bwd"),
             ("bwd", "kda", "gdn0/prep", "kda_corrected_bwd"),
             # since PR 58 the heads' norm under its gate, each way once
             ("fwd", "kda", "gdn0/norm", "head_norm_gate_fwd"),
             ("bwd", "kda", "gdn0/norm", "head_norm_gate_bwd"),
             # since PR 59 the convolution with SiLU, each way once
             ("fwd", "kda", "gdn0/conv", "conv_silu_fwd"),
             ("bwd", "kda", "gdn0/conv", "conv_silu_bwd")]
        )
        found[QWEN3NEXT_INVARIANTS[3]] = "ok" if calls == want else f"{calls}"
        # no float32 [.., 64, 64] or [rows, 64] buffer is left under `prep`
        # at all since PR 71:
        # A, the inverse and dA are kernels' results two chunk-heads to a
        # 128-lane row, Diag(beta) A and dn exist in VMEM alone (a kernel's
        # [.., 64, 64], the kept inverse's `reduce_precision` and ONE `mul`
        # were allowed before); no `dot_general` a value head since PR 54
        # (the inverse's backward and `_corrected`'s cotangents were six),
        # and the masks exp(G_r - G_j) were a `sub` and Q K^T, K K^T
        # products a KEY head ([16, ..] and [16, 2, ..]). What IS there, in
        # pairs, must be a kernel's or the kept inverse's `reduce_precision`
        under_prep = [
            (name, line.split('op_name="')[1].split('"')[0].split("/")[-1], dims)
            for name, result, opcode, _, line in entry_instructions(text)
            if opcode not in _NO_BUFFER and "/prep/" in line
            for dtype, dims in shapes_of(result)
            if dtype == "f32" and (
                (dims[-1] == 64 and (dims[-2] == 64 or len(dims) == 2))
                or dims[-2:] == (64, 128) or (len(dims) == 2 and dims[-1] == 128)
            )
        ]
        strays = [
            f"{name} {made_by} f32{list(dims)}" for name, made_by, dims in under_prep
            if dims[-1] == 64 or made_by not in ("pallas_call", "reduce_precision")
        ]
        found[QWEN3NEXT_INVARIANTS[4]] = (
            "ok" if under_prep and not strays
            else ", ".join(strays) or "no pair array under prep"
        )
        # the compile above is Mosaic's of both kernels under the limit
        # their `CompilerParams` carry, which is the chip's default here
        limit = kda._HeadPrepBlocks(
            1, attrs.key_heads, attrs.num_heads // attrs.key_heads,
            QWEN3NEXT_SHAPE[1], attrs.key_dim, attrs.chunk_size,
        ).params.vmem_limit_bytes
        found[QWEN3NEXT_INVARIANTS[5]] = (
            "ok" if limit <= V5E_SCOPED_VMEM else f"{limit} bytes stated"
        )
        chunks = QWEN3NEXT_SHAPE[1] // attrs.chunk_size
        found[QWEN3NEXT_INVARIANTS[6]] = _corrected_kernels_limit(
            attrs.num_heads * chunks, in_place=(attrs.num_heads, chunks)
        )
        found[QWEN3NEXT_INVARIANTS[7]] = _gated_norm_part(
            text, QWEN3NEXT_SHAPE[1] * attrs.num_heads * attrs.value_dim
        )
        found[QWEN3NEXT_INVARIANTS[8]] = _conv_part(
            text, QWEN3NEXT_SHAPE[1], attrs.conv_width
        )
        # since PR 69 `gdn_prep_*` and `kda_corrected_*` read q, k and v as
        # column blocks of the convolution's pieces and write dq, dk, dv the
        # same way: what `gates` still makes at the size of q ([8192, 2048]
        # bf16) is the `W_ba` matmul's cotangent of u; no heads-first copy,
        # no float32 pass of `_unit`
        glue = [
            f"{name} {line.split('op_name="')[1].split('"')[0].split('/')[-1]}"
            f" {result[:50]}"
            for name, result, opcode, _, line in entry_instructions(text)
            if opcode not in _NO_BUFFER and "/gates/" in line
            and _nbytes(result) >= 2 * QWEN3NEXT_SHAPE[1] * attrs.key_width
            and not line.split('op_name="')[1].startswith(
                "jit(node)/transpose(jvp(ff.kda.gdn0))/gates/dot_general"
            )
        ]
        found[QWEN3NEXT_INVARIANTS[9]] = "ok" if not glue else ", ".join(glue)
        found[QWEN3NEXT_INVARIANTS[10]] = _triangular_system_in_pairs(text)
        per_position = [
            f"{name}: {result[:60]}"
            for name, result, opcode, _, _ in entry_instructions(text)
            if opcode not in _NO_BUFFER and any(
                dims[-3:] == (QWEN3NEXT_SHAPE[1], 128, 128)
                for _, dims in shapes_of(result)
            )
        ]
        found[QWEN3NEXT_INVARIANTS[1]] = (
            "ok" if not per_position else ", ".join(per_position)
        )
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        for invariant in QWEN3NEXT_INVARIANTS[:2] + QWEN3NEXT_INVARIANTS[3:]:
            found.setdefault(invariant, f"{type(e).__name__}: {e}"[:2000])
    try:
        attrs = RingAttentionAttrs(
            2048, 16, kdim=256, vdim=256, causal=True, rope_theta=1e7,
            rotary_dim=64, qk_norm_eps=1e-6, qk_norm_per_head=True,
            qk_norm_zero_centered=True, num_kv_heads=2, output_gate=True,
        )
        flat = on_chip(attrs.weights_shape(shape, shape, shape).dims)
        gain = on_chip((256,))

        def scoped(x, flat, w_q, w_k):
            with jax.named_scope("ff.ring_attention.attn3"):
                return ops._mha_forward(
                    attrs, x, x, x, flat, causal=True, qk_gains=[w_q, w_k]
                )

        def node(x, flat, w_q, w_k, cot):
            y, vjp = jax.vjp(scoped, x, flat, w_q, w_k)
            return y, vjp(cot)

        text = jax.jit(node).lower(x, flat, gain, gain, x).compile().as_text()
        names = sorted(set(re.findall(r"/(flash_\w+)/pallas_call", text)))
        want = ["flash_bwd_causal_grouped", "flash_delta_grouped",
                "flash_fwd_causal_grouped"]
        found[QWEN3NEXT_INVARIANTS[2]] = (
            "ok" if names == want else f"kernels {names}, want {want}"
        )
    except Exception as e:  # noqa: BLE001
        found[QWEN3NEXT_INVARIANTS[2]] = f"{type(e).__name__}: {e}"[:2000]
    return found


JOYAI_INVARIANTS = [
    "latent_node_with_rotary_compiles_on_the_long_row_forward",
    "fused_loss_read_twice_keeps_no_float32_logits",
]
JOYAI_SHAPE = (1, 8192, 2048)
JOYAI_VOCAB_ROWS = 16160


def check_joyai():
    """{invariant: "ok" or what was found} for the `joyai_llm_flash` cell's
    new node and its second loss at the published shape."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import loss, ops
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import (
        LabelCrossEntropyAttrs,
        RingAttentionAttrs,
    )
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    found = {}
    on_chip = _described_chip()
    x = on_chip(JOYAI_SHAPE)
    shape = TensorShape(JOYAI_SHAPE, DataType.FLOAT)
    try:
        attrs = RingAttentionAttrs(
            2048, 32, kdim=192, vdim=128, causal=True, rope_theta=3.2e7,
            rope_interleaved=True, kv_latent_rank=512, shared_key_dim=64,
            kv_latent_norm_eps=1e-6, q_latent_rank=1536,
            q_latent_norm_eps=1e-6,
        )
        flat = on_chip(attrs.weights_shape(shape, shape, shape).dims)
        g_kv, g_q = on_chip((512,)), on_chip((1536,))

        def scoped(x, flat, g_kv, g_q):
            with jax.named_scope("ff.ring_attention.mla1"):
                return ops._latent_mha_forward(
                    attrs, x, flat, g_kv, True, q_gain=g_q
                )

        def node(x, flat, g_kv, g_q, cot):
            y, vjp = jax.vjp(scoped, x, flat, g_kv, g_q)
            return y, vjp(cot)

        text = jax.jit(node).lower(x, flat, g_kv, g_q, x).compile().as_text()
        names = sorted(set(re.findall(r"/(flash_\w+)/pallas_call", text)))
        want = ["flash_bwd_causal_bshf", "flash_delta_bshf",
                "flash_fwd_causal_wide_key"]
        found[JOYAI_INVARIANTS[0]] = (
            "ok" if names == want else f"kernels {names}, want {want}"
        )
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        found[JOYAI_INVARIANTS[0]] = f"{type(e).__name__}: {e}"[:2000]
    try:
        rows, vocab = JOYAI_SHAPE[1], JOYAI_VOCAB_ROWS
        head = on_chip((JOYAI_SHAPE[2], vocab))
        labels = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=x.sharding)
        second = LabelCrossEntropyAttrs(weight=0.3)

        def both(h, z, head, y, y2):
            def total(h, z, head):
                return loss._fused_scce(h @ head, y) + loss.label_cross_entropy(
                    second, z @ head, y2
                )[0]

            return jax.value_and_grad(total, argnums=(0, 1, 2))(h, z, head)

        text = jax.jit(both).lower(x, x, head, labels, labels).compile().as_text()
        whole = [
            f"{name}: {result[:60]}"
            for name, result, opcode, _, _ in entry_instructions(text)
            if opcode not in _NO_BUFFER and any(
                dtype == "f32" and math.prod(dims) >= rows * vocab
                for dtype, dims in shapes_of(result)
            )
        ]
        found[JOYAI_INVARIANTS[1]] = "ok" if not whole else ", ".join(whole)
    except Exception as e:  # noqa: BLE001
        found[JOYAI_INVARIANTS[1]] = f"{type(e).__name__}: {e}"[:2000]
    return found


PHI4FLASH_INVARIANTS = [
    "scan_node_compiles_with_its_two_kernels_and_no_state_a_position",
    "window_node_compiles_on_the_banded_kernels",
    "full_and_cross_nodes_compile_on_the_causal_kernels",
    "scan_nodes_convolution_is_two_kernels_on_the_projections_row",
]
PHI4FLASH_SHAPE = (1, 4096, 2560)


def check_phi4flash():
    """{invariant: "ok" or what was found} for what the `phi4flash` cell
    added, at the published sizes and 4,096 positions, forward and backward:
    ONE selective-scan node (5,120 channels, a 16-wide state, a step rank of
    160), whose recurrence must come out as `s6_scan_fwd` (twice: the
    backward's own call is `s6_scan_bwd`, and the forward runs once) with no
    [positions, channels, state] tensor between ENTRY instructions; ONE
    window node (40 query heads of 64 in pairs over 128-wide values, a
    512-key window), whose core must be the banded kernels by the names the
    profile will carry; and the full node with the cross node that reads its
    keys and values, whose cores must be the unbanded ones."""
    import jax

    from flexflow_tpu.kernels import ops, selective_scan
    from flexflow_tpu.op_attrs.core import get_weight_shapes
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs, SelectiveScanAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    found = {}
    on_chip = _described_chip()
    x = on_chip(PHI4FLASH_SHAPE)
    shape = TensorShape(PHI4FLASH_SHAPE, DataType.FLOAT)
    rows = PHI4FLASH_SHAPE[1]

    def compiled(node, *operands):
        def both(*operands):
            outs, vjp = jax.vjp(node, *operands)
            return outs, vjp(outs)

        return jax.jit(both).lower(*operands).compile().as_text()

    try:
        attrs = SelectiveScanAttrs(5120, 16, 160, 4, memory_output=True)
        ws = [on_chip(w.dims) for w in get_weight_shapes(attrs, [shape])]

        def scan_node(x, *ws):
            with jax.named_scope("ff.s6.s16"):
                return selective_scan.selective_scan_forward(attrs, x, ws)

        text = compiled(scan_node, x, *ws)
        names = sorted(re.findall(r"/(s6_\w+)/pallas_call", text))
        states = [
            f"{name}: {result[:60]}"
            for name, result, opcode, _, _ in entry_instructions(text)
            if opcode not in _NO_BUFFER and any(
                math.prod(dims) >= rows * 5120 * 16 for _, dims in shapes_of(result)
            )
        ]
        want = ["s6_scan_bwd", "s6_scan_fwd"]
        found[PHI4FLASH_INVARIANTS[0]] = (
            "ok" if sorted(set(names)) == want and not states
            else f"kernels {names}, want {want}; states {states}"
        )
        found[PHI4FLASH_INVARIANTS[3]] = _conv_part(text, rows, 5120)
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        for invariant in (PHI4FLASH_INVARIANTS[0], PHI4FLASH_INVARIANTS[3]):
            found.setdefault(invariant, f"{type(e).__name__}: {e}"[:2000])

    def differential(kind):
        return RingAttentionAttrs(
            2560, 40, 64, 64, bias=True, num_kv_heads=20, differential=True,
            lambda_init=0.5, window=512 if kind == "window" else None,
            kv_outputs=kind == "full", external_kv=kind == "cross", causal=True,
        )

    def kernels_of(text):
        return sorted(set(re.findall(r"/(flash_\w+)/pallas_call", text)))

    try:
        attrs = differential("window")
        ws = [on_chip(w.dims) for w in get_weight_shapes(attrs, [shape] * 3)]

        def window_node(x, *ws):
            with jax.named_scope("ff.ring_attention.attn1"):
                return ops._differential_forward(attrs, x, x, x, list(ws))

        names = kernels_of(compiled(window_node, x, *ws))
        want = ["flash_bwd_causal_bshf_window", "flash_delta_bshf",
                "flash_fwd_causal_bshf_window"]
        found[PHI4FLASH_INVARIANTS[1]] = (
            "ok" if names == want else f"kernels {names}, want {want}"
        )
    except Exception as e:  # noqa: BLE001
        found[PHI4FLASH_INVARIANTS[1]] = f"{type(e).__name__}: {e}"[:2000]
    try:
        full, cross = differential("full"), differential("cross")
        kv = TensorShape((1, rows, 1280), DataType.FLOAT)
        full_ws = [on_chip(w.dims) for w in get_weight_shapes(full, [shape] * 3)]
        cross_ws = [
            on_chip(w.dims) for w in get_weight_shapes(cross, [shape, kv, kv])
        ]

        def pair(x, full_ws, cross_ws):
            with jax.named_scope("ff.ring_attention.attn17"):
                out, keys, values = ops._differential_forward(
                    full, x, x, x, list(full_ws)
                )
            with jax.named_scope("ff.ring_attention.attn19"):
                return ops._differential_forward(
                    cross, out, keys, values, list(cross_ws)
                )[0]

        names = kernels_of(compiled(pair, x, full_ws, cross_ws))
        want = ["flash_bwd_causal_bshf", "flash_delta_bshf",
                "flash_fwd_causal_bshf"]
        found[PHI4FLASH_INVARIANTS[2]] = (
            "ok" if names == want else f"kernels {names}, want {want}"
        )
    except Exception as e:  # noqa: BLE001
        found[PHI4FLASH_INVARIANTS[2]] = f"{type(e).__name__}: {e}"[:2000]
    return found


MELLUM2_INVARIANTS = [
    "window_node_compiles_on_the_banded_kernels",
    "full_node_with_yarn_compiles_on_the_causal_kernels",
    "a_group_read_in_place_compiles_with_batch_rows_folded",
    "window_nodes_account_books_s1_and_copies_to_its_scope",
    "window_node_holds_no_float32_query_row",
]
MELLUM2_SHAPE = (1, 8192, 2304)
# PR 66: the plain attention nodes whose norm and rotary are ONE Pallas pass
# each way (`kernels/norm_rotary`), whole, forward and backward, at their
# cells' shapes: (batch, positions, attrs). Mellum2's two nodes are compiled
# by `check_mellum2`, Qwen3-Next's (the plain form: 64 of a head's 256
# columns turned) by `check_qwen3next`
BETWEEN_NODES = {
    "ouro_rotary_alone_on_16_heads_of_128": (1, 8192, dict(
        embed_dim=2048, num_heads=16, kdim=128, vdim=128, rope_theta=1e6)),
    "olmoe_row_norm_of_16_heads_of_128": (4, 4096, dict(
        embed_dim=2048, num_heads=16, kdim=128, vdim=128, rope_theta=1e4,
        qk_norm_eps=1e-5)),
    "lfm2_per_head_norm_of_32_over_8_heads_of_64": (2, 8192, dict(
        embed_dim=2048, num_heads=32, kdim=64, vdim=64, rope_theta=1e6,
        qk_norm_eps=1e-5, qk_norm_per_head=True, num_kv_heads=8)),
}
BETWEEN_FALLBACK = "qwen3next_node_keeps_the_plain_form_and_says_why"
ACCOUNT_CELL = "bertlarge_s128_1chip"
ACCOUNT_INVARIANTS = ["walk_over_xla_of_a_whole_one_chip_cell"]


def _window_node_account(compiled):
    """"ok" where the account of the window node (PR 64:
    `observability/step_account.account`) books what XLA laid in `S(1)` to
    `ff.ring_attention.attn0`, forward and backward, its walk lands on
    XLA's own peak, and (PR 66: the norm and the rotary are the kernels
    `norm_rotary_*`) the copies under the node outside its core are less
    than one `[8192, 4096]` bf16 row each way (the plain form relaid q and k
    in float32 for the rotary, PERF.md PR 63: `copy` under the attention
    scopes; the core keeps the group sum's relayouts of dk and dv, ROADMAP
    S3 (vii))."""
    if compiled is None:
        return "the window node did not compile"
    try:
        found = account(compiled)
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        return f"{type(e).__name__}: {e}"[:2000]
    node = [
        r for r in found["rows"]
        if r["kind"] == "ring_attention" and r["name"].startswith("attn0")
    ]
    complaints = []
    for phase in ("fwd", "bwd"):
        rows = [r for r in node if r["phase"] == phase]
        copies = sum(
            r["families"].get("copy", {"written_bytes": 0})["written_bytes"]
            for r in rows if r["name"] == "attn0"
        )
        if not sum(r["s1_bytes"] for r in rows):
            complaints.append(f"no S(1) byte under {phase} ring_attention attn0")
        if copies >= 8192 * 4096 * 2:
            complaints.append(f"{phase} copies under the node: {copies} bytes")
    unattributed = sum(
        r["written_bytes"] for r in found["rows"] if r["phase"] == "unattributed"
    )
    made = sum(r["written_bytes"] for r in found["rows"])
    if unattributed > 0.1 * made:
        complaints.append(f"{unattributed} of {made} bytes made carry no scope")
    ratio = found["walk"]["walk_over_xla"]
    if not 0.95 <= ratio <= 1.05:
        complaints.append(f"walk_over_xla {ratio:.4f}")
    return ", ".join(complaints) or "ok"


def _float32_rows_under(text, scope, rows):
    """"ok" where no ENTRY instruction under `scope` makes a float32 buffer
    as wide as one of `rows` (`[.., positions, heads * d]`: q's or k's; the
    plain form's norm and rotary left nine such results under Mellum2's
    window node, 134 MB each)."""
    wide = [
        name for name, result, opcode, _, line in entry_instructions(text)
        if opcode not in _NO_BUFFER and scope in line and any(
            dtype == "f32" and dims[-2:] in rows
            for dtype, dims in shapes_of(result)
        )
    ]
    return f"float32 rows under {scope}: {wide[:4]}" if wide else "ok"


def check_between():
    """{node: "ok" or what was found}: each plain attention node of
    `BETWEEN_NODES` whole, forward and backward, compiles for the described
    chip with the pass's two kernels twice (q and k) by the names the
    profile will carry, the counter says `pallas`, and no float32 buffer as
    wide as q's or k's row lies under the node's scope; and Qwen3-Next's
    node keeps the plain form and says why."""
    import jax

    from flexflow_tpu.kernels import context, ops
    from flexflow_tpu.observability import trace
    from flexflow_tpu.op_attrs.core import get_weight_shapes
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    on_chip = _described_chip()
    scope = "ff.ring_attention.attn0"
    found = {}
    for name, (b, s, fields) in BETWEEN_NODES.items():
        try:
            attrs = RingAttentionAttrs(causal=True, **fields)
            dims = (b, s, attrs.embed_dim)
            shape = TensorShape(dims, DataType.FLOAT)
            x = on_chip(dims)
            ws = [on_chip(w.dims) for w in get_weight_shapes(attrs, [shape] * 3)]

            def node(x, *ws, attrs=attrs):
                with jax.named_scope(scope):
                    return ops._mha_forward(
                        attrs, x, x, x, ws[0], causal=True,
                        qk_gains=ws[1:] or None,
                    )

            def both(*operands, node=node):
                out, vjp = jax.vjp(node, *operands)
                return out, vjp(out)

            with context.lowering_node(scope):
                text = jax.jit(both).lower(x, *ws).compile().as_text()
            said = trace.kernel_choices("between_passes").get(scope)
            names = sorted(re.findall(r"/(norm_rotary_\w+)/pallas_call", text))
            # (the same kernel's call sites may share one custom call's name)
            want = {"norm_rotary_bwd", "norm_rotary_fwd"}
            d = attrs.q_proj_size
            rows = {(s, attrs.num_heads * d), (s, attrs.kv_heads * d)}
            found[name] = (
                f"the counter says {said}" if said != "pallas"
                else f"kernels {names}" if set(names) != want
                else _float32_rows_under(text, scope, rows)
            )
        except Exception as e:  # noqa: BLE001 - the complaint is the result
            found[name] = f"{type(e).__name__}: {e}"[:2000]
    gated = RingAttentionAttrs(
        embed_dim=2048, num_heads=16, kdim=256, vdim=256, causal=True,
        rope_theta=1e7, rotary_dim=64, qk_norm_eps=1e-6, qk_norm_per_head=True,
        qk_norm_zero_centered=True, num_kv_heads=2, output_gate=True,
    )
    said, _ = ops.between_form(gated, "fused_row", 8192)
    found[BETWEEN_FALLBACK] = "ok" if said == "xla (rotary_dim)" else f"{said}"
    return found


def check_account(root):
    """{invariant: "ok" or what was found}: the account of ONE whole one-chip
    cell's step (`ACCOUNT_CELL`, the cheapest to compile, 24 layers) against
    XLA's own totals of the same executable."""
    try:
        found = cell_step_bytes(ACCOUNT_CELL, root)
    except Exception as e:  # noqa: BLE001 - the complaint is the result
        return {ACCOUNT_INVARIANTS[0]: f"{type(e).__name__}: {e}"[:2000]}
    memory, walk = found["account"]["memory"], found["account"]["walk"]
    complaints = []
    if memory["total"] != round(found["step_hbm_gb"] * 1e9):
        complaints.append(f"total {memory['total']}, step_hbm_gb {found['step_hbm_gb']}")
    if not memory["xla_peak"] or memory["xla_peak"] > memory["total"]:
        complaints.append(f"xla_peak {memory['xla_peak']} of total {memory['total']}")
    if not 0.90 <= walk["walk_over_xla"] <= 1.10:
        complaints.append(f"walk_over_xla {walk['walk_over_xla']:.4f}")
    held = {r["phase"]: r["bytes"] for r in walk["held_at_peak"][:1]}
    if held != {"arguments": memory["arguments"]}:
        complaints.append(f"the largest holder at the peak is {held}")
    if not sum(r["bytes"] for r in walk["kept_for_backward"]):
        complaints.append("nothing is kept for the backward pass")
    return {ACCOUNT_INVARIANTS[0]: ", ".join(complaints) or "ok"}


def check_mellum2():
    """{invariant: "ok" or what was found} for what the `mellum2` cell added,
    at the published sizes and 8,192 positions, forward and backward: ONE
    plain grouped-query node (32 query over 4 key/value heads of 128, a
    per-head QK-norm, the default rotary) under a 1,024-key window, whose
    core must be the banded kernels by the names the profile will carry, and
    the full node with its YaRN rotary, whose core must be the unbanded
    ones; and the group read in place where batch rows fold."""
    import jax

    from flexflow_tpu.kernels import ops
    from flexflow_tpu.kernels.flash_attention import (
        causal_plan,
        flash_attention_bshf,
    )
    from flexflow_tpu.op_attrs.core import get_weight_shapes
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs, YarnScaling
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    on_chip = _described_chip()
    x = on_chip(MELLUM2_SHAPE)
    shape = TensorShape(MELLUM2_SHAPE, DataType.FLOAT)
    banded = ["flash_bwd_causal_bshf_window", "flash_delta_bshf",
              "flash_fwd_causal_bshf_window"]
    causal = ["flash_bwd_causal_bshf", "flash_delta_bshf",
              "flash_fwd_causal_bshf"]
    found, window_node = {}, None
    for invariant, want, extra in (
        (MELLUM2_INVARIANTS[0], banded, dict(window=1024)),
        (MELLUM2_INVARIANTS[1], causal, dict(
            rope_scaling=YarnScaling(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
        )),
    ):
        try:
            attrs = RingAttentionAttrs(
                2304, 32, 128, 128, rope_theta=500000.0, qk_norm_eps=1e-6,
                qk_norm_per_head=True, num_kv_heads=4, causal=True, **extra,
            )
            ws = [on_chip(w.dims) for w in get_weight_shapes(attrs, [shape] * 3)]

            def node(x, *ws, attrs=attrs):
                with jax.named_scope("ff.ring_attention.attn0"):
                    return ops._mha_forward(
                        attrs, x, x, x, ws[0], causal=True, qk_gains=ws[1:]
                    )

            def both(*operands, node=node):
                out, vjp = jax.vjp(node, *operands)
                return out, vjp(out)

            compiled = jax.jit(both).lower(x, *ws).compile()
            window_node = window_node or compiled
            text = compiled.as_text()
            names = sorted(set(re.findall(r"/(flash_\w+)/pallas_call", text)))
            found[invariant] = (
                "ok" if names == want else f"kernels {names}, want {want}"
            )
        except Exception as e:  # noqa: BLE001 - the complaint is the result
            found[invariant] = f"{type(e).__name__}: {e}"[:2000]
    found[MELLUM2_INVARIANTS[3]] = _window_node_account(window_node)
    found[MELLUM2_INVARIANTS[4]] = (
        "the window node did not compile" if window_node is None
        else _float32_rows_under(
            window_node.as_text(), "ff.ring_attention.attn0",
            {(8192, 32 * 128), (8192, 4 * 128)},
        )
    )

    # no cell has a grouped node with more than one sequence a chip: two
    # sequences of 4,096 positions fold into one forward program, whose
    # `[2, 4096, 128]` key and value blocks are the group's shared head
    def core(q, k, v):
        return flash_attention_bshf(
            q, k, v, 32, causal=True, num_kv_heads=4, window=1024
        )

    q, kv = on_chip((2, 4096, 32 * 128)), on_chip((2, 4096, 4 * 128))
    plan = causal_plan(2, 4096, 32, 4, 128, 128, 2, window=1024)
    found[MELLUM2_INVARIANTS[2]] = (
        _causal_core_kernels(core, q, kv, kv, q)
        if (plan.fold, plan.group) == (2, 8) else f"the plan says {plan}"
    )
    return found


def joyai_step_bytes(held, root):
    """The `joyai_llm_flash` cell's WHOLE step compiled for the described
    chip with `held` experts a node (`cell_step_bytes`)."""
    return dict(
        cell_step_bytes(
            "joyaiflash48b_s8192_1chip", root, n_routed_experts=held
        ),
        held_experts=held,
    )


def cell_step_bytes(cell, root, **overrides):
    """A one-chip cell's WHOLE step compiled for the described chip, its
    configuration's keys replaced by `overrides`: XLA's bytes as `run.py`
    adds them (`step_hbm_gb`), the parameters, the Pallas kernels' names. The
    state is never allocated (`jax.eval_shape` of the instance's
    `initialize`)."""
    import time

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run as bench

    from flexflow_tpu.analysis import lowering
    from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.local_execution import training_backing as tb

    on_chip = _described_chip()
    chip = on_chip((1,)).sharding
    spec = bench.load_cell(os.path.join(root, "BENCHMARK.json"), cell)
    config = dict(spec["config"], **overrides)
    job, training = spec["job"], spec["config"]["training"]
    module = bench.load_module(spec["module_path"])
    graph, logits = module.build(config, job["batch_per_chip"], job["seq"])
    model = FFModel.from_computation_graph(
        graph, logits,
        FFConfig(batch_size=job["batch_per_chip"], seed=1, print_freq=0,
                 max_devices=1),
    )
    initialize = tb.ModelTrainingInstance.initialize
    tb.ModelTrainingInstance.initialize = (
        lambda self, seed=0: jax.eval_shape(lambda: initialize(self, seed))
    )
    t0 = time.time()
    model.compile(
        AdamOptimizer(
            alpha=training["alpha"], beta1=training["beta1"],
            beta2=training["beta2"], epsilon=training["epsilon"],
            weight_decay=training["weight_decay"],
        ),
        training["loss"], compute_dtype=jnp.dtype(training["compute_dtype"]),
    )
    example = lowering.step_example_args_cg(model.instance, model.loss_attrs)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        (model.params, model.opt_state, *example),
    )
    compiled = model.instance.compiled_step().lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        "cell": cell,
        "parameters": sum(
            math.prod(v.shape) for v in jax.tree_util.tree_leaves(model.params)
        ),
        "step_hbm_gb": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        ) / 1e9,
        "temp_bytes": mem.temp_size_in_bytes,
        "kernels": sorted(set(re.findall(r"/(\w+)/pallas_call", text))),
        "seconds": round(time.time() - t0, 1),
        # the same program by scope: rows, the walk's peak and who holds it
        "account": account(compiled),
    }


def gdn_node_account():
    """The Qwen3-Next delta-rule node's compiled program booked by part and
    phase (`observability/step_account.account_of_text`), MB made and read
    and the `copy` family's share of what is made: `qwen3next_account
    [--root <the parent's checkout>]` prints one side of the comparison."""
    from flexflow_tpu.observability.step_account import account_of_text

    rows = {}
    for row in account_of_text(compiled_gdn_node()[1])["rows"]:
        part = row["name"].partition("/")[2] or "(projections)"
        key = f"{part} {row['phase']}" if row["kind"] == "kda" else row["phase"]
        to = rows.setdefault(key, {"instructions": 0, "made_mb": 0.0,
                                   "read_mb": 0.0, "copies_made_mb": 0.0})
        to["instructions"] += row["instructions"]
        to["made_mb"] += row["written_bytes"] / 1e6
        to["read_mb"] += row["read_bytes"] / 1e6
        to["copies_made_mb"] += sum(
            f["written_bytes"] for name, f in row["families"].items()
            if name.startswith("copy")
        ) / 1e6
    total = {k: round(sum(r[k] for r in rows.values()), 1)
             for k in ("instructions", "made_mb", "read_mb", "copies_made_mb")}
    return {
        "rows": {k: {n: round(v, 1) for n, v in r.items()}
                 for k, r in sorted(rows.items())},
        "total": total,
    }


def listing(name, least=4e6):
    """The node's ENTRY instructions that move `least` bytes or more."""
    if name == "kimi":
        text = compiled_kda_node()
    elif name == "qwen3next":
        text = compiled_gdn_node()[1]
    else:
        text = compiled_node(name)[1]
    rows = entry_instructions(text)
    result_of = {r[0]: r[1] for r in rows}
    lines, moved = [], 0
    for row_name, result, opcode, operands, line in rows:
        if opcode in _NO_BUFFER or opcode.endswith("-done"):
            continue  # an asynchronous copy is counted at its start
        read = sum(_nbytes(result_of.get(o, "")) for o in operands)
        written = _nbytes(result)
        moved += read + written
        if read + written >= least:
            lines.append(
                listing_row(row_name, result, opcode, line, read, written)
            )
    lines.append(
        f"operands and results of every ENTRY instruction: {moved / 1e6:.1f} MB "
        "(an operand is counted whole, also where a fusion reads a slice of it)"
    )
    return "\n".join(lines)


@pytest.fixture(scope="module")
def compiled():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU's compiler (libtpu) is not installed here")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
        ALLOW_MULTIPLE_LIBTPU_LOAD="1",
    )
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, timeout=1200,
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert child.returncode == 0, child.stderr[-4000:]
    return json.loads(child.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("invariant", INVARIANTS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_node_compiled_for_the_described_chip(compiled, shape, invariant):
    assert compiled[shape][invariant] == "ok"


@pytest.mark.parametrize("invariant", KIMI_INVARIANTS)
def test_kimi_kernels_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["kimi"][invariant] == "ok"


@pytest.mark.parametrize("invariant", LFM2_INVARIANTS)
def test_lfm2_node_and_core_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["lfm2"][invariant] == "ok"


@pytest.mark.parametrize("invariant", EXPERTS_INVARIANTS)
def test_held_experts_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["experts"][invariant] == "ok"


@pytest.mark.parametrize("invariant", HELD_SUM_INVARIANTS)
def test_held_rows_sum_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["held_sums"][invariant] == "ok"


@pytest.mark.parametrize("invariant", QWEN3NEXT_INVARIANTS)
def test_qwen3next_nodes_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["qwen3next"][invariant] == "ok"


@pytest.mark.parametrize("invariant", JOYAI_INVARIANTS)
def test_joyai_node_and_second_loss_compiled_for_the_described_chip(
    compiled, invariant
):
    assert compiled["joyai"][invariant] == "ok"


@pytest.mark.parametrize("invariant", PHI4FLASH_INVARIANTS)
def test_phi4flash_nodes_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["phi4flash"][invariant] == "ok"


@pytest.mark.parametrize("invariant", MELLUM2_INVARIANTS)
def test_mellum2_nodes_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["mellum2"][invariant] == "ok"


@pytest.mark.parametrize("invariant", GRANITE_INVARIANTS)
def test_granite_node_compiled_for_the_described_chip(compiled, invariant):
    assert compiled["granite"][invariant] == "ok"


@pytest.mark.parametrize("node", list(BETWEEN_NODES) + [BETWEEN_FALLBACK])
def test_norm_and_rotary_pass_compiled_for_the_described_chip(compiled, node):
    assert compiled["between"][node] == "ok"


@pytest.mark.parametrize("invariant", ACCOUNT_INVARIANTS)
def test_account_of_a_whole_cell_compiled_for_the_described_chip(
    compiled, invariant
):
    assert compiled["account"][invariant] == "ok"


if __name__ == "__main__":
    argv = sys.argv[1:]
    root = os.getcwd()
    if "--root" in argv:
        at = argv.index("--root")
        root = os.path.abspath(argv[at + 1])
        del argv[at:at + 2]
    sys.path.insert(0, root)
    _bind_parser()
    from flexflow_tpu.kernels import context

    # nothing runs in this process: every gate is asked as on the chip
    with context.described_tpu():
        if argv and argv[0] == "joyai_step":
            print(json.dumps(joyai_step_bytes(int(argv[1]), root)))
        elif argv and argv[0] == "phi4flash_step":
            print(json.dumps(cell_step_bytes("phi4miniflash_s4096_1chip", root)))
        elif argv and argv[0] == "mellum2_step":
            print(json.dumps(cell_step_bytes("mellum2_12b_s8192_1chip", root)))
        elif argv and argv[0] == "ouro_step":
            # `ouro_step <layers>`: the looped cell's whole step at that depth
            layers = int(argv[1]) if len(argv) > 1 else None
            cut = {} if layers is None else {
                "num_hidden_layers": layers,
                "layer_types": ["full_attention"] * layers,
            }
            print(json.dumps(cell_step_bytes("ouro26b_s8192_1chip", root, **cut)))
        elif argv and argv[0] == "granite_step":
            print(json.dumps(cell_step_bytes("granite4hmicro_s4096_1chip", root)))
        elif argv and argv[0] == "qwen3next_account":
            print(json.dumps(gdn_node_account()))
        elif argv and argv[0] == "qwen3next_step":
            print(json.dumps(cell_step_bytes("qwen3next80b_s8192_1chip", root)))
        elif argv and argv[0] == "granite":
            print(json.dumps(check_granite()))
        elif argv and argv[0] == "mellum2":
            print(json.dumps(check_mellum2()))
        elif argv and argv[0] == "between":
            print(json.dumps(check_between()))
        elif argv:
            print(listing(argv[0]))
        else:
            print(json.dumps(
                dict({name: check(name) for name in SHAPES}, kimi=check_kimi(),
                     lfm2=check_lfm2(), experts=check_experts(),
                     held_sums=check_held_sums(), qwen3next=check_qwen3next(),
                     joyai=check_joyai(), phi4flash=check_phi4flash(),
                     mellum2=check_mellum2(), granite=check_granite(),
                     between=check_between(),
                     account=check_account(root))
            ))

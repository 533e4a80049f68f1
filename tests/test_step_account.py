"""The account of a compiled step's bytes (`observability/step_account.py`):
the ENTRY parser and the liveness walk on hand-written HLO texts whose peak
is worked out in the comments, then on a two-block model compiled on the CPU
(rows that add up, the three phases, what `recompute` stops keeping), the
step `fit` runs accounted without a second trace, and the report's parts.

All on the CPU: bytes and counts, never a time."""

import re

import numpy as np
import pytest

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.observability import step_account, trace
from flexflow_tpu.observability.step_account import (
    _nbytes,
    account_of_text,
    entry_instructions,
    family,
    shapes_of,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.pcg import ComputationGraphBuilder


def holders(account):
    return {
        (r["phase"], r["kind"], r["name"]): r["bytes"]
        for r in account["walk"]["held_at_peak"]
    }


def rows(account):
    return {(r["phase"], r["kind"], r["name"]): r for r in account["rows"]}


# -- hand-written programs -----------------------------------------------------

# index                                                    live bytes after it
#  0 p0   the argument, 1,024 B, held throughout, and the
#         result (1,024 B, the caller's from the start)      2,048
#  1 two  makes 1,024 + 2,048                                5,120
#  2, 3   forward a member each, make nothing                5,120
#  4 z    reads two's second member, makes 1,024             6,144  <- peak
#  5 out  two's second member is dead since 4                4,096
TUPLE = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0), metadata={op_name="x"}
  %two = (f32[256]{0}, f32[512]{0}) fusion(%p0), kind=kLoop, calls=%f0, metadata={op_name="jit(s)/jvp(ff.dense.a)/mul" stack_frame_id=1}
  %x = f32[256]{0} get-tuple-element(%two), index=0
  %y = f32[512]{0} get-tuple-element(%two), index=1
  %z = f32[256]{0} fusion(%y), kind=kLoop, calls=%f1, metadata={op_name="jit(s)/jvp(ff.dense.b)/add"}
  ROOT %out = f32[256]{0} add(%x, %z), metadata={op_name="jit(s)/transpose(jvp(ff.dense.a))/add"}
}
"""


def test_tuple_members_live_each_to_its_own_last_reader():
    a = account_of_text(TUPLE)
    walk = a["walk"]
    assert walk["peak_bytes"] == 6144
    assert walk["peak_at"]["instruction"] == "z"
    assert walk["peak_at"]["scope"] == ("fwd", "dense", "b")
    assert holders(a) == {
        ("arguments", "", ""): 1024, ("fwd", "dense", "a"): 3072,
        ("fwd", "dense", "b"): 1024, ("bwd", "dense", "a"): 1024,
    }
    # no XLA totals were given: nothing to compare the walk with
    assert a["memory"] is None and walk["walk_over_xla"] is None
    made = rows(a)
    assert made[("fwd", "dense", "a")]["written_bytes"] == 3072
    assert made[("fwd", "dense", "a")]["read_bytes"] == 1024
    # `z` reads the member it is handed, not the whole tuple
    assert made[("fwd", "dense", "b")]["read_bytes"] == 2048
    assert made[("bwd", "dense", "a")]["families"] == {
        "out": {"instructions": 1, "written_bytes": 1024,
                "read_bytes": 2048, "s1_bytes": 0},
    }
    assert a["not_walked"] == {"fusions": 2, "computations": []}


def test_kept_for_backward_is_what_a_forward_scope_leaves_a_backward_reader():
    kept = account_of_text(TUPLE)["walk"]["kept_for_backward"]
    # two's first member and z: made under `jvp(ff...)`, read last under
    # `transpose(jvp(ff...))`; two's second member died in the forward pass
    assert sorted((r["kind"], r["name"], r["bytes"]) for r in kept) == [
        ("dense", "a", 1024), ("dense", "b", 1024),
    ]


# [100, 64] in bf16 under T(8,128)(2,1): rows of 8, columns of 128: 104 x 128
# elements, 26,624 B; the other way round (minor_to_major {0,1}) 64 x 128
# elements, 16,384 B. The copy in S(1) is no byte of the device's memory.
FAST = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (p0: bf16[100,64]) -> bf16[100,64] {
  %p0 = bf16[100,64]{1,0:T(8,128)(2,1)} parameter(0)
  %fast = bf16[100,64]{1,0:T(8,128)(2,1)S(1)} fusion(%p0), kind=kLoop, calls=%f, metadata={op_name="jit(s)/ff.cast/convert_element_type"}
  ROOT %out = bf16[100,64]{0,1:T(8,128)(2,1)} copy(%fast), metadata={op_name="jit(s)/jvp(ff.dense.a)/transpose"}
}
"""


def test_sizes_follow_the_layouts_tiles_and_s1_is_told_apart():
    a = account_of_text(FAST)
    made = rows(a)
    assert made[("other", "cast", "")]["written_bytes"] == 26624
    assert made[("other", "cast", "")]["s1_bytes"] == 26624
    assert made[("fwd", "dense", "a")]["written_bytes"] == 16384
    assert made[("fwd", "dense", "a")]["s1_bytes"] == 0
    assert a["walk"]["peak_bytes"] == 26624 + 16384
    # the listings' plain product reads no layout
    assert _nbytes("bf16[100,64]{1,0:T(8,128)(2,1)S(1)}") == 12800
    assert shapes_of("(bf16[100,64]{1,0}, /*index=1*/s32[])") == [
        ("bf16", (100, 64)), ("s32", ()),
    ]


# w comes back in place (`input_output_alias`): the new weights are written
# where the old lay, 4,096 B once. w + g + the loss's 4 B = 8,196.
DONATED = """HloModule jit_s, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[1024]{0}, f32[1024]{0})->(f32[1024]{0}, f32[])}

ENTRY %main.1 (w: f32[1024], g: f32[1024]) -> (f32[1024], f32[]) {
  %w = f32[1024]{0} parameter(0)
  %g = f32[1024]{0} parameter(1)
  %new = f32[1024]{0} fusion(%w, %g), kind=kLoop, calls=%f, metadata={op_name="jit(s)/ff.optimizer/sub"}
  %loss = f32[] fusion(%g), kind=kLoop, calls=%f2, metadata={op_name="jit(s)/ff.loss/reduce_sum"}
  ROOT %t = (f32[1024]{0}, f32[]) tuple(%new, %loss)
}
"""


def test_a_donated_parameter_is_counted_once_with_the_output_in_its_place():
    a = account_of_text(DONATED)
    assert a["walk"]["peak_bytes"] == 8196
    assert holders(a) == {("arguments", "", ""): 8192, ("fwd", "loss", ""): 4}
    # the optimizer made the buffer all the same
    assert rows(a)[("opt", "optimizer", "")]["written_bytes"] == 4096
    kept = DONATED.replace("input_output_alias={ {0}: (0, {}, may-alias) }, ", "")
    assert account_of_text(kept)["walk"]["peak_bytes"] == 8196 + 4096


# the kernel writes its result over its second operand: a, b, tmp and the
# result, 4,096 + 64 + 4,096 + 4,096; a buffer of its own would add 4,096
# (`out` reads tmp too, so tmp lives until `out` is made and cannot lie where
# `out` will: `LODGED`)
IN_PLACE = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (a: f32[1024], b: f32[16]) -> f32[1024] {
  %a = f32[1024]{0} parameter(0)
  %b = f32[16]{0} parameter(1)
  %tmp = f32[1024]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(s)/jvp(ff.dense.a)/mul"}
  %rows_add.3 = f32[1024]{0} custom-call(%b, %tmp), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (1, {})}, metadata={op_name="jit(s)/jvp(ff.dense.a)/rows_add/pallas_call"}
  ROOT %out = f32[1024]{0} fusion(%rows_add.3, %tmp), kind=kLoop, calls=%g, metadata={op_name="jit(s)/jvp(ff.dense.a)/add"}
}
"""


def test_a_result_written_over_its_operand_is_counted_once():
    a = account_of_text(IN_PLACE)
    assert a["walk"]["peak_bytes"] == 12352
    own = IN_PLACE.replace("output_to_operand_aliasing={{}: (1, {})}, ", "")
    assert account_of_text(own)["walk"]["peak_bytes"] == 12352 + 4096
    # in place, the kernel made nothing: it has no row; on its own it has,
    # under the name the device trace gives it
    families = rows(a)[("fwd", "dense", "a")]["families"]
    assert "pallas/rows_add" not in families
    families = rows(account_of_text(own))[("fwd", "dense", "a")]["families"]
    assert families["pallas/rows_add"]["written_bytes"] == 4096


def test_an_in_place_dynamic_update_slice_is_counted_once():
    text = IN_PLACE.replace(
        '%rows_add.3 = f32[1024]{0} custom-call(%b, %tmp), custom_call_target'
        '="tpu_custom_call", output_to_operand_aliasing={{}: (1, {})}',
        "%rows_add.3 = f32[1024]{0} dynamic-update-slice(%tmp, %b, %b)",
    )
    assert "dynamic-update-slice" in text
    assert account_of_text(text)["walk"]["peak_bytes"] == 12352


# index                                              HBM bytes   with x read by out
#  0 w, g  the arguments, and the results' 4,096 + 4       12,292      12,292
#  2 x     dead before `new` is made: it lies where `new`
#          will, no byte of its own                         12,292      16,388
#  3 y     x is dead; as large, but `new`'s allocation
#          takes one at a time, the first of equals         16,388      20,484  <- peak
#  4 loss  reads y                                          16,388      20,484
#  5 new   written in its own allocation                    12,292      12,292
LODGED = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (w: f32[1024], g: f32[1024]) -> (f32[1024], f32[]) {
  %w = f32[1024]{0} parameter(0)
  %g = f32[1024]{0} parameter(1)
  %x = f32[1024]{0} fusion(%w), kind=kLoop, calls=%f0, metadata={op_name="jit(s)/jvp(ff.dense.a)/mul"}
  %y = f32[1024]{0} fusion(%x, %g), kind=kLoop, calls=%f1, metadata={op_name="jit(s)/jvp(ff.dense.b)/mul"}
  %loss = f32[] fusion(%y), kind=kLoop, calls=%f2, metadata={op_name="jit(s)/ff.loss/reduce_sum"}
  %new = f32[1024]{0} fusion(%w, %g), kind=kLoop, calls=%f, metadata={op_name="jit(s)/ff.optimizer/sub"}
  ROOT %t = (f32[1024]{0}, f32[]) tuple(%new, %loss)
}
"""


def test_a_temporary_dead_before_a_result_is_made_lies_where_the_result_will():
    """XLA's buffer assignment gives an allocation that outlives the run (a
    result's) to temporaries that live wholly before the result is made, one
    at a time at offset 0: the q projection of an attention node ALONE lies
    where its weights' gradient is put together (PR 66; the walk read 1.12 of
    XLA's peak there without this, 1.00 with it)."""
    walk = account_of_text(LODGED)["walk"]
    # x [2, 3] and y [3, 4] overlap at 3: x, the first of equals, is lodged
    assert (walk["peak_bytes"], walk["lodged_bytes"]) == (16388, 4096)
    assert walk["peak_at"]["instruction"] == "y"
    # read by the instruction that makes the result: not dead before it
    held = LODGED.replace("fusion(%w, %g), kind=kLoop, calls=%f,",
                          "fusion(%w, %x), kind=kLoop, calls=%f,")
    walk = account_of_text(held)["walk"]
    # y [3, 4] still is: the peak is x, y and the allocations that outlive
    assert (walk["peak_bytes"], walk["lodged_bytes"]) == (16388, 4096)
    both = held.replace("fusion(%w, %x)", "fusion(%y, %x)")
    walk = account_of_text(both)["walk"]
    assert (walk["peak_bytes"], walk["lodged_bytes"]) == (20484, 0)


def test_a_donated_argument_dead_before_its_result_is_made_takes_temporaries():
    """`w` comes back in place and is read last by x: from then until `new`
    is made its allocation is empty, and y (made after w's last reader) lies
    there; x, which w's last reader makes, does not."""
    donated = LODGED.replace(
        "is_scheduled=true",
        "is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }",
    ).replace("fusion(%w, %g), kind=kLoop, calls=%f,",
              "fusion(%g), kind=kLoop, calls=%f,")
    walk = account_of_text(donated)["walk"]
    # w, g, the loss and x; y in w's place
    assert (walk["peak_bytes"], walk["lodged_bytes"]) == (12292, 4096)
    # w read by `new` itself: never empty
    kept = donated.replace("fusion(%g), kind=kLoop, calls=%f,",
                           "fusion(%w, %g), kind=kLoop, calls=%f,")
    walk = account_of_text(kept)["walk"]
    assert (walk["peak_bytes"], walk["lodged_bytes"]) == (16388, 0)


# index                                                            HBM bytes
#  0 p     argument 4,096 and the result's 2,048 (too small for
#          big to lie in: `LODGED`)                                  6,144
#  1 big   4,096                                                   10,240
#  2 cs    the copy in S(1) and its flag in S(2): none of HBM      10,240
#  3 other 8,192; big is still being read by the copy              18,432  <- peak
#  4 cd    the copy has ended: big dies after it                   18,432
#  5 out                                                           14,336
ASYNC = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (p: f32[1024]) -> f32[512] {
  %p = f32[1024]{0} parameter(0)
  %big = f32[1024]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(s)/jvp(ff.dense.a)/mul"}
  %copy-start.2 = (f32[1024]{0:S(1)}, f32[1024]{0}, u32[]{:S(2)}) copy-start(%big)
  %other = f32[2048]{0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(s)/jvp(ff.dense.b)/mul"}
  %copy-done.2 = f32[1024]{0:S(1)} copy-done(%copy-start.2)
  ROOT %out = f32[512]{0} fusion(%copy-done.2, %other), kind=kLoop, calls=%h, metadata={op_name="jit(s)/jvp(ff.dense.b)/add"}
}
"""


def test_an_asynchronous_copy_is_counted_at_its_start_and_holds_its_source():
    a = account_of_text(ASYNC)
    assert a["walk"]["peak_bytes"] == 18432
    assert a["walk"]["peak_at"]["instruction"] == "other"
    # XLA's copy carries no name: it is booked to the scope of what it moves
    start = rows(a)[("fwd", "dense", "a")]["families"]["copy-start"]
    assert start == {"instructions": 1, "written_bytes": 4100,
                     "read_bytes": 4096, "s1_bytes": 4096}
    # the done made nothing, and `out` read the copy through it
    assert all(
        "copy-done" not in r["families"] for r in a["rows"]
    )
    assert rows(a)[("fwd", "dense", "b")]["families"]["out"]["read_bytes"] == (
        4096 + 8192
    )


def test_the_chips_spelling_of_an_asynchronous_slice_reads_the_same():
    """The chip's executable prints `async-start(...), calls=%async_computation`
    / `async-done` where the described chip's prints `slice-start` /
    `slice-done` (my chip run, PR 64): `((operands), output, context)` either
    way, and the wrapper it calls is the one operation, nothing unwalked."""
    described = ASYNC.replace(
        "(f32[1024]{0:S(1)}, f32[1024]{0}, u32[]{:S(2)}) copy-start(%big)",
        "((f32[1024]{0}), f32[256]{0:S(1)}, s32[]{:S(2)}) slice-start(%big), "
        "slice={[0:256]}",
    ).replace("f32[1024]{0:S(1)} copy-done(", "f32[256]{0:S(1)} slice-done(")
    chip = described.replace(
        "slice-start(%big), slice={[0:256]}",
        "async-start(%big), calls=%async_computation.2",
    ).replace("slice-done(", "async-done(")
    assert "async-done(%copy-start.2)" in chip
    accounts = [account_of_text(text) for text in (described, chip)]
    for a in accounts:
        assert a["walk"]["peak_bytes"] == 18432  # `big` held until the done
        assert a["not_walked"]["computations"] == []
        start = rows(a)[("fwd", "dense", "a")]["families"]["copy-start"]
        assert (start["written_bytes"], start["s1_bytes"]) == (1028, 1024)
    assert accounts[0]["rows"] == accounts[1]["rows"]


def test_a_copy_xla_made_is_its_source_again_and_no_reader_of_its_own():
    """`big` is made in the forward pass and read by the backward pass
    through a prefetch into S(1) that XLA scheduled: kept once, under its
    maker; the copy's 4,096 B are the same bytes, and the copy is no
    forward reader that would end `big`'s life before the backward pass."""
    text = ASYNC.replace(
        'jit(s)/jvp(ff.dense.b)/add', 'jit(s)/transpose(jvp(ff.dense.b))/add'
    )
    kept = account_of_text(text)["walk"]["kept_for_backward"]
    assert sorted((r["kind"], r["name"], r["bytes"]) for r in kept) == [
        ("dense", "a", 4096), ("dense", "b", 8192),
    ]
    assert account_of_text(ASYNC)["walk"]["kept_for_backward"] == []


# what XLA adds without a name and that moves nothing a scope made: the
# argument prefetched into S(1), the argument relaid under the ARGUMENT's
# name, the zeros a gradient is put together in
FOR_A_NODE = """HloModule jit_s, is_scheduled=true

ENTRY %main.1 (w: f32[1024]) -> f32[1024] {
  %w = f32[1024]{0} parameter(0), metadata={op_name="params['n0']"}
  %c = f32[] constant(0)
  %copy-start.1 = (f32[1024]{0:S(1)}, f32[1024]{0}, u32[]{:S(2)}) copy-start(%w)
  %copy-done.1 = f32[1024]{0:S(1)} copy-done(%copy-start.1)
  %relaid = f32[512]{0} reduce(%w, %c), dimensions={1}, to_apply=%sum, metadata={op_name="params['n0']"}
  %zeros = f32[1024]{0} broadcast(%c), dimensions={}
  %y = f32[1024]{0} fusion(%copy-done.1, %relaid), kind=kLoop, calls=%f, metadata={op_name="jit(s)/jvp(ff.dense.a)/mul"}
  ROOT %out = f32[1024]{0} fusion(%zeros, %y), kind=kLoop, calls=%g, metadata={op_name="jit(s)/transpose(jvp(ff.dense.a))/add"}
}
"""


def test_what_xla_adds_for_a_node_is_booked_to_the_node_it_is_read_by():
    """A nameless instruction is booked to the scope of what it moves; where
    that is an argument or nothing, to the scope of its first reader: the
    prefetch and the relaid argument to the forward pass of `a`, the zeros to
    its backward pass. Nothing is left without a scope."""
    made = rows(account_of_text(FOR_A_NODE))
    assert set(made) == {("fwd", "dense", "a"), ("bwd", "dense", "a")}
    fwd = made[("fwd", "dense", "a")]
    assert fwd["s1_bytes"] == 4096
    assert fwd["written_bytes"] == 4100 + 2048 + 4096  # the copy, relaid, y
    assert made[("bwd", "dense", "a")]["written_bytes"] == 4096 + 4096
    # written outside every scope with a name of its own: it stays so
    bare = FOR_A_NODE.replace(
        "broadcast(%c), dimensions={}",
        'broadcast(%c), dimensions={}, metadata={op_name="jit(s)/zeros"}',
    )
    assert rows(account_of_text(bare))[("unattributed", "", "")][
        "written_bytes"
    ] == 4096


# the loop works in place on what it is handed: p, the copy of it and the
# result, 12,288 B from the copy on; its body is named, not entered
LOOP = """HloModule jit_s, is_scheduled=true

%body (arg: (s32[], f32[1024])) -> (s32[], f32[1024]) {
  %arg = (s32[], f32[1024]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[1024]{0} get-tuple-element(%arg), index=1
  %y = f32[1024]{0} fusion(%x), kind=kLoop, calls=%f
  %j = s32[] fusion(%i), kind=kLoop, calls=%inc
  ROOT %r = (s32[], f32[1024]{0}) tuple(%j, %y)
}

%cond (arg.1: (s32[], f32[1024])) -> pred[] {
  %arg.1 = (s32[], f32[1024]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt = pred[] fusion(%i.1), kind=kLoop, calls=%less
}

ENTRY %main.1 (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %zero = s32[] constant(0)
  %begin = f32[1024]{0} copy(%p), metadata={op_name="jit(s)/jvp(ff.ssm.m0)/scan/copy"}
  %init = (s32[], f32[1024]{0}) tuple(%zero, %begin)
  %loop = (s32[], f32[1024]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(s)/jvp(ff.ssm.m0)/scan/while"}
  %end = f32[1024]{0} get-tuple-element(%loop), index=1
  ROOT %out = f32[1024]{0} fusion(%end), kind=kLoop, calls=%h, metadata={op_name="jit(s)/jvp(ff.ssm.m0)/scan/mul"}
}
"""


def test_a_while_forwards_its_operand_and_its_body_is_named_not_walked():
    a = account_of_text(LOOP)
    assert a["walk"]["peak_bytes"] == 12288
    assert holders(a) == {
        ("arguments", "", ""): 4096, ("fwd", "ssm", "m0/scan"): 8192,
    }
    # the while made no buffer: it has no row
    assert set(rows(a)[("fwd", "ssm", "m0/scan")]["families"]) == {"begin", "out"}
    assert a["not_walked"]["fusions"] == 1
    bodies = {c["computation"]: c for c in a["not_walked"]["computations"]}
    assert set(bodies) == {"cond", "body"}
    assert bodies["body"]["as"] == "while/body"
    assert bodies["body"]["called_by"] == "loop"
    assert bodies["body"]["scope"] == ("fwd", "ssm", "m0/scan")
    assert bodies["body"]["instructions"] == 6
    assert bodies["body"]["written_bytes"] == 4096 + 4
    assert bodies["cond"]["as"] == "while/condition"


@pytest.mark.parametrize("result, complaint", [
    ("q7[256]{0}", "no size known for dtype 'q7'"),
    ("f32[256]{0:D(S)}", "cannot size the layout {0:D(S)}"),
    ("f32[<=256]{0}", "cannot size the dimensions [<=256]"),
    ("(f32[256]{0}, [256])", "cannot read the result type"),
])
def test_what_cannot_be_sized_raises_with_the_instructions_line(
    result, complaint
):
    text = TUPLE.replace("%z = f32[256]{0} fusion", f"%z = {result} fusion")
    with pytest.raises(ValueError) as e:
        account_of_text(text)
    assert complaint in str(e.value)
    # the line, so that the reader knows what to teach the sizer
    assert "fusion(%y), kind=kLoop, calls=%f1" in str(e.value)


def test_every_dtype_of_the_old_table_and_more_is_sized():
    assert _nbytes(
        "(f32[2], bf16[2], s32[2], u32[2], pred[2], s8[2], u8[2])"
    ) == 2 * (4 + 2 + 4 + 4 + 1 + 1 + 1)
    assert _nbytes("(f16[2], s64[2], f8e4m3fn[2], c64[2], token[])") == 2 * (
        2 + 8 + 1 + 8
    )


@pytest.mark.parametrize("line, want", [
    ('%fusion.18 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%c',
     "fusion.kOutput"),
    ('%copy.3 = f32[8]{0} copy(%a)', "copy"),
    ('%convert_element_type.25 = f32[8]{0} convert(%a)',
     "convert_element_type"),
    ('%flash_fwd_causal_bshf_window.1 = f32[8]{0} custom-call(%a), '
     'custom_call_target="tpu_custom_call"',
     "pallas/flash_fwd_causal_bshf_window"),
    ('%psum.7 = f32[8]{0} all-reduce(%a), to_apply=%add', "all-reduce/psum"),
    ('%all-reduce.2 = f32[8]{0} all-reduce(%a), to_apply=%add', "all-reduce"),
    ('%pad_maximum_fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%c',
     "pad_maximum_fusion"),
])
def test_families_carry_the_names_the_device_trace_prints(line, want):
    name, _, opcode, _, _ = entry_instructions(
        "ENTRY %m (a: f32[8]) -> f32[8] {\n  " + line + "\n}\n"
    )[0]
    assert family(name, opcode, line) == want


# -- a two-block model compiled on the CPU ------------------------------------

BATCH, SEQ, WIDTH = 4, 16, 32


def two_blocks(recomputed=()):
    """Two residual blocks of two dense layers and a norm, a head; the
    blocks named in `recomputed` under the builder's `recompute`."""
    import contextlib

    b = ComputationGraphBuilder()
    h = b.create_input([BATCH, SEQ, WIDTH], DataType.FLOAT, name="x")
    for block in ("b0", "b1"):
        scope = b.recompute() if block in recomputed else contextlib.nullcontext()
        with scope:
            inner = b.gelu(b.dense(h, 4 * WIDTH, name=f"{block}_up"),
                           name=f"{block}_act")
            h = b.layer_norm(
                b.add(h, b.dense(inner, WIDTH, name=f"{block}_down"),
                      name=f"{block}_sum"),
                axes=[2], name=f"{block}_norm",
            )
    logits = b.dense(h, 8, name="head")
    model = FFModel.from_computation_graph(
        b, logits,
        FFConfig(batch_size=BATCH, seed=5, print_freq=0, max_devices=1),
    )
    model.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    return model


def batch_of():
    rs = np.random.RandomState(1)
    return (
        rs.randn(BATCH, SEQ, WIDTH).astype(np.float32),
        rs.randint(0, 8, (BATCH, SEQ)).astype(np.int32),
    )


@pytest.fixture(scope="module")
def accounts():
    """{recomputed blocks: the model's account}, each model compiled once."""
    return {
        recomputed: two_blocks(recomputed).step_account()
        for recomputed in ((), ("b0",))
    }


def kept_by_block(account, block):
    return sum(
        r["bytes"] for r in account["walk"]["kept_for_backward"]
        if r["name"].startswith(block + "_")
    )


def test_rows_add_up_to_the_buffers_the_entry_makes(accounts):
    a = accounts[()]
    total = sum(r["written_bytes"] for r in a["rows"])
    for row in a["rows"]:
        for key in ("instructions", "written_bytes", "read_bytes", "s1_bytes"):
            assert row[key] == sum(f[key] for f in row["families"].values())
    assert total > 0 and a["walk"]["buffers"] > len(a["rows"])
    # the CPU's layouts have no tile and no S(1): a buffer is its elements
    assert sum(r["s1_bytes"] for r in a["rows"]) == 0
    memory = a["memory"]
    assert memory["total"] == (
        memory["arguments"] + memory["outputs"] - memory["aliased"]
        + memory["temp"]
    )
    assert memory["total_less_xla_peak"] == memory["total"] - memory["xla_peak"]
    # the state is donated: the arguments are held once, at the peak too
    held = holders(a)
    assert held[("arguments", "", "")] == memory["arguments"]
    assert a["walk"]["peak_bytes"] >= memory["arguments"]
    assert a["walk"]["walk_over_xla"] == pytest.approx(
        a["walk"]["peak_bytes"] / memory["xla_peak"]
    )


def test_the_three_phases_and_both_blocks_are_in_the_rows(accounts):
    made = rows(accounts[()])
    assert {"fwd", "bwd", "opt"} <= {phase for phase, _, _ in made}
    for block in ("b0", "b1"):
        for phase in ("fwd", "bwd"):
            assert (phase, "dense", f"{block}_up") in made
    assert ("opt", "optimizer", "") in made


def test_recompute_keeps_less_of_the_block_it_takes_and_no_less_of_the_other(
    accounts,
):
    kept, again = accounts[()], accounts[("b0",)]
    assert kept_by_block(kept, "b0") > 0 and kept_by_block(kept, "b1") > 0
    # the block's wide activations ([4, 16, 128] in float32, 32,768 B each)
    # are computed again in the backward pass and not kept for it
    assert kept_by_block(again, "b0") <= kept_by_block(kept, "b0") - 32768
    assert kept_by_block(again, "b1") == kept_by_block(kept, "b1")
    # and the second forward is booked where `step_anatomy` books its time
    up = ("bwd", "dense", "b0_up")
    assert (
        rows(again)[up]["written_bytes"] > rows(kept)[up]["written_bytes"]
    )


def test_without_recompute_the_block_keeps_as_much_again():
    """A third model, built after the recomputed one: what a block keeps
    rises back with the plan, and is not left over from the last account."""
    kept = two_blocks().step_account()
    again = two_blocks(("b0",)).step_account()
    back = two_blocks().step_account()
    assert kept_by_block(back, "b0") == kept_by_block(kept, "b0")
    assert kept_by_block(again, "b0") < kept_by_block(back, "b0")


def test_the_step_fit_runs_is_accounted_without_a_second_trace():
    trace.reset_span_totals()
    model = two_blocks()
    model.fit(*batch_of(), epochs=1, shuffle=False, verbose=False)
    totals = trace.span_totals()
    assert totals[trace.STEP_TRACE]["count"] == 1
    # nothing of the account has run: no span, and compile + fit of the
    # one-chip backend lowered nothing statically
    assert "step_account" not in totals and "compile/lower_step" not in totals
    account = model.step_account()
    totals = trace.span_totals()
    assert totals["step_account"]["count"] == 1
    assert totals[trace.STEP_TRACE]["count"] == 1
    assert step_account.noted_instance() is model.instance
    # made once and kept
    assert step_account.last() is account and model.step_account() is account
    assert trace.span_totals()["step_account"]["count"] == 1
    assert trace.span_totals()["compile/lower_step"]["count"] == 1
    # and the step goes on as it was
    model.fit(*batch_of(), epochs=1, shuffle=False, verbose=False)
    assert trace.span_totals()[trace.STEP_TRACE]["count"] == 1


def test_a_lowering_is_noted_and_costs_nothing_until_asked():
    from flexflow_tpu.analysis.lowering import lower_step_trace

    model = two_blocks()
    trace.reset_span_totals()
    lowered = lower_step_trace(
        model.instance, model.loss_attrs, params=model.params,
        opt_state=model.opt_state,
    )
    assert step_account.noted_instance() is model.instance
    assert "step_account" not in trace.span_totals()
    account = step_account.last()
    assert account["memory"] == step_account._memory(
        lowered.compile().memory_analysis()
    )
    assert account["walk"]["instructions"] == len(
        entry_instructions(lowered.compile().as_text())
    )


def test_report_has_its_five_parts(accounts):
    text = step_account.report(top=3, of=accounts[("b0",)])
    lines = text.splitlines()
    assert lines[0].startswith("memory (MB): arguments ")
    assert re.search(r"xla_peak [0-9.]+ total_less_xla_peak -?[0-9.]+ code", lines[0])
    assert re.match(
        r"walk: peak [0-9.]+ MB at instruction \d+ of \d+ \(.*\), [0-9.]+ MB "
        r"of temporaries laid where a result will lie, walk_over_xla [0-9.]+$",
        lines[1],
    )
    for part in (
        "held at the peak (the 3 largest of ",
        "kept for the backward pass: ",
        "made in S(1): 0.0 MB of ",
        "not walked: the inside of ",
    ):
        assert sum(line.startswith(part) for line in lines) == 1, part
    at = lines.index(next(l for l in lines if l.startswith("held at the peak")))
    assert lines[at + 1].split() == ["phase", "kind", "name", "MB"]
    assert lines[at + 2].split()[0] == "arguments"
    # `top` rows a table and no more
    assert lines[at + 5].startswith("kept for the backward pass")
    assert step_account.report(of=accounts[()]) != text


def test_report_says_so_where_no_step_was_lowered(monkeypatch):
    monkeypatch.setattr(step_account, "_noted", None)
    monkeypatch.setattr(step_account, "_account", None)
    assert step_account.last() is None
    assert step_account.report() == "no step was lowered in this process"

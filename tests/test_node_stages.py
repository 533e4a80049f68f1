"""`tools/node_stages.py` on the committed trace of `mellum2_12b_s8192_1chip`
(`benchmark/testdata/mellum2_events.json.gz`, PR 60's program, which still
repeated k and v): what a family alone hides and the last primitive shows;
and on the op names of PR 66's program, whose norm and rotary are kernels."""

import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import node_stages  # noqa: E402


def test_copies_under_the_attention_nodes_are_named_after_what_they_feed():
    """The `copy` rows under the four attention nodes, 7.0 ms a step, are the
    rotary's and the norm's (`mul`, `tile`, `convert_element_type`, ...); the
    8-fold repeat of k and v is the `broadcast_in_dim` rows, 1.25 ms here (2.5
    beside PR 61's kernels), and no copy carries that name. The rows add up
    to the node kind's time."""
    out = node_stages.stages(node_stages.load(os.path.join(
        ROOT, "benchmark", "testdata", "mellum2_events.json.gz"
    )))
    rows = out["rows"]
    assert {node.partition("/")[0] for node, *_ in rows} == {
        "attn0", "attn1", "attn2", "attn3"
    }
    copies = {}
    for _node, _phase, last, family, ms in rows:
        if family.startswith("copy"):
            copies[last] = copies.get(last, 0.0) + ms
    assert 6.5 < sum(copies.values()) < 7.5
    assert "broadcast_in_dim" not in copies
    assert sum(copies.get(k, 0) for k in ("mul", "tile", "convert_element_type")) > 6.0
    repeat = sum(ms for *_, last, _family, ms in rows if last == "broadcast_in_dim")
    assert 1.0 < repeat < 3.0
    by_kind = dict(((kind, phase), ms) for kind, phase, ms in out["copies"])
    assert abs(
        by_kind["ring_attention", "fwd"] + by_kind["ring_attention", "bwd"]
        - sum(copies.values())
    ) < 1e-6
    kernels = sum(
        ms for *_, last, _family, ms in rows if last.endswith("/pallas_call")
    )
    assert {last for *_, last, _family, _ms in rows if "pallas" in last} == {
        f"flash_{name}/pallas_call" for name in (
            "fwd_causal_bshf", "fwd_causal_bshf_window", "delta_bshf",
            "bwd_causal_bshf", "bwd_causal_bshf_window",
        )
    }
    assert 31.0 < kernels < 32.5 and 85.0 < sum(r[-1] for r in rows) < 92.0


def test_the_norm_and_rotary_kernels_are_rows_of_their_node_by_their_op_name():
    """The op names the compiled Mellum2 window node carries (described-chip
    compile, PR 66): the pass's forward kernel under the node's `fwd`, its
    backward under `bwd`, each a row of its own beside the core's kernels,
    q's and k's calls added up; a recomputed forward is booked `bwd`."""
    step = "jit(_step)/jit(main)/"
    ops = [
        ("norm_rotary_fwd.1", step + "jvp(ff.ring_attention.attn0)/jit(_forward)/norm_rotary_fwd/pallas_call", 0, 240),
        ("norm_rotary_fwd.2", step + "jvp(ff.ring_attention.attn0)/jit(_forward)/norm_rotary_fwd/pallas_call", 240, 30),
        ("flash_fwd_causal_bshf_window.3", step + "jvp(ff.ring_attention.attn0)/core/flash_fwd_causal_bshf_window/pallas_call", 270, 900),
        ("norm_rotary_fwd.4", step + "transpose(jvp(jvp()))/checkpoint/rematted_computation/ff.ring_attention.attn1#2/jit(_forward)/norm_rotary_fwd/pallas_call", 1170, 200),
        ("norm_rotary_bwd.5", step + "transpose(jvp(ff.ring_attention.attn0))/jit(_backward)/norm_rotary_bwd/pallas_call", 1370, 350),
        ("norm_rotary_bwd.6", step + "transpose(jvp(ff.ring_attention.attn0))/jit(_backward)/norm_rotary_bwd/pallas_call", 1720, 40),
        ("fusion.7", step + "transpose(jvp(ff.ring_attention.attn0))/jit(_backward)/reduce_sum", 1760, 10),
    ]
    events = {
        "devices": {0: {"ops": ops, "modules": [("jit__step", 0, 1770)]}},
        "host": [],
    }
    rows = {
        (node, phase, last): (family, round(1e6 * ms))  # ns a step
        for node, phase, last, family, ms in node_stages.stages(events)["rows"]
    }
    assert rows == {
        ("attn0", "fwd", "norm_rotary_fwd/pallas_call"): ("norm_rotary_fwd", 270),
        ("attn0/core", "fwd", "flash_fwd_causal_bshf_window/pallas_call"): (
            "flash_fwd_causal_bshf_window", 900),
        ("attn1#2", "bwd", "norm_rotary_fwd/pallas_call"): ("norm_rotary_fwd", 200),
        ("attn0", "bwd", "norm_rotary_bwd/pallas_call"): ("norm_rotary_bwd", 390),
        ("attn0", "bwd", "reduce_sum"): ("fusion", 10),
    }

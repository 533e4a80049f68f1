"""`tools/node_stages.py` on the committed trace of `mellum2_12b_s8192_1chip`
(`benchmark/testdata/mellum2_events.json.gz`, PR 60's program, which still
repeated k and v): what a family alone hides and the last primitive shows."""

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
    kernels = sum(ms for *_, last, _family, ms in rows if last == "pallas_call")
    assert 31.0 < kernels < 32.5 and 85.0 < sum(r[-1] for r in rows) < 92.0

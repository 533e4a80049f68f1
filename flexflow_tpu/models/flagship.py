"""The flagship: the one transformer that `chip_smoke.py`, the search
profiler (`tools/profile_search.py`) and the search tests all build."""


def build_flagship_cg(
    batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000
):
    """The headline 12-layer transformer (reference
    examples/cpp/Transformer/transformer.cc:80-100 family). Single source
    of truth for both the chip smoke and the search-time measurement."""
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    x = b.create_input([batch, seq, embed], name="x")
    h = x
    for i in range(layers):
        # MHA bias on (the reference builder's default,
        # computation_graph_builder.h:236); dense layers bias-FREE — every
        # dense in the reference Transformer passes `false /*bias*/`
        # (examples/cpp/Transformer/transformer.cc:41-74,158)
        attn = b.multihead_attention(h, h, h, embed, heads, name=f"attn{i}")
        h = b.add(h, attn)
        h = b.layer_norm(h, axes=[-1], name=f"ln1_{i}")
        ff = b.dense(h, 4 * embed, use_bias=False, name=f"ff1_{i}")
        ff = b.gelu(ff)
        ff = b.dense(ff, embed, use_bias=False, name=f"ff2_{i}")
        h = b.add(h, ff)
        h = b.layer_norm(h, axes=[-1], name=f"ln2_{i}")
    logits = b.dense(h, vocab, use_bias=False, name="head")
    return b.graph, logits


def build_flagship_pcg(
    batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000
):
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    graph, _ = build_flagship_cg(batch, seq, embed, heads, layers, vocab)
    return pcg_from_computation_graph(graph)


def flagship_step_flops(batch, seq, embed, heads, layers, vocab):
    """Matmul FLOPs of one training step (three times the forward's)."""
    d_ff = 4 * embed
    per_layer = (
        2 * batch * seq * embed * embed * 4
        + 2 * batch * heads * seq * seq * (embed // heads) * 2
        + 2 * batch * seq * embed * d_ff * 2
    )
    return 3 * (layers * per_layer + 2 * batch * seq * embed * vocab)

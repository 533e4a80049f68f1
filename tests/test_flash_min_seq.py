"""Where the attention kernels start: the least sequence length per kernel
family (`flash_attention.min_seq_for`), the route `ops.mha_core_route` reads
from it, and the head-pair kernels at the short lengths it admits (interpret
mode on the CPU; the chip's numbers are in PERF.md, section 6, PR 31)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import flash_attention as fa
from flexflow_tpu.kernels.ops import mha_core_route
from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs


@pytest.fixture
def tpu_shaped_gate(monkeypatch):
    """The gates as a TPU sees them: they ask `jax.default_backend()`, which
    is the CPU here, and no override of the least length is set."""
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH", raising=False)
    from flexflow_tpu.kernels import ring_flash

    for module in (fa, ring_flash):  # ring_flash binds the name at import
        monkeypatch.setattr(
            module, "_backend_ok", lambda allow_interpret=False: True
        )


def dense_core(qkv, h, causal):
    """XLA's attention on the interleaved [b, s, 3f] row: per pair group
    [q_pair | k_pair | v_pair] of 128 lanes each."""
    b, s, f3 = qkv.shape
    f = f3 // 3
    d = f // h
    q, k, v = (
        jnp.swapaxes(
            qkv.reshape(b, s, f // 128, 3, 128)[:, :, :, i].reshape(b, s, h, d),
            1, 2,
        )
        for i in range(3)
    )
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(d)
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask, scores, -1e30)
    ctx = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, -1), v)
    return jnp.swapaxes(ctx, 1, 2).reshape(b, s, f)


@pytest.mark.parametrize(
    "s,b,causal",
    [(128, 16, False), (128, 16, True), (256, 8, False), (256, 8, True)],
)
def test_pair_qkv_kernels_at_short_sequences_match_dense(s, b, causal):
    """The fused-QKV head-pair kernels at the lengths the gate now admits,
    with a batch that folds several rows into a program: the forward and
    the gradients of q, k and v (the three lane groups of dqkv) against the
    dense core."""
    h, d = 2, 64
    f = h * d
    for fused_bwd in (False, True):
        assert 1 < fa._batch_block(
            b, s, s, s, 128, 4, fused_bwd=fused_bwd, bwd_blocks=8
        ) <= fa._MAX_FOLD
    rs = np.random.RandomState(s + causal)
    qkv = jnp.asarray(rs.randn(b, s, 3 * f), jnp.float32)
    weight = jnp.asarray(rs.randn(b, s, f), jnp.float32)

    def flash_loss(x):
        out = fa.flash_attention_bshf_qkv(x, h, causal=causal, interpret=True)
        return jnp.sum(out * weight)

    def dense_loss(x):
        return jnp.sum(dense_core(x, h, causal) * weight)

    np.testing.assert_allclose(
        np.asarray(
            fa.flash_attention_bshf_qkv(qkv, h, causal=causal, interpret=True)
        ),
        np.asarray(dense_core(qkv, h, causal)),
        atol=2e-5,
    )
    got = np.asarray(jax.grad(flash_loss)(qkv)).reshape(b, s, f // 128, 3, 128)
    want = np.asarray(jax.grad(dense_loss)(qkv)).reshape(b, s, f // 128, 3, 128)
    for i, name in enumerate(("dq", "dk", "dv")):
        np.testing.assert_allclose(
            got[:, :, :, i], want[:, :, :, i], atol=2e-4, err_msg=name
        )


def test_fold_is_capped_only_where_the_budget_allows_more():
    """_MAX_FOLD binds at short sequences; at [512, 64] tiles the budget's
    own answer (4 rows a program, forward and fused backward) stands, so the
    seq-512 cells lower the programs they lowered."""
    for fused_bwd in (False, True):
        assert fa._batch_block(
            64, 128, 128, 128, 128, 2, fused_bwd=fused_bwd, bwd_blocks=8
        ) == fa._MAX_FOLD
        assert fa._batch_block(
            24, 512, 512, 512, 128, 2, fused_bwd=fused_bwd, bwd_blocks=8
        ) == 4


@pytest.mark.parametrize(
    "s,block_q,block_k,transposed",
    [
        (128, 128, 128, True),
        (256, 256, 256, True),
        (512, 512, 512, False),  # the seq-512 cells keep their kernel
        (256, 128, 128, False),  # more than one tile: the online softmax
    ],
)
def test_pair_forward_body_is_chosen_from_the_shape(
    s, block_q, block_k, transposed
):
    want = fa._fwd_kernel_pair_t if transposed else fa._fwd_kernel_pair
    assert fa._pair_fwd_kernel(s, block_q, block_k) is want


BERT_LARGE = MultiHeadAttentionAttrs(embed_dim=1024, num_heads=16, bias=True)
# 16 heads of 128 (cerebras-gpt-1.3b, olmoe-1b-7b) and of 96
LANE_HEADS = MultiHeadAttentionAttrs(embed_dim=2048, num_heads=16, bias=True)
OTHER_HEADS = MultiHeadAttentionAttrs(embed_dim=1536, num_heads=16, bias=True)


@pytest.mark.parametrize(
    "attrs,seq,fused_qkv,want",
    [
        (BERT_LARGE, 128, True, "fused_row_qkv"),
        (BERT_LARGE, 512, True, "fused_row_qkv"),
        (BERT_LARGE, 64, True, "dense"),  # under the pair family's least
        (BERT_LARGE, 192, True, "dense"),  # 128 does not divide it
        (BERT_LARGE, 128, False, "fused_row"),  # distinct q, k, v: same family
        (LANE_HEADS, 512, True, "fused_row"),
        (LANE_HEADS, 256, True, "fused_row"),
        (LANE_HEADS, 128, True, "dense"),  # even with dense on the chip
        (OTHER_HEADS, 512, True, "rows"),
        (OTHER_HEADS, 256, True, "dense"),
    ],
)
def test_mha_core_route_reads_the_least_length_of_its_family(
    tpu_shaped_gate, attrs, seq, fused_qkv, want
):
    batch = 8192 // seq if 8192 % seq == 0 else 16
    shape = (batch, seq, attrs.embed_dim)
    assert mha_core_route(attrs, shape, shape, shape, fused_qkv) == want


def test_override_moves_every_family(tpu_shaped_gate, monkeypatch):
    """FLEXFLOW_TPU_FLASH_MIN_SEQ keeps working as it did: one length for
    every family, above or below what the table holds."""
    shape = (64, 128, 1024)
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "512")
    assert mha_core_route(BERT_LARGE, shape, shape, shape, True) == "dense"
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")
    shape = (64, 128, 1536)
    assert mha_core_route(OTHER_HEADS, shape, shape, shape, True) == "rows"


def test_sequence_parallel_kernels_keep_the_unmeasured_least_block(
    tpu_shaped_gate,
):
    """The ring and all-to-all kernels read MIN_SEQ_UNMEASURED as their
    least local block: no cell runs them and nothing was measured there."""
    from flexflow_tpu.kernels.ring_flash import ring_flash_supported

    assert fa._min_seq_default() == fa.MIN_SEQ_UNMEASURED == 512
    assert fa.min_seq_for("no such family") == 512
    for blk, want in ((128, False), (256, False), (512, True)):
        shape = (2, 16, blk, 64)
        assert ring_flash_supported(shape, shape, shape, interpret=False) is want
        # ulysses_attention._attend_full_seq's own condition
        assert fa._flash_shape_ok(shape, fa._min_seq_default()) is want

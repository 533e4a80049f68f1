"""Where the attention kernels start: the least sequence length per kernel
family (`flash_attention.min_seq_for`), the route `ops.mha_core_route` reads
from it, the head-pair kernels at the short lengths it admits and the
forward body `_pair_fwd_kernel` chooses from the shape (interpret mode on
the CPU; the chip's numbers are in PERF.md, section 6, PR 31 and PR 35)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import flash_attention as fa
from flexflow_tpu.kernels.ops import mha_core_route
from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs


@pytest.fixture
def tpu_shaped_gate(monkeypatch, entered):
    """The gates as a TPU sees them: they ask `jax.default_backend()`, which
    is the CPU here, and no override of the least length is set."""
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH", raising=False)
    entered(context.described_tpu())


def heads_of(qkv, h):
    """q, k, v as [b, h, s, d] from the interleaved [b, s, 3f] row: per pair
    group [q_pair | k_pair | v_pair] of 128 lanes each."""
    b, s, f3 = qkv.shape
    f = f3 // 3
    return (
        jnp.swapaxes(
            qkv.reshape(b, s, f // 128, 3, 128)[:, :, :, i]
            .reshape(b, s, h, f // h),
            1, 2,
        )
        for i in range(3)
    )


def plain_softmax(qkv, h, causal, precision=None):
    """XLA's attention on the interleaved row: o [b, s, f] and the base-2
    lse [b, h, 1, s] the kernels store."""
    b, s, f3 = qkv.shape
    q, k, v = heads_of(qkv, h)
    scores = jnp.einsum(
        "bhsd,bhtd->bhst", q, k, precision=precision
    ) / np.sqrt(q.shape[-1])
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask, scores, -1e30)
    ctx = jnp.einsum(
        "bhst,bhtd->bhsd", jax.nn.softmax(scores, -1), v, precision=precision
    )
    lse = jax.nn.logsumexp(scores, -1) * fa.LOG2E
    return jnp.swapaxes(ctx, 1, 2).reshape(b, s, f3 // 3), lse[:, :, None, :]


def dense_core(qkv, h, causal):
    return plain_softmax(qkv, h, causal)[0]


@pytest.mark.parametrize(
    "s,b,causal",
    [
        (128, 16, False), (128, 16, True), (256, 8, False), (256, 8, True),
        (512, 4, False), (512, 4, True),
        (384, 4, True), (640, 2, True), (896, 2, True),
    ],
)
def test_pair_qkv_kernels_at_short_sequences_match_dense(s, b, causal):
    """The fused-QKV head-pair kernels at the lengths the gate now admits,
    with a batch that folds several rows into a program (896 holds one):
    the forward and the gradients of q, k and v (the three lane groups of
    dqkv) against the dense core. The gradient comes through the lse of
    the forward that holds p stationary; where 256 does not divide the
    length (384, 640, 896) a causal forward takes 128 queries at a time."""
    h, d = 2, 64
    f = h * d
    for fused_bwd in (False, True):
        assert (s < 896) < fa._batch_block(
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
    """_MAX_FOLD binds at short sequences (8 rows a program at s = 128 and
    256, forward and fused backward, as PR 31 measured); at [512, 64] tiles
    the budget's own answer stands, 4 rows, which is also what the chip
    chose for the forward that holds p stationary (PR 35: 2 rows 0.396 ms a
    call on [24, 512, 3072], 4 rows 0.395, and 8 do not fit); at 1,024 one
    row fits."""
    for fused_bwd in (False, True):
        assert fa._batch_block(
            64, 128, 128, 128, 128, 2, fused_bwd=fused_bwd, bwd_blocks=8
        ) == fa._MAX_FOLD
        assert fa._batch_block(
            32, 256, 256, 256, 128, 2, fused_bwd=fused_bwd, bwd_blocks=8
        ) == fa._MAX_FOLD
        for batch in (24, 16):  # the seq-512 cells' rows a chip
            assert fa._batch_block(
                batch, 512, 512, 512, 128, 2, fused_bwd=fused_bwd,
                bwd_blocks=8,
            ) == 4
        assert fa._batch_block(
            8, 1024, 1024, 1024, 128, 2, fused_bwd=fused_bwd, bwd_blocks=8
        ) == 1


@pytest.mark.parametrize(
    "s,block_q,block_k,one_tile",
    [
        (128, 128, 128, True),
        (256, 256, 256, True),
        (384, 384, 384, True),
        (512, 512, 512, True),  # the seq-512 cells
        (1024, 1024, 1024, True),
        (256, 128, 128, False),  # more than one tile: no pair forward (and
        (512, 256, 512, False),  # no pair backward) is written for it, and
        (1024, 1024, 512, False),  # the entries' gate keeps it away
    ],
)
def test_pair_forward_body_is_chosen_from_the_shape(
    s, block_q, block_k, one_tile
):
    if one_tile:
        assert fa._pair_fwd_kernel(s, block_q, block_k) is fa._fwd_kernel_pair
    else:
        with pytest.raises(AssertionError):
            fa._pair_fwd_kernel(s, block_q, block_k)


@pytest.mark.parametrize(
    "s,chunk",
    [(128, 128), (256, 256), (384, 128), (512, 256), (640, 128), (768, 256),
     (896, 128), (1024, 256)],
)
def test_causal_chunk_divides_every_length_the_pair_gate_admits(s, chunk):
    """The gate admits every multiple of 128 up to the block; a chunk that
    does not divide s leaves the rows after the last whole chunk unwritten."""
    assert fa.bshf_pair_supported(16, 64, s)
    assert fa._pair_causal_chunk(s) == chunk and s % chunk == 0


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("entry", ["fused_qkv", "separate"])
@pytest.mark.parametrize(
    "s,b",
    [(128, 8), (256, 4), (384, 2), (512, 2), (640, 1), (896, 1), (1024, 1)],
)
def test_pair_forward_of_one_tile_matches_the_plain_softmax(
    s, b, entry, causal
):
    """The single-tile body at every kind of length the gate admits, through
    the forward of both entries: o AND the base-2 lse the backward will
    read. A causal program takes its queries 256 at a time where 256 divides
    s and 128 at a time where it does not (384, 640, 896), and reads no key
    past a chunk's last query; every row has to be written."""
    h = 2
    f = h * 64
    rs = np.random.RandomState(s + causal)
    qkv = jnp.asarray(rs.randn(b, s, 3 * f), jnp.float32)
    if entry == "fused_qkv":
        o, lse = fa._fwd_bshf_pair_qkv(qkv, h, causal, s, s, interpret=True)
    else:
        q, k, v = (
            jnp.swapaxes(x, 1, 2).reshape(b, s, f) for x in heads_of(qkv, h)
        )
        o, lse = fa._fwd_bshf_pair(q, k, v, h, causal, s, s, interpret=True)
    want_o, want_lse = plain_softmax(qkv, h, causal, precision="highest")
    for got in (o, lse):
        assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), atol=2e-5
    )


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

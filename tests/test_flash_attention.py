"""Pallas flash attention numerics vs dense reference (interpret mode on the
CPU mesh; the compiled path runs on the real chip via bench/verify)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels.flash_attention import flash_attention


def dense_attention(q, k, v, causal):
    d = q.shape[-1]
    sc = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(d)
    if causal:
        s = q.shape[2]
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask, sc, -1e30)
    return jnp.einsum("bhst,bhtv->bhsv", jax.nn.softmax(sc, -1), v)


@pytest.fixture(scope="module")
def qkv():
    rs = np.random.RandomState(0)
    shape = (2, 2, 256, 64)
    return tuple(jnp.asarray(rs.randn(*shape), jnp.float32) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(qkv, causal):
    q, k, v = qkv
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(qkv, causal):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_uneven_blocks():
    """seq not a multiple of 128 uses block size = seq."""
    rs = np.random.RandomState(1)
    q, k, v = (
        jnp.asarray(rs.randn(1, 2, 64, 32), jnp.float32) for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestShardedFlash:
    """shard_map composition (VERDICT round-1 weak #2): flash must run in
    exactly the distributed paths where attention matters."""

    def _mesh(self, shape, names):
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
        return Mesh(devs, names)

    @pytest.mark.parametrize(
        "mesh_shape,names,batch_axes,head_axes",
        [
            ((2,), ("dp",), "dp", None),
            ((2, 2), ("dp", "tp"), "dp", "tp"),
            ((1, 2), ("dp", "tp"), "dp", "tp"),
        ],
    )
    def test_sharded_matches_dense(self, qkv, mesh_shape, names, batch_axes, head_axes):
        from flexflow_tpu.kernels.flash_attention import (
            sharded_flash_attention,
            sharded_flash_supported,
        )

        if len(jax.devices()) < int(np.prod(mesh_shape)):
            pytest.skip("needs multi-device")
        q, k, v = qkv  # [2, 2, 256, 64]
        mesh = self._mesh(mesh_shape, names)
        assert sharded_flash_supported(
            q.shape, mesh, batch_axes, head_axes, min_seq=128, interpret=True
        )
        out = sharded_flash_attention(
            q, k, v, mesh, batch_axes, head_axes, interpret=True
        )
        ref = dense_attention(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_sharded_gradients_match_dense(self, qkv):
        from flexflow_tpu.kernels.flash_attention import sharded_flash_attention

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        q, k, v = qkv
        mesh = self._mesh((2,), ("dp",))

        def loss_sharded(q, k, v):
            return jnp.sum(
                sharded_flash_attention(
                    q, k, v, mesh, "dp", None, interpret=True
                )
                ** 2
            )

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, False) ** 2)

        gf = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_local_block_gate(self):
        """The support gate checks the LOCAL block, not the global shape."""
        from flexflow_tpu.kernels.flash_attention import sharded_flash_supported

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        mesh = self._mesh((8,), ("dp",))
        # batch 4 cannot split over 8 dp shards
        assert not sharded_flash_supported(
            (4, 2, 256, 64), mesh, "dp", None, min_seq=128, interpret=True
        )
        # heads 2 cannot split over 4 tp shards
        mesh2 = self._mesh((2, 4), ("dp", "tp"))
        assert not sharded_flash_supported(
            (4, 2, 256, 64), mesh2, "dp", "tp", min_seq=128, interpret=True
        )

    # one attention node under the distributed executor, per plan: the
    # kernel entry it must reach (None: XLA's dense attention) and the route
    # the instance's counter names. heads x d = 128 = embed_dim throughout.
    # id: (attention class kwargs, heads, bias, distinct operands, seq,
    #      batch degree, head degree, entry, route)
    EXECUTOR_CASES = {
        # (a) BERT's node: biased d=64 self-attention, batch-sharded
        "biased_d64_self": (
            {}, 2, True, False, 128, 2, 1,
            "flash_attention_bshf_qkv", "fused_row_sharded"),
        # (b) distinct operands and d=128 take the three-matmul fused row
        "d64_three_operands": (
            {}, 2, True, True, 128, 2, 1,
            "flash_attention_bshf", "fused_row_sharded"),
        "d128_self": (
            {}, 1, False, False, 128, 2, 1,
            "flash_attention_bshf", "fused_row_sharded"),
        # (c) a head-sharded plan keeps the [b, h, s, d] rows kernels
        "head_sharded": (
            {}, 2, False, False, 128, 1, 2,
            "sharded_flash_attention", "rows_sharded"),
        "batch_and_head_sharded": (
            {}, 2, False, False, 128, 2, 2,
            "sharded_flash_attention", "rows_sharded"),
        # (d) causal RingAttentionAttrs, sequence whole, batch-only mesh
        "causal_ring_seq_whole": (
            {"causal": True}, 2, False, False, 128, 2, 1,
            "flash_attention_bshf_qkv", "fused_row_sharded"),
        # (f) a local sequence under the threshold stays dense
        "short_seq": (
            {}, 2, True, False, 64, 2, 1, None, "dense"),
    }

    @pytest.mark.parametrize("case", sorted(EXECUTOR_CASES))
    def test_executor_attention_lowering(self, case, monkeypatch):
        """The searched executor lowers an attention node through the op's
        own dispatch (`_mha_forward`) under the node's declared mesh: the
        entry one chip would take, per shard, with the output and every
        gradient equal to the single-device op's."""
        import flexflow_tpu.kernels.flash_attention as fa
        from flexflow_tpu.kernels.ops import _mha_forward
        from flexflow_tpu.op_attrs.ops import (
            MultiHeadAttentionAttrs,
            RingAttentionAttrs,
        )
        from flexflow_tpu.parallel import MachineMesh, pcg_shardings
        from flexflow_tpu.parallel.executor import (
            attention_routes,
            param_key,
            pcg_forward_interpreter,
        )
        from flexflow_tpu.pcg.parallel_computation_graph_builder import (
            ParallelComputationGraphBuilder,
        )
        from test_parallel_lowering import pts

        (kw, heads, bias, distinct, seq, dp, tp, entry, route) = (
            self.EXECUTOR_CASES[case]
        )
        if len(jax.devices()) < dp * tp:
            pytest.skip("needs multi-device")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")
        calls = []
        for name in (
            "flash_attention_bshf", "flash_attention_bshf_qkv",
            "sharded_flash_attention",
        ):
            def spy(*a, _name=name, _orig=getattr(fa, name), **k):
                calls.append(_name)
                return _orig(*a, **k)

            monkeypatch.setattr(fa, name, spy)

        e, batch = 128, 4
        cls = RingAttentionAttrs if kw else MultiHeadAttentionAttrs
        attrs = cls(e, heads, kdim=e // heads, vdim=e // heads, bias=bias, **kw)
        b = ParallelComputationGraphBuilder()
        names = ["q", "k", "v"] if distinct else ["x"]
        ins = [
            b.create_input_tensor(pts([batch, seq, e], [dp, 1, 1]), name=n)
            for n in names
        ]
        ops = [b.parallel_replicate(t, tp) if tp > 1 else t for t in ins]
        (out,) = b.add_layer(attrs, ops if distinct else ops * 3, [], "attn")
        if tp > 1:
            out = b.parallel_reduce(out, tp)
        pcg = b.graph
        mm = MachineMesh.for_devices(dp * tp)
        shardings = pcg_shardings(pcg, mm)
        scope = "ff.ring_attention.attn" if kw else "ff.mha.attn"
        assert attention_routes(pcg, shardings, mm.mesh) == {scope: route}

        rs = np.random.RandomState(5)
        attn = next(
            n for n in pcg.topological_ordering()
            if pcg.layer_attrs(n).name == "attn"
        )
        weights = [t.node for t in pcg.inputs_of(attn)[3:]]
        params = {
            param_key(n): jnp.asarray(
                0.1 * rs.randn(*pcg.tensor_shape(pcg.outputs_of(n)[0]).sizes()),
                jnp.float32,
            )
            for n in weights
        }
        inputs = {
            n: jnp.asarray(rs.randn(batch, seq, e), jnp.float32) for n in names
        }

        def lowered(params, inputs):
            env = pcg_forward_interpreter(
                pcg, params, inputs, shardings, mesh=mm.mesh
            )
            return env[out]

        def single(params, inputs):
            q, k, v = (inputs[n] for n in (names if distinct else names * 3))
            w = [params[param_key(n)] for n in weights]
            res = _mha_forward(
                attrs, q, k, v, w[0], w[1] if bias else None,
                causal=kw.get("causal", False),
            )
            return res + w[2] if bias else res

        def with_grads(f):
            loss = lambda p, i: jnp.sum(f(p, i) ** 2)
            return jax.jit(
                lambda p, i: (f(p, i), jax.grad(loss, argnums=(0, 1))(p, i))
            )

        got = with_grads(lowered)(params, inputs)
        assert sorted(set(calls)) == ([entry] if entry else [])
        calls.clear()
        want = with_grads(single)(params, inputs)
        assert calls == []  # the reference is XLA's dense attention
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(want[0]), atol=1e-5
        )
        for a, w in zip(
            jax.tree_util.tree_leaves(got[1]),
            jax.tree_util.tree_leaves(want[1]),
        ):
            # the bias gradients sum over every position: up to ~1e3 here
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w), atol=2e-4, rtol=1e-5
            )

    def test_data_parallel_backend_takes_the_fused_row(self, monkeypatch):
        """(e) DataParallelTrainingInstance declares a batch-only mesh, so
        its attention takes the same per-shard fused-row dispatch."""
        import flexflow_tpu.kernels.flash_attention as fa
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
        from flexflow_tpu.parallel.data_parallel import (
            DataParallelTrainingInstance,
        )

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")
        calls = []
        orig = fa.flash_attention_bshf_qkv

        def spy(*a, **kw):
            calls.append(kw.get("interpret"))
            return orig(*a, **kw)

        monkeypatch.setattr(fa, "flash_attention_bshf_qkv", spy)
        monkeypatch.setattr(
            fa, "sharded_flash_attention",
            lambda *a, **kw: pytest.fail("took the [b, h, s, d] layout"),
        )
        cfg = FFConfig(
            batch_size=4, epochs=1, seed=0, max_devices=2,
            only_data_parallel=True,
        )
        m = FFModel(cfg)
        x = m.create_tensor([4, 128, 128], name="x")
        t = m.multihead_attention(x, x, x, 128, 2)
        t = m.dense(t, 8, use_bias=False)
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        assert isinstance(m.instance, DataParallelTrainingInstance)
        rs = np.random.RandomState(0)
        xs = rs.randn(4, 128, 128).astype(np.float32)
        ys = rs.randint(0, 8, (4, 128))
        m.fit(xs, ys, epochs=1, verbose=False)
        assert calls and all(calls), calls

    def test_distributed_executor_uses_sharded_flash(self, monkeypatch):
        """End-to-end: a DP-sharded transformer train step through the
        distributed executor hits the shard_mapped Pallas kernel (the
        round-1 no_flash guard disabled it everywhere multi-device). Heads
        of 8 lanes are no fused row: the plan takes the [b, h, s, d] entry."""
        import flexflow_tpu.kernels.flash_attention as fa
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")

        calls = []
        orig = fa.sharded_flash_attention

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(fa, "sharded_flash_attention", spy)

        cfg = FFConfig(batch_size=8, epochs=1, seed=0)
        m = FFModel(cfg)
        x = m.create_tensor([8, 128, 32], name="x")
        t = m.multihead_attention(x, x, x, 32, 4)
        t = m.dense(t, 8, use_bias=False)
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        rs = np.random.RandomState(0)
        xs = rs.randn(8, 128, 32).astype(np.float32)
        ys = rs.randint(0, 8, (8, 128))
        m.fit(xs, ys, epochs=1, verbose=False)
        assert calls, "distributed step never reached the sharded flash path"


# -- bshf ([b, s, h*d] seq-major) layout variant ----------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshf_matches_dense(causal):
    from flexflow_tpu.kernels.flash_attention import flash_attention_bshf

    rs = np.random.RandomState(2)
    b, h, s, d = 2, 2, 256, 128
    q4, k4, v4 = (
        jnp.asarray(rs.randn(b, h, s, d), jnp.float32) for _ in range(3)
    )
    # [b,h,s,d] -> [b,s,h*d]
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)
    out = flash_attention_bshf(
        to_bshf(q4), to_bshf(k4), to_bshf(v4), h, causal=causal, interpret=True
    )
    ref = to_bshf(dense_attention(q4, k4, v4, causal))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshf_gradients_match_dense(causal):
    from flexflow_tpu.kernels.flash_attention import flash_attention_bshf

    rs = np.random.RandomState(3)
    b, h, s, d = 1, 2, 256, 128
    q4, k4, v4 = (
        jnp.asarray(rs.randn(b, h, s, d), jnp.float32) for _ in range(3)
    )
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)

    def loss_bshf(q, k, v):
        return jnp.sum(
            flash_attention_bshf(
                to_bshf(q), to_bshf(k), to_bshf(v), h,
                causal=causal, interpret=True,
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) ** 2)

    gf = jax.grad(loss_bshf, argnums=(0, 1, 2))(q4, k4, v4)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q4, k4, v4)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_mha_project_qkv_bshf_matches_reference_layout():
    """The fused-head projection path must agree with mha_project_qkv."""
    from flexflow_tpu.kernels.ops import mha_project_qkv, mha_project_qkv_bshf
    from flexflow_tpu.op_attrs.ops.attention import MultiHeadAttentionAttrs

    e, H = 64, 4
    attrs = MultiHeadAttentionAttrs(
        embed_dim=e, num_heads=H, kdim=e, vdim=e, dropout=0.0, bias=True,
        add_bias_kv=False, add_zero_attn=False,
    )
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, 8, e), jnp.float32)
    w = jnp.asarray(rs.randn(e * kd * 2 + e * vd + vd * e, H), jnp.float32)
    bias = jnp.asarray(rs.randn(3 * kd), jnp.float32)

    qp, kp, vp, wo = mha_project_qkv(attrs, x, x, x, w, bias)
    qf, kf, vf, wo2 = mha_project_qkv_bshf(attrs, x, x, x, w, bias)
    b, s = x.shape[0], x.shape[1]
    to_bshf = lambda t: jnp.transpose(t, (0, 2, 1, 3)).reshape(b, s, -1)
    np.testing.assert_allclose(np.asarray(to_bshf(qp)), np.asarray(qf), atol=1e-5)
    np.testing.assert_allclose(np.asarray(to_bshf(kp)), np.asarray(kf), atol=1e-5)
    np.testing.assert_allclose(np.asarray(to_bshf(vp)), np.asarray(vf), atol=1e-5)
    # wo [vd, e, H] -> [H*vd, e]
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(wo, (2, 0, 1)).reshape(H * vd, e)),
        np.asarray(wo2),
        atol=1e-6,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshf_split_backward_matches_dense(causal):
    """Explicit small blocks force the split dq/dkv kernels (the default
    single-tile config takes the fused backward)."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_bshf

    rs = np.random.RandomState(5)
    b, h, s, d = 1, 2, 256, 128
    q4, k4, v4 = (
        jnp.asarray(rs.randn(b, h, s, d), jnp.float32) for _ in range(3)
    )
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)

    def loss_bshf(q, k, v):
        return jnp.sum(
            flash_attention_bshf(
                to_bshf(q), to_bshf(k), to_bshf(v), h, causal=causal,
                block_q=128, block_k=128, interpret=True,
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) ** 2)

    gf = jax.grad(loss_bshf, argnums=(0, 1, 2))(q4, k4, v4)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q4, k4, v4)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_flash_bshf_onepass_backward_matches_dense():
    """The non-causal one-pass tiled backward (dq/dk/dv from one tile
    visit, dq accumulated in VMEM scratch, dk/dv via partials): small
    explicit blocks with nq == 2 exercise both the accumulation and the
    partial reduction."""
    from flexflow_tpu.kernels import flash_attention as fa

    rs = np.random.RandomState(11)
    b, h, s, d = 1, 2, 256, 128
    q, k, v = (
        jnp.asarray(rs.randn(b, s, h * d), jnp.float32) for _ in range(3)
    )

    def loss(q, k, v):
        o, lse = fa._fwd_bshf(q, k, v, h, False, 128, 128, True)
        do = jnp.ones_like(o)
        return o, lse, do

    o, lse, do = loss(q, k, v)
    got = fa._bwd_bshf_onepass(q, k, v, o, lse, do, h, False, 128, 128, True)
    want = fa._bwd_bshf(q, k, v, o, lse, do, h, False, 128, 128, True)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_flash_bshf_bf16_backward_error_bounded():
    """bf16 training path precision pin: the backward computes
    p * bf16(dp - delta) (the round-5 pass-minimizing form); its gradients
    must stay within bf16-roundoff distance of the f32 dense reference so
    the precision tradeoff is measured, not assumed."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_bshf

    rs = np.random.RandomState(13)
    b, h, s, d = 1, 2, 256, 128
    # compare on IDENTICAL bf16-rounded inputs so the measured error is the
    # kernel's arithmetic (bf16 probs + bf16 dp-delta), not input rounding
    qf, kf, vf = (
        rs.randn(b, h, s, d).astype(np.float32).astype(jnp.bfloat16)
        .astype(np.float32)
        for _ in range(3)
    )
    to_bshf = lambda x: jnp.transpose(
        jnp.asarray(x), (0, 2, 1, 3)
    ).reshape(b, s, h * d)

    def loss_bf16(q, k, v):
        out = flash_attention_bshf(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), h, interpret=True,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, False) ** 2)

    gf = jax.grad(loss_bf16, argnums=(0, 1, 2))(
        to_bshf(qf), to_bshf(kf), to_bshf(vf)
    )
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf)
    )
    for a, b_ in zip(gf, gd):
        b_bshf = np.asarray(
            jnp.transpose(b_, (0, 2, 1, 3)).reshape(b, s, h * d)
        )
        a = np.asarray(a, dtype=np.float32)
        # norm-relative error: pointwise max-relative is dominated by
        # near-zero elements and does not predict training behavior
        rel = np.linalg.norm(a - b_bshf) / np.linalg.norm(b_bshf)
        assert rel < 0.02, rel  # bf16 probs + bf16 (dp - delta) roundoff


@pytest.mark.parametrize("s", [256, 384, 512])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshf_head_pair_matches_dense(causal, s):
    """d=64 head-PAIR path (two heads per 128-lane block): forward and
    backward must match dense attention — the reference TransformerConfig
    default (num_heads=16, d=64) rides these kernels. 512 is the seq-512
    cells' length: the backward reads the lse of the forward that holds p
    stationary (_fwd_kernel_pair), in chunks of queries when causal (of
    128 at 384, which 256 does not divide)."""
    from flexflow_tpu.kernels.flash_attention import (
        bshf_pair_supported,
        flash_attention_bshf,
    )

    rs = np.random.RandomState(7)
    b, h, d = 2, 4, 64
    assert bshf_pair_supported(h, d, s)
    q4, k4, v4 = (
        jnp.asarray(rs.randn(b, h, s, d), jnp.float32) for _ in range(3)
    )
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)

    def loss_pair(q, k, v):
        return jnp.sum(
            flash_attention_bshf(
                to_bshf(q), to_bshf(k), to_bshf(v), h, causal=causal,
                interpret=True,
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) ** 2)

    out = flash_attention_bshf(
        to_bshf(q4), to_bshf(k4), to_bshf(v4), h, causal=causal,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(to_bshf(dense_attention(q4, k4, v4, causal))),
        atol=2e-5,
    )
    gp = jax.grad(loss_pair, argnums=(0, 1, 2))(q4, k4, v4)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q4, k4, v4)
    for a, b_ in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


@pytest.mark.parametrize("s", [256, 384, 512])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshf_qkv_fused_matches_pair(causal, s):
    """Fused-QKV pair entry (one interleaved [b, s, 3f] operand, one fused
    dqkv gradient) must match the three-operand pair path bit-for-bit in
    forward and, after de-interleaving, in gradient (through
    _flash_bshf_qkv, whose backward consumes its forward's lse)."""
    from flexflow_tpu.kernels.flash_attention import (
        flash_attention_bshf,
        flash_attention_bshf_qkv,
    )

    rs = np.random.RandomState(11)
    b, h, d = 2, 4, 64
    f = h * d
    q, k, v = (
        jnp.asarray(rs.randn(b, s, f), jnp.float32) for _ in range(3)
    )

    def interleave(q, k, v):
        return jnp.stack(
            [x.reshape(b, s, f // 128, 128) for x in (q, k, v)], axis=3
        ).reshape(b, s, 3 * f)

    qkv = interleave(q, k, v)
    out_pair = flash_attention_bshf(q, k, v, h, causal=causal, interpret=True)
    out_qkv = flash_attention_bshf_qkv(qkv, h, causal=causal, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pair), np.asarray(out_qkv))

    def loss_pair(q, k, v):
        return jnp.sum(
            flash_attention_bshf(q, k, v, h, causal=causal, interpret=True)
            ** 2
        )

    def loss_qkv(q, k, v):
        return jnp.sum(
            flash_attention_bshf_qkv(
                interleave(q, k, v), h, causal=causal, interpret=True
            )
            ** 2
        )

    gp = jax.grad(loss_pair, argnums=(0, 1, 2))(q, k, v)
    gq = jax.grad(loss_qkv, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gq):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-6
        )


@pytest.mark.parametrize("with_bias", [False, True])
def test_mha_fused_qkv_projection_matches_bshf(with_bias):
    """mha_project_qkv_bshf_fused's single interleaved matmul must produce
    exactly the interleaving of the three bshf projections (weight and
    bias lane order is the part the kernels cannot check)."""
    from flexflow_tpu.kernels.ops import (
        mha_project_qkv_bshf,
        mha_project_qkv_bshf_fused,
    )
    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs

    rs = np.random.RandomState(3)
    b, s, e, H = 2, 16, 128, 16
    attrs = MultiHeadAttentionAttrs(
        embed_dim=e, num_heads=H, bias=with_bias,
    )
    kd = attrs.q_proj_size  # 8; f = H*kd = 128 satisfies the lane gate
    # packed reference layout: [q|k|v|o] rows x H columns
    # (unpack_mha_weights)
    rows = e * kd * 3 + kd * e
    weight = jnp.asarray(rs.randn(rows, H), jnp.float32)
    bias = (
        jnp.asarray(rs.randn(3 * kd), jnp.float32) if with_bias else None
    )
    x = jnp.asarray(rs.randn(b, s, e), jnp.float32)
    qp, kp, vp, wo2 = mha_project_qkv_bshf(attrs, x, x, x, weight, bias)
    qkv, wo2_f = mha_project_qkv_bshf_fused(attrs, x, weight, bias)
    f = H * kd
    expect = jnp.stack(
        [t.reshape(b, s, f // 128, 128) for t in (qp, kp, vp)], axis=3
    ).reshape(b, s, 3 * f)
    # one [e, 3f] matmul vs three [e, f] matmuls: same math, different f32
    # summation order
    np.testing.assert_allclose(
        np.asarray(qkv), np.asarray(expect), rtol=1e-5, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(wo2), np.asarray(wo2_f))


def test_bshf_pair_gate():
    from flexflow_tpu.kernels.flash_attention import bshf_pair_supported

    assert bshf_pair_supported(16, 64, 512)
    assert not bshf_pair_supported(15, 64, 512)  # odd heads
    assert not bshf_pair_supported(16, 32, 512)  # d != 64
    assert not bshf_pair_supported(16, 64, 2048)  # exceeds fused-bwd tile


# -- the causal tile schedule of the d % 128 == 0 bshf kernels ---------------


def _brute_force_schedule(s, block_q, block_k):
    """(live, diagonal, total) by looking at every (row, column) of every
    tile: live holds a pair the mask keeps, diagonal also one it drops."""
    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    keep = rows >= cols
    live = diagonal = 0
    for i in range(s // block_q):
        for j in range(s // block_k):
            tile = keep[
                i * block_q:(i + 1) * block_q, j * block_k:(j + 1) * block_k
            ]
            live += bool(tile.any())
            diagonal += bool(tile.any() and not tile.all())
    return live, diagonal, (s // block_q) * (s // block_k)


@pytest.mark.parametrize(
    "s,block_q,block_k,want",
    [
        (2048, 512, 512, (10, 4, 16)),
        (4096, 512, 512, (36, 8, 64)),
        (512, 128, 128, (10, 4, 16)),
        (512, 256, 128, (6, 4, 8)),
        (512, 128, 256, (6, 4, 8)),
        (1024, 512, 512, (3, 2, 4)),
        (256, 256, 128, (2, 2, 2)),
        (4096, 1024, 256, (40, 16, 64)),
    ],
)
def test_causal_tile_schedule_counts_what_the_mask_keeps(
    s, block_q, block_k, want
):
    from flexflow_tpu.kernels import flash_attention as fa

    assert fa.causal_tile_schedule(s, block_q, block_k) == want
    assert _brute_force_schedule(s, block_q, block_k) == want
    # the backward walks the same tiles by k block: its ranges are the
    # transpose of the forward's
    by_q = {
        (i, j, j >= full)
        for i in range(s // block_q)
        for full, live in [fa._causal_k_range(i, block_q, block_k)]
        for j in range(live)
    }
    by_k = {
        (i, j, i < full)
        for j in range(s // block_k)
        for start, full in [fa._causal_q_range(j, block_q, block_k)]
        for i in range(start, s // block_q)
    }
    assert by_q == by_k and len(by_q) == want[0]


# id: (batch, seq, block_q, block_k, form): explicit blocks, or None for the
# rule the dispatch reads from (causal, s, d); `form` names what differs from
# two float32 heads of 128 | 128 with a key/value head each at the default
# scale: heads, kv (key/value heads), dk, dv, scale, dtype, long_rows (the
# scope's budget set to 0: one row a program under the 64 MB limit), window
# (keys a query sees), fold (batch rows a forward program must take), node
# (the call goes through `kernels/ops._mha_forward` on heads of 64, which pads
# them to 128 lanes at the key/value count).
CAUSAL_SCHEDULE_CASES = {
    # s = 4 x block: the first k block is full (unmasked) for every q block
    # but the first, and the last q block walks three full tiles
    "square_blocks": (1, 512, 128, 128, {}),
    "block_q_twice_block_k": (3, 512, 256, 128, {}),
    "block_k_twice_block_q": (1, 512, 128, 256, {}),
    # every live tile straddles the diagonal: the masked loop alone
    "only_diagonal_tiles": (1, 256, 256, 128, {}),
    # the default blocks above the single-tile limit: 512 x 512, 3 of 4 live
    "default_blocks": (1, 1024, None, None, {}),
    # the forms the eight cells run (`causal_plan`): a padded latent key
    # beside its value at the true width's scale (Kimi), ...
    "wide_key_with_scale": (
        1, 1024, None, None, {"dk": 256, "scale": 192 ** -0.5}),
    # ... the same as one long row a program (JoyAI), ...
    "wide_key_long_rows": (
        1, 1024, None, None,
        {"dk": 256, "scale": 192 ** -0.5, "long_rows": True}),
    # ... batch rows folded into a program (cgpt, OLMoE: 2 of 4), ...
    "folded_batch_rows": (4, 1024, None, None, {}),
    # ... 4 heads of 256 over 2 read in place (Qwen3-Next), ...
    "grouped_in_place": (
        1, 1024, None, None,
        {"heads": 4, "kv": 2, "dk": 256, "dv": 256, "long_rows": True}),
    # ... 4 heads of 128 over 2 read in place in rows that fit the default
    # scope, under the folded names (TwoTower, Super) ...
    "grouped_folded_rows": (1, 1024, None, None, {"heads": 4, "kv": 2}),
    # ... with two batch rows a forward program (no cell: `fold` 2, group 2)
    "grouped_folded_batch": (
        4, 1024, None, None, {"heads": 4, "kv": 2, "fold": 2}),
    # ... under a window shorter than the rows (Mellum2's three) ...
    "grouped_folded_band": (
        1, 1024, None, None, {"heads": 4, "kv": 2, "window": 300}),
    # ... and heads of 64 padded to 128 lanes at the key/value count (LFM2)
    "grouped_padded_heads_of_64": (
        1, 1024, 512, 512,
        {"heads": 4, "kv": 2, "dk": 64, "dv": 64, "node": True}),
    # ... six tiles a q block: five unmasked ones before the diagonal's
    "many_tiles": (1, 768, 128, 128, {}),
    # ... a k tile that is no multiple of _FWD_KEY_CHUNK: three chunks of 128
    "key_block_of_384": (1, 768, 128, 384, {}),
    # ... and bf16 operands (every cell), against the float32 reference
    "bf16_inputs": (2, 1024, None, None, {"dtype": jnp.bfloat16}),
}


@pytest.fixture
def interpret_node_kernels(monkeypatch, entered):
    """Called, steers `kernels/ops`' gates and its fused-row entry to the
    Pallas interpreter until the test ends: a node on the CPU then takes the
    kernels a chip would."""
    from flexflow_tpu.kernels import flash_attention as fa

    def steer():
        entered(context.described_tpu())
        monkeypatch.setattr(
            fa, "flash_attention_bshf",
            functools.partial(fa.flash_attention_bshf, interpret=True),
        )

    return steer


def _attention_node_core(rows, h, kv, dk, dv, window):
    """`kernels/ops._mha_forward` as nothing but its core: a causal
    grouped-query node whose four projections are identities (exact in
    float32), on fused rows q [b, s, h * dk], k [b, s, kv * dk] and v
    [b, s, kv * dv] -> [b, s, h * dv]."""
    from flexflow_tpu.kernels.ops import _mha_forward
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

    attrs = RingAttentionAttrs(
        h * dv, h, dk, dv, causal=True, num_kv_heads=kv, window=window
    )
    weight = jnp.concatenate([
        jnp.eye(n, dtype=rows[0].dtype).reshape(-1)
        for n in (h * dk, kv * dk, kv * dv, h * dv)
    ])
    return _mha_forward(attrs, *rows, weight, causal=True)


def _causal_case(case, monkeypatch, interpret_node_kernels):
    """A CAUSAL_SCHEDULE_CASES entry as a namespace: `flash`, the entry on
    fused rows in interpret mode, and `dense`, the float32 reference, both
    on `qkv`, float32 [b, heads, s, d] operands already rounded to the
    case's `dtype`; `scores`, the reference's masked scores; `to_bshf`;
    `plan`, the call's CausalPlan; `scale`, the stated scale or None."""
    import types

    from flexflow_tpu.kernels import flash_attention as fa

    b, s, block_q, block_k, form = CAUSAL_SCHEDULE_CASES[case]
    h, kv = form.get("heads", 2), form.get("kv", form.get("heads", 2))
    dk, dv = form.get("dk", 128), form.get("dv", 128)
    scale = form.get("scale", dk ** -0.5)
    dtype = form.get("dtype", jnp.float32)
    window, node = form.get("window"), form.get("node", False)
    if form.get("long_rows"):
        monkeypatch.setattr(fa, "_SCOPED_ROWS_BUDGET", 0)
    if node:
        # a node names no blocks: the sweep's knobs make 1,024 positions two
        # tiles (more than the head-pair kernels' one)
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_Q", str(block_q))
        monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_K", str(block_k))
        interpret_node_kernels()
        block_q = block_k = None
    plan = fa.causal_plan(
        b, s, h, kv, max(dk, 128), max(dv, 128), jnp.dtype(dtype).itemsize,
        block_q, block_k, window,
    )
    # a key as wide as its value is read in place by its group, whatever the
    # rows' length; a wide key has a key/value head a query head
    assert plan.group == (h // kv if dk == dv else 1)
    assert plan.fold == form.get("fold", plan.fold)
    assert plan.fwd_name.startswith(
        "flash_fwd_causal_grouped" if form.get("long_rows") and dk == dv
        else "flash_fwd_causal_wide_key" if form.get("long_rows")
        else "flash_fwd_causal_bshf"
    )
    rs = np.random.RandomState(17)
    q, k, v = (
        jnp.asarray(rs.randn(b, n, s, d), dtype).astype(jnp.float32)
        for n, d in ((h, dk), (kv, dk), (kv, dv))
    )
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, -1)
    blocks = (
        {} if block_q is None else {"block_q": block_q, "block_k": block_k}
    )

    def flash(q, k, v):
        rows = [to_bshf(x).astype(dtype) for x in (q, k, v)]
        if node:
            return _attention_node_core(rows, h, kv, dk, dv, window)
        return fa.flash_attention_bshf(
            *rows, h, causal=True,
            interpret=True, num_kv_heads=kv, scale=form.get("scale"),
            window=window, **blocks,
        ).astype(jnp.float32)

    def scores(q, k):
        sc = jnp.einsum(
            "bhsd,bhtd->bhst", q, jnp.repeat(k, h // kv, axis=1)
        ) * scale
        ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        mask = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
        return jnp.where(mask, sc, -1e30)

    def dense(q, k, v):
        return to_bshf(jnp.einsum(
            "bhst,bhtv->bhsv", jax.nn.softmax(scores(q, k), -1),
            jnp.repeat(v, h // kv, axis=1),
        ))

    return types.SimpleNamespace(
        flash=flash, dense=dense, scores=scores, qkv=(q, k, v), dtype=dtype,
        to_bshf=to_bshf, plan=plan, scale=form.get("scale"),
    )


@pytest.mark.parametrize("case", sorted(CAUSAL_SCHEDULE_CASES))
def test_flash_bshf_causal_schedule_matches_dense(
    case, monkeypatch, interpret_node_kernels
):
    """Forward and the three gradients of the causal d % 128 bshf entry
    against dense attention, over every branch of the tile schedule (dead
    tiles skipped, diagonal tiles masked, full tiles unmasked, one visit a
    tile in the backward) and every form of `causal_plan`."""
    from test_step_scopes import pallas_eqns

    c = _causal_case(case, monkeypatch, interpret_node_kernels)
    flash, dense, qkv = c.flash, c.dense, c.qkv
    # k and v reach every kernel with the heads they came with (a padded
    # head of 64 is 128 lanes wide): nobody wrote them out a query head
    (b, _, s, _), (kv, dk), dv = qkv[0].shape, qkv[1].shape[1::2], qkv[2].shape[3]
    grad = jax.grad(lambda *xs: jnp.sum(flash(*xs)), argnums=(0, 1, 2))
    grouped = c.plan.group > 1
    for eqn in pallas_eqns(jax.make_jaxpr(grad)(*qkv).jaxpr) if grouped else ():
        if "delta" not in eqn.params["name"]:
            assert [v.aval.shape for v in eqn.invars[1:3]] == [
                (b, s, kv * max(dk, 128)), (b, s, kv * max(dv, 128))
            ], eqn.params["name"]
    # bf16: the probabilities and the output are rounded to 8 bits
    fwd_tol, bwd_tol = (
        (1e-5, 2e-4) if c.dtype == jnp.float32 else (2e-2, 1e-1)
    )
    np.testing.assert_allclose(
        np.asarray(flash(*qkv)), np.asarray(dense(*qkv)), atol=fwd_tol,
    )
    gf = jax.grad(
        lambda q, k, v: jnp.sum(flash(q, k, v) ** 2), argnums=(0, 1, 2)
    )(*qkv)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense(q, k, v) ** 2), argnums=(0, 1, 2)
    )(*qkv)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=bwd_tol)


def test_grouped_heads_of_64_in_one_pair_tile_are_repeated_for_the_pair_kernels(
    interpret_node_kernels,
):
    """1,024 positions of heads of 64 are ONE tile of the head-pair kernels
    and would be two of the causal schedule's on padded heads: the node
    takes the pair kernels, which read a head a query head, so its 2
    key/value heads are written out for the 4 query heads and no plan's
    group is read. Forward and gradients against dense attention."""
    from test_step_scopes import pallas_eqns

    interpret_node_kernels()
    b, s, h, kv, d = 1, 1024, 4, 2, 64
    rs = np.random.RandomState(5)
    q, k, v = (
        jnp.asarray(rs.randn(b, n, s, d), jnp.float32) for n in (h, kv, kv)
    )
    to_bshf = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, -1)

    def node(q, k, v):
        return _attention_node_core(
            [to_bshf(x) for x in (q, k, v)], h, kv, d, d, None
        )

    def dense(q, k, v):
        k, v = (jnp.repeat(x, h // kv, axis=1) for x in (k, v))
        return to_bshf(dense_attention(q, k, v, True))

    names = {
        eqn.params["name"] for eqn in pallas_eqns(jax.make_jaxpr(node)(q, k, v).jaxpr)
    }
    assert names == {"flash_fwd_pair"}, names
    np.testing.assert_allclose(node(q, k, v), dense(q, k, v), atol=1e-5)
    gf = jax.grad(lambda *x: jnp.sum(node(*x) ** 2), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *x: jnp.sum(dense(*x) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


@pytest.mark.parametrize(
    "case", ["default_blocks", "folded_batch_rows", "grouped_in_place",
             "wide_key_long_rows", "block_q_twice_block_k",
             "grouped_folded_batch", "grouped_folded_band"],
)
def test_causal_forward_lse_is_the_dense_log_sum_exp(
    case, monkeypatch, interpret_node_kernels
):
    """The forward's second output, row by row: log2 of the sum over a
    query's keys of 2 ** (score * log2(e)), [b, heads, 1, s] float32. The
    backward rebuilds every probability from it, and it leaves the kernel
    as the `[1, block_q]` row the transposed softmax carries."""
    from flexflow_tpu.kernels import flash_attention as fa

    c = _causal_case(case, monkeypatch, interpret_node_kernels)
    q, k, v = c.qkv
    (b, h, s, _), kv = q.shape, k.shape[1]
    assert c.plan.group == h // kv  # k and v as they lie, a wide key's too
    _, lse = fa._fwd_causal(
        *(c.to_bshf(x) for x in (q, k, v)), h, c.plan, True, c.scale
    )
    want = jax.scipy.special.logsumexp(c.scores(q, k), axis=-1)
    assert lse.shape == (b, h, 1, s) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(lse[:, :, 0, :]), np.asarray(want) * fa.LOG2E, atol=1e-5,
    )


def _pallas_calls(jaxpr):
    """(name, grid, block shapes) of every pallas_call in a jaxpr; a
    squeezed block dim reads None."""
    from test_step_scopes import pallas_eqns

    return [
        (
            eqn.params["name"],
            tuple(eqn.params["grid_mapping"].grid),
            tuple(
                tuple(
                    dim if isinstance(dim, int)
                    else getattr(dim, "block_size", None)
                    for dim in block.block_shape
                )
                for block in eqn.params["grid_mapping"].block_mappings
            ),
        )
        for eqn in pallas_eqns(jaxpr)
    ]


_ROW, _COL = (None, 2048, 128), (None, 512, 128)
_STAT = (None, None, 1, 2048)
# id: (entry, operand shape, heads, causal, the Pallas calls of forward +
# backward). The first two are literals read off the parent of PR 29 (commit
# 2fb166c) and pin what the causal tile schedule must not change.
DISPATCH_CASES = {
    "noncausal_d128_s2048": (
        "flash_attention_bshf", (4, 2048, 2048), 16, False,
        [
            ("flash_fwd_bshf", (2, 16, 8),  # single k block: bk = s
             ((2, 256, 128), (2, 2048, 128), (2, 2048, 128), (2, 256, 128),
              (2, None, 1, 256))),
            ("flash_delta_bshf", (2, 16),
             ((2, 2048, 128), (2, 2048, 128), (2, None, 1, 2048))),
            ("flash_bwd_onepass_bshf", (4, 16, 1, 4),
             (_ROW, _COL, _COL, _ROW, _STAT, _STAT, _ROW,
              (None, None, 512, 128), (None, None, 512, 128))),
        ],
    ),
    "pair_d64_s512": (
        "flash_attention_bshf_qkv", (16, 512, 3072), 16, False,
        [
            ("flash_fwd_pair_qkv", (4, 8, 1),
             ((4, 512, 128),) * 4 + ((4, 2, 1, 512),)),
            ("flash_bwd_fused_pair_qkv", (4, 8),
             ((4, 512, 128),) * 5 + ((4, 2, 1, 512), (4, 512, 384))),
        ],
    ),
    # 512 x 512 blocks: 10 live tiles of 16; dq's row and q / do resident
    "causal_d128_s2048": (
        "flash_attention_bshf", (4, 2048, 2048), 16, True,
        [
            ("flash_fwd_causal_bshf", (2, 16, 4),
             ((2, 512, 128), (2, 2048, 128), (2, 2048, 128), (2, 512, 128),
              (2, None, 1, 512))),
            ("flash_delta_bshf", (2, 16),
             ((2, 2048, 128), (2, 2048, 128), (2, None, 1, 2048))),
            ("flash_bwd_causal_bshf", (4, 16, 4),
             (_ROW, _COL, _COL, _ROW, _STAT, _STAT, _ROW, _COL, _COL)),
        ],
    ),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_flash_bshf_dispatch_is_pinned(case):
    """Which kernels a call lowers to, on which grid and blocks, at the
    benchmark's shapes (bf16, traced only): the non-causal d=128 call and
    the d=64 pair call as on the parent; the causal d=128 call on the tile
    schedule, through neither half of the dq/dkv kernel pair."""
    from flexflow_tpu.kernels import flash_attention as fa

    entry, shape, heads, causal, want = DISPATCH_CASES[case]
    operand = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    args = (operand,) if entry.endswith("_qkv") else (operand,) * 3

    def loss(*xs):
        out = getattr(fa, entry)(*xs, heads, causal=causal, interpret=True)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    found = _pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr)
    assert found == want
    if causal:
        assert not {"flash_bwd_dq_bshf", "flash_bwd_dkv_bshf"} & {
            name for name, _, _ in found
        }


_BSHF = ("flash_fwd_causal_bshf", "flash_bwd_causal_bshf", "flash_delta_bshf")
_GROUPED = (
    "flash_fwd_causal_grouped", "flash_bwd_causal_grouped",
    "flash_delta_grouped",
)
_LIMIT = 64 * 1024 * 1024
# id: (where the shape is read, then what the plan must say: supported, the
# forward's batch fold and vmem limit, the group read in place, the delta
# kernel's block, the three kernel names). The shape is a cell's
# (configuration, job) under benchmark/ or, for the boundary shapes the old
# predicates' tests had, (b, s, heads, key/value heads, dk, dv[, window]). The
# answers were written down from the PARENT of PR 55 (commit 5ee988b) before
# there was a plan: a script over its five predicates (`wide_key_supported`,
# `wide_key_rows_exceed_scope`, `causal_rows_exceed_scope`,
# `mha_reads_kv_in_place`'s conjunction, `_batch_block`) said which of its four
# `custom_vjp`s a shape took, and that door's wrappers what they were built
# with. Since PR 63 the group is the node's (heads over key/value heads where
# the key is as wide as the value) at every row length: every other field of
# the rows that fit the scope is still the folded door's.
CAUSAL_PLAN_CASES = {
    "cgpt13b": (("cerebras-gpt-1.3b", "pretrain_s2048_b4_1chip"),
                True, 2, None, 1, None, _BSHF),
    "olmoe": (("olmoe-1b-7b", "pretrain_s4096_b4_1chip"),
              True, 2, None, 1, None, _BSHF),
    # 32 over 2 and 4 over 1 heads of 128: read in place under the folded
    # names (PR 63; the caller wrote them out a query head before)
    "twotower": (("nemotron-twotower-30b-a3b", "pretrain_s4096_b1_1chip"),
                 True, 1, None, 16, None, _BSHF),
    "super": (("nemotron-3-super-120b-a12b", "pretrain_s4096_b1_1chip"),
              True, 1, None, 4, None, _BSHF),
    # 32 heads of 192 -> 256 | 128
    "kimi": (("kimi-linear-48b-a3b", "pretrain_s4096_b1_1chip"),
             True, 1, None, 1, None, _BSHF),
    # 32 over 8 heads of 64, padded to 128 | 128: 8 MB of rows
    "lfm2": (("lfm2-24b-a2b", "pretrain_s8192_b2_1chip"),
             True, 1, None, 4, None, _BSHF),
    # 16 over 2 heads of 256: 16 MB of rows, read in place
    "qwen3next": (("qwen3-next-80b-a3b", "pretrain_s8192_b1_1chip"),
                  True, 1, _LIMIT, 8, 512, _GROUPED),
    # 12 MB of rows, the budget itself: one row under the limit, and the
    # folded form's backward with its whole-row delta
    "joyai": (("joyai-llm-flash", "pretrain_s8192_b1_1chip"),
              True, 1, _LIMIT, 1, None,
              ("flash_fwd_causal_wide_key",) + _BSHF[1:]),
    # rows that fit the scope keep the folded names, no limit and the
    # whole-row delta, and read their group in place all the same
    "qwen3next_at_4096": ((1, 4096, 16, 2, 256, 256),
                          True, 1, None, 8, None, _BSHF),
    "heads_of_128_over_8_at_8192": ((1, 8192, 32, 8, 128, 128),
                                    True, 1, None, 4, None, _BSHF),
    # 32 over 4 heads of 128 at 8,192 positions, three nodes of four banded
    "mellum2_full": ((1, 8192, 32, 4, 128, 128),
                     True, 1, None, 8, None, _BSHF),
    "mellum2_window": ((1, 8192, 32, 4, 128, 128, 1024),
                       True, 1, None, 8, None,
                       ("flash_fwd_causal_bshf_window",
                        "flash_bwd_causal_bshf_window", "flash_delta_bshf")),
    # as many key/value heads as query heads: the grouped form, a group of 1
    "heads_of_256_ungrouped": ((1, 8192, 16, 16, 256, 256),
                               True, 1, _LIMIT, 1, 512, _GROUPED),
    # one causal tile: no such body, whatever the rest says
    "kimi_one_tile": ((1, 512, 32, 32, 256, 128),
                      False, 1, None, 1, None, _BSHF),
}


def _cell_attention_shape(config_name, job_name):
    """(b, s, heads, key/value heads, dk, dv) of a cell's causal attention
    as `kernels/ops` asks the plan for it: a latent key padded to whole
    tiles, heads of 64 padded to 128 lanes."""
    import json
    import os

    from flexflow_tpu.kernels.flash_attention import wide_key_padded

    root = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark")
    with open(os.path.join(root, "configs", config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "jobs", job_name + ".json")) as f:
        job = json.load(f)
    heads = config.get("num_attention_heads", config.get("n_head"))
    kv = config.get("num_key_value_heads", heads)
    if "qk_nope_head_dim" in config:
        dk = wide_key_padded(
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        )
        dv = config["v_head_dim"]
    else:
        hidden = config.get("hidden_size", config.get("n_embd"))
        dk = dv = max(config.get("head_dim") or hidden // heads, 128)
    return job["batch_per_chip"], job["seq"], heads, kv, dk, dv


@pytest.mark.parametrize("case", sorted(CAUSAL_PLAN_CASES))
def test_causal_plan_is_pinned(case):
    """`causal_plan` at the eight cells' attention shapes (bf16) and at the
    boundary shapes: what the parent's four doors were built with."""
    from flexflow_tpu.kernels.flash_attention import causal_plan

    shape, supported, fold, limit, group, delta_block, names = (
        CAUSAL_PLAN_CASES[case]
    )
    if isinstance(shape[0], str):
        shape = _cell_attention_shape(*shape)
    plan = causal_plan(*shape[:6], 2, None, None, *shape[6:])
    assert plan.supported == supported
    if not supported:
        return
    assert (plan.fold, plan.vmem_limit, plan.group, plan.delta_block) == (
        fold, limit, group, delta_block
    )
    assert (plan.fwd_name, plan.bwd_name, plan.delta_name) == names
    blocks = (plan.block_q, plan.block_k, plan.bwd_block_q, plan.bwd_block_k)
    assert blocks == (512,) * 4

"""Pallas kernel validation (interpret mode): shape/dtype sweeps against
the pure-jnp oracles, per the kernel contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_reference
from repro.kernels.grad_quant.ops import quantize, dequantize
from repro.kernels.grad_quant import kernel as QK, ops as GQ, ref as QR


def _fold(x):
    B, S, N, H = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, S, H)


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,dtype", [
        (128, 32, jnp.float32),
        (256, 64, jnp.float32),
        (128, 64, jnp.bfloat16),
        (512, 128, jnp.float32),
    ])
    def test_shape_dtype_sweep(self, S, H, dtype):
        rng = np.random.RandomState(hash((S, H)) % 2**31)
        B, N = 2, 2
        q = jnp.asarray(rng.randn(B, S, N, H), dtype)
        k = jnp.asarray(rng.randn(B, S, N, H), dtype)
        v = jnp.asarray(rng.randn(B, S, N, H), dtype)
        out = flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
        ref = reference_attention(_fold(q), _fold(k), _fold(v))
        ref = ref.reshape(B, N, S, H).transpose(0, 2, 1, 3)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [32, 128])
    def test_sliding_window(self, window):
        rng = np.random.RandomState(7)
        B, S, N, H = 1, 256, 2, 32
        q, k, v = (jnp.asarray(rng.randn(B, S, N, H), jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v, window=window, block_q=64,
                              block_k=64, interpret=True)
        ref = reference_attention(_fold(q), _fold(k), _fold(v),
                                  window=window)
        ref = ref.reshape(B, N, S, H).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_softcap(self):
        rng = np.random.RandomState(8)
        B, S, N, H = 1, 128, 2, 32
        q, k, v = (jnp.asarray(rng.randn(B, S, N, H) * 3, jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v, softcap=10.0, block_q=64,
                              block_k=64, interpret=True)
        ref = reference_attention(_fold(q), _fold(k), _fold(v),
                                  softcap=10.0)
        ref = ref.reshape(B, N, S, H).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_block_size_invariance(self):
        rng = np.random.RandomState(9)
        B, S, N, H = 1, 256, 1, 32
        q, k, v = (jnp.asarray(rng.randn(B, S, N, H), jnp.float32)
                   for _ in range(3))
        o1 = flash_attention(q, k, v, block_q=32, block_k=64,
                             interpret=True)
        o2 = flash_attention(q, k, v, block_q=128, block_k=32,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)


class TestSSD:
    @pytest.mark.parametrize("s,p,n,chunk", [
        (64, 16, 16, 16), (128, 32, 64, 32), (256, 64, 128, 64),
    ])
    def test_vs_reference(self, s, p, n, chunk):
        rng = np.random.RandomState(s + p)
        b, h = 2, 3
        xbar = jnp.asarray(rng.randn(b, s, h, p) * 0.5, jnp.float32)
        log_a = jnp.asarray(-np.abs(rng.randn(b, s, h)) * 0.1, jnp.float32)
        Bm = jnp.asarray(rng.randn(b, s, h, n) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.randn(b, s, h, n) * 0.3, jnp.float32)
        yk, _ = ssd(xbar, log_a, Bm, Cm, chunk=chunk, interpret=True)
        yr, _ = ssd_reference(xbar, log_a, Bm, Cm, chunk=chunk)
        scale = float(jnp.max(jnp.abs(yr))) + 1e-9
        assert float(jnp.max(jnp.abs(yk - yr))) / scale < 1e-5

    def test_vs_sequential_recurrence(self):
        """Independent O(S) oracle: h_t = a_t h_{t-1} + B_t x_t."""
        rng = np.random.RandomState(11)
        b, s, h, p, n = 1, 64, 2, 8, 8
        xbar = jnp.asarray(rng.randn(b, s, h, p) * 0.5, jnp.float32)
        log_a = jnp.asarray(-np.abs(rng.randn(b, s, h)) * 0.2, jnp.float32)
        Bm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, jnp.float32)
        Cm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, jnp.float32)

        def step(st, inp):
            x_t, la_t, b_t, c_t = inp
            st = (jnp.exp(la_t)[..., None, None] * st
                  + jnp.einsum("bhp,bhn->bhpn", x_t, b_t))
            return st, jnp.einsum("bhpn,bhn->bhp", st, c_t)

        st0 = jnp.zeros((b, h, p, n))
        _, ys = jax.lax.scan(step, st0, (
            xbar.transpose(1, 0, 2, 3), log_a.transpose(1, 0, 2),
            Bm.transpose(1, 0, 2, 3), Cm.transpose(1, 0, 2, 3)))
        y_seq = ys.transpose(1, 0, 2, 3)
        yk, _ = ssd(xbar, log_a, Bm, Cm, chunk=16, interpret=True)
        scale = float(jnp.max(jnp.abs(y_seq))) + 1e-9
        assert float(jnp.max(jnp.abs(yk - y_seq))) / scale < 1e-4

    def test_chunk_invariance(self):
        rng = np.random.RandomState(12)
        b, s, h, p, n = 1, 128, 1, 8, 8
        args = (jnp.asarray(rng.randn(b, s, h, p) * 0.5, jnp.float32),
                jnp.asarray(-np.abs(rng.randn(b, s, h)) * 0.1, jnp.float32),
                jnp.asarray(rng.randn(b, s, h, n) * 0.3, jnp.float32),
                jnp.asarray(rng.randn(b, s, h, n) * 0.3, jnp.float32))
        y1, _ = ssd(*args, chunk=16, interpret=True)
        y2, _ = ssd(*args, chunk=64, interpret=True)
        scale = float(jnp.max(jnp.abs(y1))) + 1e-9
        assert float(jnp.max(jnp.abs(y1 - y2))) / scale < 1e-5


class TestGradQuant:
    @pytest.mark.parametrize("shape", [(100,), (3, 1000), (17, 65, 5)])
    def test_pallas_matches_ref(self, shape):
        rng = np.random.RandomState(sum(shape))
        x = jnp.asarray(rng.randn(*shape) * 0.01, jnp.float32)
        qp, sp = quantize(x, use_pallas=True, interpret=True)
        qr, sr = quantize(x, use_pallas=False)
        assert jnp.array_equal(qp, qr)
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                                   rtol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_roundtrip_error_bound(self, dtype):
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(4, 3333), dtype)
        q, s = quantize(x, use_pallas=True, interpret=True)
        xd = dequantize(q, s, (4, 3333), dtype=jnp.float32,
                        use_pallas=True, interpret=True)
        amax = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
        # symmetric int8: error <= scale/2 <= amax/254 per block
        err = float(jnp.max(jnp.abs(xd - x.astype(jnp.float32))))
        assert err <= amax / 127.0 + 1e-6

    @pytest.mark.parametrize("nb", [QK.ROWS + 44, 2 * QK.ROWS])
    def test_multi_tile_grid(self, nb):
        """More block rows than one tile holds, with and without a
        partial last tile: every row's codes and scale match the
        reference, and dequantize inverts them."""
        rng = np.random.RandomState(nb)
        x2d = jnp.asarray(rng.randn(nb, 2048) * 0.02, jnp.float32)
        qp, sp = QK.quantize_blocks(x2d, interpret=True)
        qr, sr = QR.quantize_blocks_ref(x2d)
        assert qp.shape == (nb, 2048) and sp.shape == (nb, 1)
        assert jnp.array_equal(qp, qr)
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                                   rtol=1e-6)
        xd = QK.dequantize_blocks(qp, sp, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(xd), np.asarray(QR.dequantize_blocks_ref(qp, sp)))

    def test_zero_tensor(self):
        x = jnp.zeros((2, 100), jnp.float32)
        q, s = quantize(x, use_pallas=True, interpret=True)
        xd = dequantize(q, s, (2, 100), use_pallas=True, interpret=True)
        assert float(jnp.max(jnp.abs(xd))) == 0.0

    @pytest.mark.parametrize("shape, view", [
        ((5, 3072, 8192), (5, 3072, 8192)),      # the leaf's own rows
        ((5, 32, 96, 3072), (5, 32, 96, 3072)),
        ((5, 3072, 32, 96), (5, 3072, 3072)),    # the trailing dims merged
        ((6, 1024), (6, 1024)),                  # a block spans a row pair
        ((3072, 32064), (48096, 2048)),          # rows wider than MAX_WIDTH
        ((37, 3072), (56, 2048)),                # a partial last block
        ((5, 3072), (8, 2048)),
        ((4, 5, 1024), (10, 2048)),              # a block would span matrices
    ])
    def test_row_view(self, shape, view):
        assert GQ.row_view(shape) == view

    @pytest.mark.parametrize("use_pallas", [True, False],
                             ids=["pallas", "jnp"])
    @pytest.mark.parametrize("shape, dtype", [
        ((3072, 8192), jnp.bfloat16),
        ((5, 3072, 32, 96), jnp.bfloat16),
        ((37, 3072), jnp.bfloat16),
        ((3072,), jnp.float32),
    ])
    def test_delta_codec_bit_identical(self, shape, dtype, use_pallas):
        """The codec on a leaf's row views of new and old gives, bit for
        bit, the codes, scales and dequantized delta of `quantize` on
        the fp32 delta. The first block is all zero: the 1e-12 floor."""
        rng = np.random.RandomState(len(shape))
        old = jnp.asarray(rng.randn(*shape) * 0.02, dtype)
        new = (old.astype(jnp.float32)
               + jnp.asarray(rng.randn(*shape) * 1e-3, jnp.float32))
        new = new.astype(dtype).reshape(-1).at[:GQ.BLOCK].set(
            old.reshape(-1)[:GQ.BLOCK]).reshape(shape)
        d = new.astype(jnp.float32) - old.astype(jnp.float32)
        q_want, s_want = quantize(d)
        y_want = dequantize(q_want, s_want, shape)

        kw = dict(use_pallas=use_pallas, interpret=use_pallas)
        q, s = GQ.quantize_delta(GQ.rows(new), GQ.rows(old), **kw)
        y = dequantize(q, s, shape, **kw)
        q_got, s_got = GQ.blocks(q, s)
        bits = lambda a: np.ascontiguousarray(np.asarray(a)).view(np.uint8)
        np.testing.assert_array_equal(bits(q_got), bits(q_want))
        np.testing.assert_array_equal(bits(s_got), bits(s_want))
        np.testing.assert_array_equal(bits(y), bits(y_want))
        assert float(s_got[0, 0]) == float(np.float32(1e-12) / 127)
        assert not np.any(np.asarray(y).reshape(-1)[:GQ.BLOCK])


class TestFlashAttentionGrad:
    def test_grad_matches_reference(self):
        """use_pallas=True must be trainable: the backward kernel pair's
        gradients match those of the pure reference."""
        rng = np.random.RandomState(21)
        B, S, N, H = 1, 128, 2, 32
        q, k, v = (jnp.asarray(rng.randn(B, S, N, H), jnp.float32)
                   for _ in range(3))

        def loss_kernel(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=64,
                                           block_k=64, interpret=True) ** 2)

        def loss_ref(q, k, v):
            f = lambda x: x.transpose(0, 2, 1, 3).reshape(B * N, S, H)
            o = reference_attention(f(q), f(k), f(v))
            return jnp.sum(o ** 2)

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-4)

    @pytest.mark.parametrize(
        "S,T,kv_heads,dtype,block_q,block_k,causal,window,softcap", [
            # blocks under S, a q block unlike the k block
            (128, 128, 2, jnp.float32, 32, 64, True, None, None),
            (128, 128, 2, jnp.bfloat16, 64, 32, True, None, None),
            # a window under a block: live tiles that differ per row of
            # blocks, whole tiles dead on both sides of the diagonal
            (256, 256, 2, jnp.float32, 64, 64, True, 20, None),
            (256, 256, 2, jnp.float32, 32, 128, True, 48, None),
            (256, 256, 2, jnp.bfloat16, 128, 32, True, 40, None),
            (128, 128, 2, jnp.float32, 32, 32, False, 40, None),
            # the cap's tanh derivative, alone and with a window
            (128, 128, 2, jnp.float32, 32, 64, True, None, 5.0),
            (128, 128, 2, jnp.bfloat16, 64, 64, True, 24, 5.0),
            # kv repeated to the q heads, as the attention layer does
            (128, 128, 1, jnp.float32, 32, 32, True, None, None),
            (128, 128, 1, jnp.bfloat16, 64, 32, True, 50, 30.0),
            # T != S
            (64, 128, 2, jnp.float32, 32, 64, True, None, None),
            (128, 64, 2, jnp.float32, 64, 32, True, None, None),
            (64, 128, 2, jnp.float32, 32, 32, False, None, None),
        ])
    def test_kernel_grads_match_reference_vjp(
            self, S, T, kv_heads, dtype, block_q, block_k, causal, window,
            softcap):
        """dq, dk and dv of the kernel pair against jax.vjp of the
        reference on the same inputs and cotangent: within fp32
        rounding for fp32 inputs, within two bf16 steps of the largest
        gradient for bf16 ones (both round their results to bf16)."""
        rng = np.random.RandomState(S + T + block_q + 7 * block_k)
        B, N, H = 1, 4, 32
        q, g = (jnp.asarray(rng.randn(B, S, N, H), dtype) for _ in range(2))
        k, v = (jnp.asarray(rng.randn(B, T, kv_heads, H), dtype)
                for _ in range(2))
        opts = dict(causal=causal, window=window, softcap=softcap)
        rep = lambda x: jnp.repeat(x, N // kv_heads, axis=2)

        def kernel(q, k, v):
            return flash_attention(q, rep(k), rep(v), block_q=block_q,
                                   block_k=block_k, interpret=True, **opts)

        def ref(q, k, v):
            o = reference_attention(_fold(q), _fold(rep(k)), _fold(rep(v)),
                                    **opts)
            return o.reshape(B, N, S, H).transpose(0, 2, 1, 3)

        out, vjp_kernel = jax.vjp(kernel, q, k, v)
        out_ref, vjp_ref = jax.vjp(ref, q, k, v)
        tol = 8e-3 if dtype == jnp.bfloat16 else 2e-6
        for name, got, want in zip(("out", "dq", "dk", "dv"),
                                   (out,) + vjp_kernel(g),
                                   (out_ref,) + vjp_ref(g)):
            assert got.dtype == want.dtype, name
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            err = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert err <= tol, (name, err)

    @pytest.mark.parametrize("causal,window,softcap", [
        (True, None, None), (True, 24, 5.0), (False, None, None)])
    def test_forward_lse(self, causal, window, softcap):
        """The forward's second output is each row's log-sum-exp of the
        scaled, capped and masked scores."""
        from repro.kernels.flash_attention.kernel import flash_attention_bnh
        rng = np.random.RandomState(3)
        BN, S, H = 2, 128, 32
        q, k, v = (jnp.asarray(rng.randn(BN, S, H), jnp.float32)
                   for _ in range(3))
        _, lse = flash_attention_bnh(q, k, v, causal=causal, window=window,
                                     softcap=softcap, block_q=32,
                                     block_k=64, interpret=True)
        s = jnp.einsum("bsh,bth->bst", q, k) / np.sqrt(H)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = np.arange(S)
        mask = np.ones((S, S), bool)
        if causal:
            mask &= pos[None, :] <= pos[:, None]
        if window is not None:
            mask &= pos[None, :] > pos[:, None] - window
        want = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
        assert lse.shape == (BN, 1, S) and lse.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_model_trains_with_pallas_attention(self):
        """End-to-end: a smoke transformer takes a grad step with
        cfg.use_pallas=True (TPU interpret mode on CPU)."""
        import dataclasses
        from repro import configs
        from repro.models import lm
        cfg = configs.get_config("phi3-mini-3.8b", smoke=True)
        cfg = dataclasses.replace(cfg, use_pallas=True, attn_chunk=8)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(
                     rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32),
                 "labels": jnp.asarray(
                     rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32)}
        with pltpu.force_tpu_interpret_mode():
            loss, grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, cfg, batch))(params)
        assert bool(jnp.isfinite(loss))
        gn = sum(float(jnp.sum(jnp.abs(g)))
                 for g in jax.tree.leaves(grads))
        assert gn > 0


class TestRGLRU:
    @pytest.mark.parametrize("S,W,chunk,bw", [
        (64, 16, 16, 16), (128, 64, 32, 32), (256, 32, 128, 32),
    ])
    def test_vs_associative_scan(self, S, W, chunk, bw):
        from repro.kernels.rglru.ops import rglru_scan
        from repro.kernels.rglru.ref import rglru_scan_ref
        rng = np.random.RandomState(S + W)
        log_a = jnp.asarray(-np.abs(rng.randn(2, S, W)) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(2, S, W) * 0.5, jnp.float32)
        hk = rglru_scan(log_a, b, chunk=chunk, block_w=bw, interpret=True)
        hr = rglru_scan_ref(log_a, b)
        scale = float(jnp.max(jnp.abs(hr))) + 1e-9
        assert float(jnp.max(jnp.abs(hk - hr))) / scale < 1e-5

    def test_recurrentgemma_forward_with_pallas(self):
        """Full hybrid model forward with the RG-LRU kernel engaged."""
        import dataclasses
        from repro import configs
        from repro.models import lm
        cfg = configs.get_config("recurrentgemma-2b", smoke=True)
        cfg = dataclasses.replace(cfg, use_pallas=True, attn_chunk=8)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)),
                           jnp.int32)
        ref_cfg = dataclasses.replace(cfg, use_pallas=False)
        with pltpu.force_tpu_interpret_mode():
            lo_k, _ = lm.forward(params, cfg, toks)
        lo_r, _ = lm.forward(params, ref_cfg, toks)
        err = float(jnp.max(jnp.abs(lo_k - lo_r)))
        assert err < 2e-3, err


class TestGroupedMatmul:
    """The held experts' grouped matmul: the megablox kernels (use_pallas)
    against `lax.ragged_dot`, value and both gradients, with the rows
    past the groups zero."""

    @staticmethod
    def _vjp(x, w, sizes, g, pallas):
        from repro.models.layers import grouped_matmul
        y, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes, pallas),
                         x, w)
        return (y,) + vjp(g)

    @pytest.mark.parametrize("M,K,N,sizes,dtype", [
        (128, 64, 32, (10, 0, 50, 20), jnp.float32),
        (128, 64, 48, (128, 0, 0, 0), jnp.float32),
        (512, 384, 232, (100, 37, 200), jnp.float32),
        (512, 384, 232, (0, 0, 0), jnp.float32),
        (512, 384, 232, (100, 37, 200), jnp.bfloat16),
    ])
    def test_megablox_matches_ragged_dot(self, M, K, N, sizes, dtype):
        kx, kw, kg = jax.random.split(jax.random.key(M + K + N), 3)
        x = jax.random.normal(kx, (M, K)).astype(dtype)
        w = (jax.random.normal(kw, (len(sizes), K, N)) / K ** .5
             ).astype(dtype)
        g = jax.random.normal(kg, (M, N)).astype(dtype)
        sizes = jnp.asarray(sizes, jnp.int32)
        want = self._vjp(x, w, sizes, g, False)
        with pltpu.force_tpu_interpret_mode():
            got = self._vjp(x, w, sizes, g, True)
        # bf16 results round once, each side summing in fp32
        rtol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
        rows = int(sizes.sum())
        for a, b in zip(want, got):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.all(np.isfinite(b))
            np.testing.assert_allclose(b, a, rtol=rtol,
                                       atol=rtol * np.max(np.abs(a)))
        for b in got[:2]:
            assert not np.any(np.asarray(b, np.float32)[rows:])

    def test_megablox_under_vmap_takes_each_slot_alone(self):
        """Two client slots with their own group sizes, as the round
        program vmaps the local step over slots."""
        from repro.models.layers import grouped_matmul
        kx, kw = jax.random.split(jax.random.key(1))
        x = jax.random.normal(kx, (2, 128, 64))
        w = jax.random.normal(kw, (2, 4, 64, 32)) / 8.0
        sizes = jnp.asarray([[10, 0, 50, 20], [0, 128, 0, 0]], jnp.int32)
        f = jax.vmap(lambda a, b, s: grouped_matmul(a, b, s, True))
        with pltpu.force_tpu_interpret_mode():
            y = f(x, w, sizes)
        for i in range(2):
            want = grouped_matmul(x[i], w[i], sizes[i], False)
            np.testing.assert_allclose(y[i], want, rtol=1e-5, atol=1e-5)

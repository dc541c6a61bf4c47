"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in
interpret mode (CPU executes the kernel bodies in Python)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunked_attention import chunked_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru
from repro.kernels.wkv6 import wkv6

KEY = jax.random.PRNGKey(0)


def _tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 2e-4


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,K,hd,bq,bk", [
        (2, 256, 4, 2, 64, 128, 128),
        (1, 256, 4, 1, 128, 64, 64),
        (1, 128, 8, 8, 64, 128, 32),
        (2, 512, 2, 1, 64, 128, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, B, S, H, K, hd, bq, bk, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
        v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        want = ref.attention(q, k, v, causal=True)
        assert out.shape == want.shape
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        assert err < _tol(dtype)

    @pytest.mark.parametrize("window", [32, 100, 511])
    def test_sliding_window(self, window):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 512, 4, 64))
        k = jax.random.normal(ks[1], (1, 512, 2, 64))
        v = jax.random.normal(ks[2], (1, 512, 2, 64))
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=128, block_k=128, interpret=True)
        want = ref.attention(q, k, v, causal=True, window=window)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-4

    @pytest.mark.parametrize("K,window", [(2, None), (4, 100)])
    def test_grad_matches_reference(self, K, window):
        """The custom VJP (Pallas forward + the chunked recomputing
        backward) against autodiff of the jnp oracle."""
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, K, 64))
        v = jax.random.normal(ks[2], (1, 256, K, 64))

        def f(*a):
            return jnp.sum(jnp.sin(flash_attention(
                *a, window=window, interpret=True)))

        def g(*a):
            return jnp.sum(jnp.sin(ref.attention(*a, causal=True,
                                                 window=window)))

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4

    def test_rejects_misaligned(self):
        q = jnp.zeros((1, 100, 2, 64))
        with pytest.raises(ValueError):
            flash_attention(q, q[:, :, :2], q[:, :, :2], block_q=64,
                            block_k=64, interpret=True)


class TestWKV6:
    @pytest.mark.parametrize("B,S,H,hd,bt", [
        (2, 128, 2, 64, 64), (1, 256, 4, 64, 64), (1, 64, 1, 32, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, S, H, hd, bt, dtype):
        ks = jax.random.split(KEY, 5)
        r = jax.random.normal(ks[0], (B, S, H, hd), dtype) * 0.5
        k = jax.random.normal(ks[1], (B, S, H, hd), dtype) * 0.5
        v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
        w = jnp.exp(-jnp.exp(
            jax.random.normal(ks[3], (B, S, H, hd)) - 3.0)).astype(dtype)
        u = jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.3
        out, st = wkv6(r, k, v, w, u, block_t=bt, interpret=True)
        want, wst = ref.wkv6(r, k, v, w, u)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        assert err < (6e-2 if dtype == jnp.bfloat16 else 1e-3)
        assert float(jnp.max(jnp.abs(st - wst))) < 1e-3

    def test_carried_state_equals_one_shot(self):
        """Chunked decode: running two halves with carried state must
        equal the full-sequence scan (serving correctness)."""
        ks = jax.random.split(KEY, 5)
        B, S, H, hd = 1, 128, 2, 64
        r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
        k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
        v = jax.random.normal(ks[2], (B, S, H, hd))
        w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) - 3.0))
        u = jax.random.normal(ks[4], (H, hd)) * 0.3
        full, s_full = wkv6(r, k, v, w, u, block_t=64, interpret=True)
        h = S // 2
        o1, s1 = wkv6(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u,
                      block_t=64, interpret=True)
        o2, s2 = wkv6(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, state=s1,
                      block_t=64, interpret=True)
        assert float(jnp.max(jnp.abs(jnp.concatenate([o1, o2], 1) - full))) < 1e-4
        assert float(jnp.max(jnp.abs(s2 - s_full))) < 1e-4


class TestRGLRU:
    @pytest.mark.parametrize("B,S,W,bt,bw", [
        (2, 128, 128, 128, 128), (1, 256, 256, 64, 128), (2, 64, 128, 32, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, S, W, bt, bw, dtype):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (B, S, W), dtype)
        r = jax.random.normal(ks[1], (B, S, W), dtype)
        i = jax.random.normal(ks[2], (B, S, W), dtype)
        lam = jnp.linspace(0.1, 2.0, W)
        out, h = rglru(x, r, i, lam, block_t=bt, block_w=bw, interpret=True)
        want, wh = ref.rglru(x, r, i, lam)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        assert err < _tol(dtype)
        assert float(jnp.max(jnp.abs(h - wh))) < _tol(dtype)

    def test_carried_state(self):
        ks = jax.random.split(KEY, 4)
        B, S, W = 1, 128, 128
        x = jax.random.normal(ks[0], (B, S, W))
        r = jax.random.normal(ks[1], (B, S, W))
        i = jax.random.normal(ks[2], (B, S, W))
        lam = jnp.linspace(0.1, 2.0, W)
        full, h_full = rglru(x, r, i, lam, block_t=64, interpret=True)
        o1, h1 = rglru(x[:, :64], r[:, :64], i[:, :64], lam, block_t=64,
                       interpret=True)
        o2, h2 = rglru(x[:, 64:], r[:, 64:], i[:, 64:], lam, h0=h1,
                       block_t=64, interpret=True)
        assert float(jnp.max(jnp.abs(jnp.concatenate([o1, o2], 1) - full))) < 1e-5
        assert float(jnp.max(jnp.abs(h2 - h_full))) < 1e-5


class TestChunkedAttention:
    """The production flash-schedule path (custom VJP)."""

    @pytest.mark.parametrize("window", [None, 96])
    def test_fwd_and_grad(self, window):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 32))
        k = jax.random.normal(ks[1], (1, 256, 2, 32))
        v = jax.random.normal(ks[2], (1, 256, 2, 32))
        out = chunked_attention(q, k, v, True, window, 64, 64)
        want = ref.attention(q, k, v, causal=True, window=window)
        assert float(jnp.max(jnp.abs(out - want))) < 1e-4

        f = lambda *a: jnp.sum(jnp.sin(chunked_attention(*a, True, window, 64, 64)))
        g = lambda *a: jnp.sum(jnp.sin(ref.attention(*a, causal=True, window=window)))
        gc = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gc, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4


class TestDecodePartials:
    def test_sharded_combine_identity(self):
        """Combining per-shard flash partials equals full attention —
        the math behind the seq-sharded 500k decode."""
        ks = jax.random.split(KEY, 3)
        B, S, H, K, hd = 2, 64, 4, 2, 32
        q = jax.random.normal(ks[0], (B, 1, H, hd))
        k = jax.random.normal(ks[1], (B, S, K, hd))
        v = jax.random.normal(ks[2], (B, S, K, hd))
        valid = jnp.arange(S) <= 37
        want = ref.decode_attention(q, k, v, valid)
        # two shards
        o1, m1, l1 = ref.decode_attention_partials(q, k[:, :32], v[:, :32],
                                                   valid[:32])
        o2, m2, l2 = ref.decode_attention_partials(q, k[:, 32:], v[:, 32:],
                                                   valid[32:])
        m = jnp.maximum(m1, m2)
        o = o1 * jnp.exp(m1 - m)[..., None] + o2 * jnp.exp(m2 - m)[..., None]
        l = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
        got = o / jnp.maximum(l, 1e-30)[..., None]
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


class TestOpsDispatch:
    """``impl="pallas"`` never substitutes another implementation: a
    shape the kernel does not cover raises."""

    @pytest.mark.parametrize("call", [
        lambda: ops.attention(jnp.zeros((1, 100, 2, 64)),
                              jnp.zeros((1, 100, 2, 64)),
                              jnp.zeros((1, 100, 2, 64)), impl="pallas"),
        lambda: ops.wkv6(*[jnp.zeros((1, 100, 2, 64))] * 4,
                         jnp.zeros((2, 64)), impl="pallas"),
        lambda: ops.rglru(*[jnp.zeros((1, 128, 100))] * 3,
                          jnp.zeros((100,)), impl="pallas"),
    ], ids=["attention", "wkv6", "rglru"])
    def test_pallas_on_uncovered_shape_raises(self, call):
        with pytest.raises(ValueError, match="does not cover"):
            call()

    def test_auto_off_tpu_is_the_reference(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 128))
        k = jax.random.normal(ks[1], (1, 128, 2, 128))
        v = jax.random.normal(ks[2], (1, 128, 2, 128))
        assert jax.default_backend() != "tpu"
        assert jnp.array_equal(ops.attention(q, k, v),
                               ref.attention(q, k, v, causal=True))

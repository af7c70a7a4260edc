"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


@pytest.mark.parametrize("k", [1, 3, 5])
@pytest.mark.parametrize("n", [100, 8192, 10000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix(k, n, dtype):
    bufs = jax.random.normal(jax.random.key(0), (k, n)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.key(1), (k,)))
    got = ops.gossip_mix(bufs, w)
    want = ref.gossip_mix_ref(bufs, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert _err(got, want) < tol
    assert got.dtype == dtype


@pytest.mark.parametrize("n", [100, 8192, 10000, 21840])
@pytest.mark.parametrize("k", [1, 4])
def test_gossip_mix_q8(k, n):
    """Fused int8 receive path: exact self buffer + K blockwise-int8
    payloads with per-block scales, dequantized in VMEM, fp32 accumulate —
    vs the pure-jnp oracle."""
    from repro.core.compression import quantize_int8

    raw = jax.random.normal(jax.random.key(0), (k, n)) * 4
    self_buf = jax.random.normal(jax.random.key(1), (n,))
    q_bufs = jnp.stack([quantize_int8(raw[i])[0] for i in range(k)])
    scales = jnp.stack([quantize_int8(raw[i])[1] for i in range(k)])
    w = jax.nn.softmax(jax.random.normal(jax.random.key(2), (k + 1,)))
    got = ops.gossip_mix_q8(self_buf, q_bufs, scales, w)
    want = ref.gossip_mix_q8_ref(self_buf, q_bufs, scales, w)
    assert got.dtype == jnp.float32 and got.shape == (n,)
    assert _err(got, want) < 1e-5


def test_gossip_mix_q8_rejects_ragged_scales():
    q = jnp.zeros((2, 4096), jnp.int8)
    with pytest.raises(ValueError, match="scale"):
        ops.gossip_mix_q8(jnp.zeros(100), q, jnp.ones((2, 3)),
                          jnp.ones(3) / 3)
    with pytest.raises(ValueError, match="shorter"):
        ops.gossip_mix_q8(jnp.zeros(9000), q, jnp.ones((2, 2)),
                          jnp.ones(3) / 3)


def test_default_interpret_tracks_live_backend(monkeypatch):
    """The interpret default must follow the *current* backend per call —
    the old ``functools.cache`` froze the first answer, so a TPU attached
    after import stayed in interpret mode forever. An explicit bool always
    overrides."""
    from repro.kernels import gossip_mix as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert gm._default_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm._default_interpret() is False         # re-evaluated per call
    # explicit override beats the (pretend-TPU) auto-selection: interpret
    # mode still runs fine on this CPU-only host
    bufs = jax.random.normal(jax.random.key(0), (2, 300))
    w = jnp.array([0.5, 0.5])
    out = ops.gossip_mix(bufs, w, interpret=True)
    assert _err(out, ref.gossip_mix_ref(bufs, w)) < 1e-5
    monkeypatch.setattr(jax, "default_backend",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):  # no silent interpret
        gm._default_interpret()


@pytest.mark.parametrize("s,hq,hkv,d", [
    (64, 4, 4, 32),    # MHA
    (80, 4, 2, 32),    # GQA, ragged seq
    (96, 8, 1, 16),    # MQA
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
def test_flash_attention(s, hq, hkv, d, causal, window):
    q = jax.random.normal(jax.random.key(0), (2, s, hq, d))
    k = jax.random.normal(jax.random.key(1), (2, s, hkv, d))
    v = jax.random.normal(jax.random.key(2), (2, s, hkv, d))
    got = ops.flash_attention_gqa(q, k, v, causal=causal, window=window,
                                  bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    assert _err(got, want) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 32)).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (1, 64, 2, 32)).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (1, 64, 2, 32)).astype(dtype)
    got = ops.flash_attention_gqa(q, k, v, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert _err(got, want) < tol
    assert got.dtype == dtype


@pytest.mark.parametrize("s,h,d,chunk", [(40, 2, 16, 16), (128, 4, 32, 32),
                                         (33, 1, 8, 16)])
def test_rwkv6(s, h, d, chunk):
    b = 2
    r = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, h, d))
    v = jax.random.normal(jax.random.key(2), (b, s, h, d))
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.key(3), (b, s, h, d)) * 0.5))
    u = jax.random.normal(jax.random.key(4), (h, d)) * 0.1
    y1, s1 = ops.rwkv6(r, k, v, w, u, chunk=chunk)
    y2, s2 = ref.rwkv6_ref(r, k, v, w, u)
    assert _err(y1, y2) < 5e-4
    assert _err(s1, s2) < 5e-4


@pytest.mark.parametrize("s,d", [(64, 128), (100, 256), (32, 64)])
def test_rglru(s, d):
    b = 2
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (b, s, d)))
    binp = jax.random.normal(jax.random.key(1), (b, s, d))
    h0 = jax.random.normal(jax.random.key(2), (b, d))
    got = ops.rglru(a, binp, h0, chunk=32)
    want = ref.rglru_ref(a, binp, h0)
    assert _err(got, want) < 1e-4


def test_rglru_matches_model_recurrence():
    """Kernel vs the model's associative-scan lowering (two independent
    implementations of the same recurrence)."""
    from repro.models.rglru import linear_recurrence
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(5), (2, 48, 128)))
    b = jax.random.normal(jax.random.key(6), (2, 48, 128))
    h0 = jax.random.normal(jax.random.key(7), (2, 128))
    got = ops.rglru(a, b, h0, chunk=16)
    want = linear_recurrence(a, b, h0)
    assert _err(got, want) < 1e-4


@pytest.mark.parametrize("r,c", [(8, 512), (5, 700), (16, 256)])
def test_quantize_roundtrip(r, c):
    x = jax.random.normal(jax.random.key(0), (r, c)) * 7
    q, s = ops.quantize_int8(x)
    deq = ops.dequantize_int8(q, s)
    # error bounded by half an int8 step of the per-block scale
    assert _err(deq, x) <= float(jnp.abs(x).max()) / 127.0 * 0.51 + 1e-6


@pytest.mark.parametrize("r,c", [(8, 6912), (16, 4352)])
def test_quantize_covers_every_column_tile(r, c):
    """Widths whose scale-block count is not a multiple of the column tile
    (27 and 17 blocks): every column is quantized exactly as the oracle."""
    x = jax.random.normal(jax.random.key(2), (r, c)) * 5
    q, s = ops.quantize_int8(x)
    qr, sr = ref.quantize_int8_ref(x)
    assert jnp.all(q == qr)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ops.dequantize_int8(q, s)),
                                  np.asarray(ref.dequantize_int8_ref(q, s)))


def test_quantize_matches_ref_exactly():
    x = jax.random.normal(jax.random.key(1), (8, 512)) * 3
    q, s = ops.quantize_int8(x)
    qr, sr = ref.quantize_int8_ref(x)
    assert jnp.all(q == qr)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def _groups(rng, g, m):
    return jnp.asarray(np.bincount(rng.integers(0, g, m), minlength=g), jnp.int32)


@pytest.mark.parametrize("m,k,n", [(256, 128, 256), (12, 16, 24), (300, 64, 40)])
@pytest.mark.parametrize("held,offset", [(8, 0), (3, 2), (3, 5)])
def test_grouped_matmul_forward_and_vjp(m, k, n, held, offset):
    """The Pallas grouped matmul over the held groups (interpret mode)
    against a plain einsum over every row's group: forward, and both
    cotangents of its VJP, under the node ``vmap`` the D-PSGD step puts on
    it."""
    from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_ref

    rng = np.random.default_rng(m + 7 * held + offset)
    lhs = jax.random.normal(jax.random.key(0), (2, m, k))
    rhs = jax.random.normal(jax.random.key(1), (2, held, k, n))
    sizes = jnp.stack([_groups(rng, 8, m), _groups(rng, 8, m)])

    def loss(fn):
        return lambda a, b: jnp.sin(jax.vmap(
            lambda x, w, s: fn(x, w, s, offset))(a, b, sizes)).sum()

    got = jax.vmap(lambda x, w, s: grouped_matmul(x, w, s, offset))(lhs, rhs, sizes)
    want = jax.vmap(lambda x, w, s: grouped_matmul_ref(x, w, s, offset))(lhs, rhs, sizes)
    assert _err(got, want) < 1e-4 * float(jnp.abs(want).max() + 1)
    g1 = jax.grad(loss(grouped_matmul), (0, 1))(lhs, rhs)
    g2 = jax.grad(loss(grouped_matmul_ref), (0, 1))(lhs, rhs)
    for a, b in zip(g1, g2):
        assert _err(a, b) < 1e-4 * float(jnp.abs(b).max() + 1)


def test_grouped_matmul_rows_of_groups_not_held_are_zero():
    from repro.kernels.grouped_matmul import grouped_matmul

    sizes = jnp.asarray([5, 7, 0, 4], jnp.int32)
    out = grouped_matmul(jnp.ones((16, 8)), jnp.ones((2, 8, 8)), sizes, 1)
    assert bool(jnp.all(out[:5] == 0)) and bool(jnp.all(out[12:] == 0))
    assert bool(jnp.all(out[5:12] == 8))

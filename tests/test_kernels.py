"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import check_impl, resolve_interpret
from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.flash_attention import attention_ref, flash_attention

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 4, 2, 128, 64),
    (2, 8, 8, 256, 64),
    (1, 8, 1, 128, 128),   # MQA
    (2, 2, 2, 192, 32),    # non-pow2 seq (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, dtype, causal, window):
    if not causal and S % 64 != 0:
        pytest.skip("non-causal pallas path requires divisible Sk")
    q = jnp.asarray(RNG.normal(size=(B, Hq, S, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    want = attention_ref(q, k, v, causal=causal, window=window)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          impl="pallas_interpret", block_q=64, block_k=64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_decode_offset():
    q = jnp.asarray(RNG.normal(size=(2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 2, 256, 64)), jnp.float32)
    for cache_len in (64, 199, 256):
        want = attention_ref(q, k, v, causal=True, q_offset=cache_len - 1)
        got = flash_attention(q, k, v, causal=True, q_offset=cache_len - 1,
                              impl="pallas_interpret", block_q=8, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("V,D,Bb,L", [(1000, 32, 64, 8), (257, 16, 33, 3),
                                      (4096, 64, 16, 1)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_sweep(V, D, Bb, L, mode):
    table = jnp.asarray(RNG.normal(size=(V, D)), jnp.float32)
    idx = RNG.integers(-1, V, size=(Bb, L)).astype(np.int32)
    want = embedding_bag_ref(table, jnp.asarray(idx), mode)
    got = embedding_bag(table, jnp.asarray(idx), mode=mode, impl="pallas",
                        interpret=True, block_b=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_embedding_bag_all_padding():
    table = jnp.ones((10, 4), jnp.float32)
    idx = jnp.full((4, 3), -1, jnp.int32)
    got = embedding_bag(table, idx, impl="pallas", interpret=True, block_b=4)
    np.testing.assert_array_equal(np.asarray(got), np.zeros((4, 4)))


def test_impl_selection_idiom():
    """The shared impl/interpret idiom: auto-interpret only on CPU backends,
    pallas_interpret always forces the interpreter, bad impls fail loudly."""
    on_cpu = jax.default_backend() == "cpu"
    assert resolve_interpret(None, "pallas") == on_cpu
    assert resolve_interpret(None, "pallas_interpret") is True
    assert resolve_interpret(False, "pallas_interpret") is True
    assert resolve_interpret(True, "pallas") is True
    assert resolve_interpret(False, "pallas") is False
    with pytest.raises(ValueError):
        check_impl("cuda")

"""Pallas kernels: shape/dtype sweeps vs their oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is an optional [test] extra: only the property test needs it
# (pinned-seed fallback below); everything else runs regardless
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.kernels.decode_attn.ops import decode
from repro.kernels.flash_attn.ops import attention
from repro.kernels.sorted_probe.ops import probe
from repro.kernels.window_agg.ops import aggregate


# ------------------------------------------------------------- sorted_probe
@pytest.mark.parametrize("t_size", [17, 512, 2048, 5000])
@pytest.mark.parametrize("n_q", [1, 300, 1024])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_sorted_probe_sweep(rng, t_size, n_q, dtype):
    table = np.unique(rng.integers(0, 1 << 20, t_size)).astype(dtype)
    queries = np.concatenate([
        rng.choice(table, min(n_q // 2 + 1, len(table))),
        rng.integers(0, 1 << 20, n_q // 2).astype(dtype)])[:n_q]
    p1, f1 = probe(table, queries, impl="interpret")
    p2, f2 = probe(table, queries, impl="ref")
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(f1, f2)


def _check_sorted_probe(table_keys, query_keys):
    table = np.asarray(sorted(table_keys), np.int32)
    queries = np.asarray(query_keys, np.int32)
    pos, found = probe(table, queries, impl="interpret")
    for q, p, f in zip(query_keys, pos, found):
        assert bool(f) == (q in table_keys)
        assert int(p) == int(np.searchsorted(table, q))


if HAS_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 10_000), min_size=1, max_size=200,
                    unique=True),
           st.lists(st.integers(0, 10_000), min_size=1, max_size=100))
    def test_sorted_probe_property(table_keys, query_keys):
        _check_sorted_probe(table_keys, query_keys)
else:
    @pytest.mark.parametrize("seed", range(20))
    def test_sorted_probe_property(seed):
        r = np.random.default_rng(seed)
        table_keys = np.unique(
            r.integers(0, 10_000, int(r.integers(1, 200)))).tolist()
        query_keys = r.integers(0, 10_000, int(r.integers(1, 100))).tolist()
        _check_sorted_probe(table_keys, query_keys)


# -------------------------------------------------------------- window_agg
@pytest.mark.parametrize("n,segs,v", [(100, 16, 1), (2048, 512, 4),
                                      (5000, 1000, 8), (1024, 513, 2)])
def test_window_agg_sweep(rng, n, segs, v):
    seg = rng.integers(0, segs, n).astype(np.int32)
    vals = rng.normal(size=(n, v)).astype(np.float32)
    s1, c1 = aggregate(seg, vals, segs, impl="interpret")
    s2, c2 = aggregate(seg, vals, segs, impl="ref")
    np.testing.assert_allclose(s1, s2, atol=1e-3)
    np.testing.assert_array_equal(c1, c2)


# -------------------------------------------------------------- flash_attn
@pytest.mark.parametrize("s,dh", [(128, 64), (300, 64), (512, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attn_sweep(rng, s, dh, causal, dtype):
    q = jnp.asarray(rng.normal(size=(2, 4, s, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 2, s, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(2, 2, s, dh)), dtype)
    o1 = attention(q, k, v, causal=causal)
    o2 = attention(q, k, v, causal=causal, impl="ref")
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attn_swa(rng, window):
    q = jnp.asarray(rng.normal(size=(1, 2, 384, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 384, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 384, 64)), jnp.float32)
    o1 = attention(q, k, v, causal=True, window=window)
    o2 = attention(q, k, v, causal=True, window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# -------------------------------------------------------------- decode_attn
@pytest.mark.parametrize("s,h,kv,dh", [(512, 8, 4, 64), (1000, 4, 4, 128),
                                       (513, 8, 2, 64)])
def test_decode_attn_sweep(rng, s, h, kv, dh):
    q = jnp.asarray(rng.normal(size=(2, h, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(2, kv, s, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, kv, s, dh)), jnp.float32)
    vl = jnp.asarray([s, max(1, s // 3)], jnp.int32)
    o1 = decode(q, kc, vc, vl)
    o2 = decode(q, kc, vc, vl, impl="ref")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_decode_attn_ragged_masking(rng):
    """Slots past valid_len must not affect the result."""
    q = jnp.asarray(rng.normal(size=(1, 2, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.float32)
    o1 = decode(q, kc, vc, 100)
    kc2 = kc.at[:, :, 100:].set(999.0)          # garbage past valid_len
    vc2 = vc.at[:, :, 100:].set(-999.0)
    o2 = decode(q, kc2, vc2, 100)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_flash_attn_matches_model_attention(rng):
    """Pallas kernel == the model's chunked_attention (the dry-run path)."""
    from repro.models.layers import chunked_attention
    q = jnp.asarray(rng.normal(size=(2, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 4, 256, 64)), jnp.float32)
    o1 = attention(q, k, v, causal=True)
    o2 = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)

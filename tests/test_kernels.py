"""Pallas kernel sweeps: shapes x dtypes x batch vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import skiplist as sl
from repro.kernels import ops as kops
from repro.kernels.foresight_traverse import base_traverse, foresight_traverse
from repro.kernels.ref import (base_search_ref, decode_float_keys,
                               encode_float_keys, foresight_search_ref)


def _state(n, cap, levels, foresight, seed=0, span=1 << 22):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int32)
    st = sl.build(jnp.asarray(keys), jnp.asarray(keys + 1), capacity=cap,
                  levels=levels, foresight=foresight, seed=seed)
    return st, keys


@pytest.mark.parametrize("n,cap,levels", [
    (16, 64, 4), (100, 256, 8), (1000, 2048, 12), (4000, 8192, 14),
])
@pytest.mark.parametrize("batch", [128, 256])
def test_foresight_kernel_matches_ref(n, cap, levels, batch):
    st, keys = _state(n, cap, levels, True, seed=n)
    rng = np.random.default_rng(n + 1)
    q = jnp.asarray(np.concatenate([
        rng.choice(keys, batch // 2),
        rng.integers(0, 1 << 22, batch - batch // 2),
    ]).astype(np.int32))
    node_k, key_k = foresight_traverse(st.fused, q, interpret=True)
    node_r, key_r = foresight_search_ref(st.fused, q)
    np.testing.assert_array_equal(np.asarray(node_k), np.asarray(node_r))
    np.testing.assert_array_equal(np.asarray(key_k), np.asarray(key_r))


@pytest.mark.parametrize("n,cap,levels", [(100, 256, 8), (1000, 2048, 12)])
def test_base_kernel_matches_ref(n, cap, levels):
    st, keys = _state(n, cap, levels, False, seed=n)
    rng = np.random.default_rng(n + 2)
    q = jnp.asarray(rng.integers(0, 1 << 22, 128).astype(np.int32))
    node_k, key_k = base_traverse(st.nxt, st.keys, q, interpret=True)
    node_r, key_r = base_search_ref(st.nxt, st.keys, q)
    np.testing.assert_array_equal(np.asarray(node_k), np.asarray(node_r))
    np.testing.assert_array_equal(np.asarray(key_k), np.asarray(key_r))


@pytest.mark.parametrize("foresight", [True, False])
def test_kernel_agrees_with_core_search(foresight):
    st, keys = _state(500, 1024, 10, foresight, seed=9)
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.integers(0, 1 << 22, 200).astype(np.int32))
    rk = kops.search_kernel(st, q)
    rc = sl.search(st, q)
    np.testing.assert_array_equal(np.asarray(rk.found), np.asarray(rc.found))
    np.testing.assert_array_equal(np.asarray(rk.vals), np.asarray(rc.vals))


def test_kernel_pads_non_multiple_batch():
    st, keys = _state(100, 256, 8, True)
    q = jnp.asarray(keys[:37])          # 37 % 128 != 0
    r = kops.search_kernel(st, q)
    assert r.found.shape == (37,)
    assert bool(jnp.all(r.found))


def test_float_key_roundtrip_and_order():
    rng = np.random.default_rng(11)
    f = np.sort(rng.normal(scale=100.0, size=512).astype(np.float32))
    enc = np.asarray(encode_float_keys(jnp.asarray(f)))
    assert (np.diff(enc) > 0).all()
    dec = np.asarray(decode_float_keys(jnp.asarray(enc)))
    np.testing.assert_allclose(dec, f, atol=0)


def test_float_keyed_kernel_search():
    """Redis-style double keys via the order-preserving transform."""
    rng = np.random.default_rng(12)
    f = np.sort(rng.normal(size=200).astype(np.float32))
    enc = encode_float_keys(jnp.asarray(f))
    st = sl.build(enc, jnp.arange(200, dtype=jnp.int32), capacity=512,
                  levels=10, foresight=True)
    r = kops.search_kernel_float(st, jnp.asarray(f[:64]))
    assert bool(jnp.all(r.found))
    np.testing.assert_array_equal(np.asarray(r.vals), np.arange(64))


def test_vmem_budget_accounting():
    st, _ = _state(1000, 2048, 12, True)
    assert kops.vmem_footprint(st) == 12 * 2048 * 2 * 4
    assert kops.fits_vmem(st)


def test_kernel_max_steps_bound_sufficient():
    """Default lock-step bound covers worst observed path length."""
    st, keys = _state(4000, 8192, 14, True, seed=3)
    q = jnp.asarray(keys.astype(np.int32))[:1024]
    r = kops.search_kernel(st, q)
    assert bool(jnp.all(r.found))

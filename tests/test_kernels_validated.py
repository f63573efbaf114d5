"""Validated-traversal Pallas kernel: exactness under corruption (hypothesis).

Note: the validated kernel's semantics differ from a plain foresight search
only when the fused table is torn; these sweeps drive corruption 0 -> 100%.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -e .[test])")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import skiplist as sl
from repro.kernels.validated_traverse import validated_traverse

SET = settings(max_examples=15, deadline=None,
               suppress_health_check=[HealthCheck.too_slow])


def _built(n, cap, levels, seed=0, span=1 << 20):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int32)
    st_ = sl.build(jnp.asarray(keys), jnp.asarray(keys + 7), capacity=cap,
                   levels=levels, foresight=True, seed=seed)
    return st_, keys, rng


@pytest.mark.parametrize("n,cap,levels", [
    (50, 128, 6), (500, 1024, 10), (3000, 8192, 13),
])
def test_validated_kernel_clean_table(n, cap, levels):
    st_, keys, rng = _built(n, cap, levels, seed=n)
    q = jnp.asarray(np.concatenate(
        [rng.choice(keys, 64), rng.integers(0, 1 << 20, 64)]).astype(np.int32))
    node, ck = validated_traverse(st_.fused, st_.keys, q, interpret=True)
    kset = set(keys.tolist())
    expect = np.array([int(x) in kset for x in np.asarray(q)])
    np.testing.assert_array_equal(np.asarray(ck) == np.asarray(q), expect)


@SET
@given(corrupt=st.floats(0.0, 1.0), seed=st.integers(0, 1000))
def test_validated_kernel_exact_under_corruption(corrupt, seed):
    st_, keys, rng = _built(300, 1024, 10, seed=seed)
    fused = np.asarray(st_.fused).copy()
    mask = rng.random(fused[..., 1].shape) < corrupt
    fused[..., 1] = np.where(
        mask, rng.integers(-2**31 + 1, 2**31 - 1, fused[..., 1].shape),
        fused[..., 1])
    q = jnp.asarray(rng.integers(0, 1 << 20, 128).astype(np.int32))
    node, ck = validated_traverse(jnp.asarray(fused), st_.keys, q,
                                  interpret=True)
    kset = set(keys.tolist())
    expect = np.array([int(x) in kset for x in np.asarray(q)])
    found = np.asarray(ck) == np.asarray(q)
    np.testing.assert_array_equal(found, expect)
    # payloads correct for hits
    vals = np.asarray(st_.vals)[np.asarray(node)]
    np.testing.assert_array_equal(vals[found], np.asarray(q)[found] + 7)


def test_validated_kernel_matches_core_reference():
    from repro.core.validated import search_validated
    st_, keys, rng = _built(800, 2048, 11, seed=3)
    fused = np.asarray(st_.fused).copy()
    mask = rng.random(fused[..., 1].shape) < 0.4
    fused[..., 1] = np.where(
        mask, rng.integers(-2**31 + 1, 2**31 - 1, fused[..., 1].shape),
        fused[..., 1])
    q = jnp.asarray(rng.integers(0, 1 << 20, 256).astype(np.int32))
    node_k, ck = validated_traverse(jnp.asarray(fused), st_.keys, q,
                                  interpret=True)
    ref = search_validated(jnp.asarray(fused), st_.keys, st_.vals, q)
    found_k = np.asarray(ck) == np.asarray(q)
    np.testing.assert_array_equal(found_k, np.asarray(ref.found))

"""Compile the index's chip programs for a described TPU v5e.

Nothing runs: each program is compiled ahead of time for one chip of a
``v5e:2x2`` topology described by the TPU compiler installed here, with
kernels compiled by Mosaic (``interpret=False``).  That catches what
interpret mode cannot — a kernel Mosaic refuses, a tile that overflows
the scoped VMEM, a program that does not fit the chip's HBM — at no chip
time.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.kernel_budget import max_capacity_under_budget
from repro.core import sharded as shd
from repro.core import skiplist as sl

ft = importlib.import_module("repro.kernels.foresight_traverse")
vt = importlib.import_module("repro.kernels.validated_traverse")

HBM_BYTES = 16 * 10**9      # one v5e chip (Google Cloud, "TPU v5e")
LEVELS = 16                 # kernel tiles: the store/page-table default
BATCH = 1024


def _smoke_store_keys() -> int:
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.STORE_KEYS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert peak < HBM_BYTES, f"needs {peak} bytes of HBM"
    return peak


def test_store_paths_compile_at_smoke_size(one_chip):
    """``search_fast`` and the donated ``apply_ops`` at the key count the
    chip smoke serves, with the store's capacity and level rule."""
    n = _smoke_store_keys()
    levels = math.ceil(math.log2(n)) + 2
    cap = int(2 ** math.ceil(math.log2(n * 2 + 4)))
    state = _on(one_chip, jax.eval_shape(
        functools.partial(sl.build, capacity=cap, levels=levels),
        _spec(one_chip, (n,)), _spec(one_chip, (n,))))
    read = jax.jit(sl.search_fast).lower(
        state, _spec(one_chip, (4096,))).compile()
    _fits(read)
    assert read.memory_analysis().temp_size_in_bytes < 2**24  # no copy
    op = _spec(one_chip, (256,))
    write = jax.jit(sl.apply_ops, donate_argnums=(0,)).lower(
        state, op, op, op).compile()
    _fits(write)


def test_search_sharded_compiles(one_chip):
    shl = _on(one_chip, jax.eval_shape(
        functools.partial(shd.build_sharded, n_shards=16, levels=22),
        _spec(one_chip, (2**21,)), _spec(one_chip, (2**21,))))
    compiled = jax.jit(shd.search_sharded).lower(
        shl, _spec(one_chip, (4096,))).compile()
    _fits(compiled)


def _kernel_args(name, s, foresight, nw):
    """(wrapper, operand specs) at the largest tile the builders emit."""
    cap = max_capacity_under_budget(LEVELS, foresight, node_width=nw)
    L, S, K = LEVELS, 4, 2
    nblk = BATCH // ft.QBLK
    q = s((BATCH,))
    fat1 = s((cap, nw)) if nw > 1 else None
    fatS = s((S, cap, nw)) if nw > 1 else None
    if name == "plain":
        if foresight:
            return ft.foresight_traverse, (s((L, cap, 2)), q, fat1)
        return ft.base_traverse, (s((L, cap)), s((cap,)), q, fat1)
    if name == "sharded":
        if foresight:
            return ft.foresight_traverse_sharded, (s((S, L, cap, 2)), q, q,
                                                   fatS)
        return ft.base_traverse_sharded, (s((S, L, cap)), s((S, cap)), q, q,
                                          fatS)
    plan = (s((nblk, K)), s((nblk,)), q, q)
    if foresight:
        return ft.foresight_traverse_clustered, (s((S, L, cap, 2)), *plan,
                                                 fatS)
    return ft.base_traverse_clustered, (s((S, L, cap)), s((S, cap)), *plan,
                                        fatS)


@pytest.mark.parametrize("node_width", [1, 128])
@pytest.mark.parametrize("foresight", [True, False])
@pytest.mark.parametrize("grid", ["plain", "sharded", "clustered"])
def test_traversal_kernel_compiles(one_chip, grid, foresight, node_width):
    fn, args = _kernel_args(grid, functools.partial(_spec, one_chip),
                            foresight, node_width)
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    # every table reaches the kernel as it lies in HBM (the fused plane as
    # a bitcast of its T(2,128) tiling): a relayout copy of even the
    # smallest table here (1 MiB) would show up as a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_validated_kernel_compiles(one_chip):
    cap = 2**15
    compiled = jax.jit(functools.partial(vt.validated_traverse,
                                         interpret=False)).lower(
        _spec(one_chip, (LEVELS, cap, 2)), _spec(one_chip, (cap,)),
        _spec(one_chip, (BATCH,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)

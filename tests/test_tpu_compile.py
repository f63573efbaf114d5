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
import re

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


_HLO_ARRAY = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")


def _table_sized_in_loops(hlo: str, elems: int) -> list:
    """Instructions of ``elems`` or more elements inside a ``while`` body of
    compiled HLO text that move data: every array-shaped instruction but a
    loop parameter, tuple element, bitcast or (fused) scatter, which
    updates its operand in place."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)

    def root_op(name):
        for line in comps[name]:
            m = re.match(r"\s*ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
            if m:
                return m.group(1)

    found = []
    for body in sorted(set(re.findall(r"body=%?([\w.\-]+)", hlo))):
        for line in comps[body]:
            m = _HLO_ARRAY.match(line)
            if not m:
                continue
            name, dims, op, rest = m.groups()
            if math.prod(int(d) for d in dims.split(",") if d) < elems:
                continue
            if op in ("parameter", "get-tuple-element", "bitcast", "scatter"):
                continue
            if op == "fusion" and root_op(re.search(
                    r"calls=%?([\w.\-]+)", rest).group(1)) == "scatter":
                continue
            found.append(f"{body}: {name} = {op}[{dims}]")
    return found


def test_store_apply_copies_no_table_in_its_loops(one_chip):
    """The donated ``apply_ops`` at ``ycsb_store_4m.ycsb_d``'s shape (4M
    keys, 24 levels, capacity 2^23, 13 inserts a call): no loop body
    copies, relays out or selects the table; only scatters write it.  The
    flat view of the fused table made one copy of it per search step, with
    3,222,485,504 bytes of temporaries."""
    n, levels, cap, ops = 4_000_000, 24, 2**23, 13
    state = _on(one_chip, jax.eval_shape(
        functools.partial(sl.build, capacity=cap, levels=levels),
        _spec(one_chip, (n,)), _spec(one_chip, (n,))))
    op = _spec(one_chip, (ops,))
    write = jax.jit(sl.apply_ops, donate_argnums=(0,)).lower(
        state, op, op, op).compile()
    _fits(write)
    assert _table_sized_in_loops(write.as_text(), levels * cap) == []
    assert write.memory_analysis().temp_size_in_bytes <= 3_222_485_504


def test_page_table_apply_copies_no_table_in_its_loops(one_chip):
    """``jax.vmap(apply_ops)`` over the page table's stacked shards (8
    shards x 16 levels x 32,768 slots, windows of 32 ops), as
    ``apply_ops_sharded`` runs it: no loop body copies or selects the
    table, where the vmapped switch copied it 3 times per scan position."""
    shards, levels, cap, window = 8, 16, 32768, 32
    stacked = _on(one_chip, jax.eval_shape(functools.partial(
        shd.empty_sharded, n_shards=shards, capacity=cap,
        levels=levels)).shards)
    op = _spec(one_chip, (shards, window))
    write = jax.jit(jax.vmap(sl.apply_ops)).lower(stacked, op, op,
                                                  op).compile()
    _fits(write)
    assert _table_sized_in_loops(write.as_text(),
                                 shards * levels * cap) == []


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


def test_mesh_store_read_compiles_at_the_full_ycsb_table(topo):
    """The mesh store's read (``mesh_index.read_rows_mesh``: search and
    row gather in one program) over all four chips of the ``v5e:2x2``, at
    the full DBx1000 YCSB table: 10,485,760 keys of 1,000-byte rows, a key
    slice and its rows a chip (24 levels, capacity 2^23, one shard).  It
    fits beside the table with no copy of it, and the lanes and rows move
    by ``all_to_all`` alone: the keys out with the flags of the live slots
    (for ``repro.obs``), found and row ids back, the row ids out and the
    rows back."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import mesh_index as mi
    n, D, levels, width = 10 * 2**20, 4, 24, 250
    m = n // D
    mesh = Mesh(topo.devices, ("index",))
    blocks = NamedSharding(mesh, P("index"))
    per_chip = jax.eval_shape(
        functools.partial(shd.build_sharded, n_shards=1, levels=levels),
        jax.ShapeDtypeStruct((m,), jnp.int32),
        jax.ShapeDtypeStruct((m,), jnp.int32))
    assert per_chip.shard_capacity == 2**23
    local = jax.tree.map(lambda a: _spec(blocks, (D,) + a.shape, a.dtype),
                         per_chip)
    replicated = NamedSharding(mesh, P())
    compiled = mi._read_rows_fn(mesh).lower(
        local, _spec(replicated, (D,)), _spec(blocks, (n, width)),
        _spec(replicated, (4096,))).compile()
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24
    hlo = compiled.as_text()
    assert len(re.findall(r"= \S+ all-to-all\(", hlo)) == 6
    assert not re.search(r"all-gather|all-reduce|collective-permute", hlo)

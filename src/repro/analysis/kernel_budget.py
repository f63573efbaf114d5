"""Pallas kernel budget / aliasing checker.

The VMEM estimator ``tile_bytes`` and the budget constants live with the
kernels they size (``kernels/foresight_traverse.py``); the builders and
this checker read the same ones, so their ideas of "fits" cannot drift.

The checker itself never executes a kernel.  ``capture_pallas_calls``
monkeypatches ``pallas_call`` so that invoking a wrapper records each
launch's grid, BlockSpecs, operand shapes/dtypes, aliasing map and
``interpret`` flag, and (in ``capture_only`` mode) returns zeros of the
declared out_shape instead of running Pallas — which lets the probe drive
the wrappers at *production-maximal* shapes (the largest tile the builders
can emit under the budget) in milliseconds, and lets fixture tests capture
deliberately malformed launches that real Pallas would reject.

Checks per captured launch (rule IDs in ``findings``):

* ``GRID-RANK`` — every BlockSpec's ``index_map`` arity matches the grid
  (+ scalar-prefetch operands), its result rank matches the block shape,
  and the block shape matches the operand rank and fits inside it.
* ``VMEM-BUDGET`` — modeled steady-state footprint: each block contributes
  ``block_bytes x 2`` when its tile index changes anywhere across the grid
  visit order (Pallas double-buffers streamed blocks) and ``x 1`` when it
  is grid-invariant (pinned/revisited).  The single largest block (the
  index tile) must fit ``VMEM_BUDGET_BYTES`` — the builder contract — and
  the total must fit ``TOTAL_VMEM_BYTES``.
* ``ALIAS-HAZARD`` — an ``input_output_aliases`` pair whose input and
  output BlockSpecs disagree (shape or index sequence) lets a later grid
  step read a tile an earlier step already overwrote in place.
* ``DMA-SKIP`` — for scalar-prefetch clustered launches: at padding slots
  (``k >= ndist[j]``) every block's index must equal the previous step's
  (the revisited-tile coalescing PR 2's DMA saving depends on); a padding
  slot that names a fresh tile silently re-introduces the copy.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding

# ---------------------------------------------------------------------------
# Budget constants + footprint estimator (owned by the kernels)
# ---------------------------------------------------------------------------

from repro.kernels.foresight_traverse import (VMEM_BUDGET_BYTES,  # noqa: F401
                                              VMEM_LIMIT_BYTES, tile_bytes)

TOTAL_VMEM_BYTES = VMEM_LIMIT_BYTES   # the scoped VMEM each kernel requests


def max_capacity_under_budget(levels: int, foresight: bool,
                              budget: int = VMEM_BUDGET_BYTES,
                              node_width: int = 1) -> int:
    """Largest power-of-two capacity whose tile fits ``budget`` — the
    worst tile any builder path (``auto_shards`` / ``shard_capacity_for``,
    both power-of-two) can actually emit."""
    cap = 8
    while tile_bytes(levels, cap * 2, foresight, node_width) <= budget:
        cap *= 2
    return cap


# ---------------------------------------------------------------------------
# Launch capture
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockCapture:
    block_shape: Tuple[int, ...]
    index_map: Optional[object]       # callable(grid..., *prefetch) -> tuple
    operand_shape: Tuple[int, ...]
    dtype_bytes: int
    is_output: bool
    label: str                        # "in[2]" / "out[0]"


@dataclasses.dataclass
class LaunchCapture:
    kernel_name: str
    grid: Tuple[int, ...]
    blocks: List[BlockCapture]
    num_scalar_prefetch: int
    aliases: Dict[int, int]
    interpret: Optional[bool]


def _kernel_name(kernel) -> str:
    fn = getattr(kernel, "func", kernel)        # unwrap functools.partial
    return getattr(fn, "__name__", str(fn))


def _spec_fields(spec):
    # a squeezed dim (``None``) is a block of extent 1 along that axis
    shape = tuple(d if isinstance(d, int) else 1
                  for d in (getattr(spec, "block_shape", ()) or ()))
    return shape, getattr(spec, "index_map", None)


def _dtype_bytes(x) -> int:
    dt = getattr(x, "dtype", None)
    return getattr(dt, "itemsize", 4) if dt is not None else 4


def _flatten_shapes(out_shape) -> List[object]:
    if isinstance(out_shape, (list, tuple)):
        return list(out_shape)
    return [out_shape]


@contextlib.contextmanager
def capture_pallas_calls(captured: List[LaunchCapture], *,
                         capture_only: bool = False):
    """Intercept ``pallas_call`` launches module-wide.

    All kernel modules bind ``pl`` to ``jax.experimental.pallas`` and look
    ``pallas_call`` up at call time, so patching the module attribute
    captures every launch.  ``capture_only=True`` short-circuits Pallas
    entirely and returns zeros of the declared ``out_shape`` — tracing
    still runs (shapes stay consistent for the wrapper's post-processing)
    but no kernel executes and no spec validation can reject a deliberately
    malformed fixture before we record it.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas

    real = pallas.pallas_call

    def spy(kernel, *args, **kw):
        inner = None if capture_only else real(kernel, *args, **kw)

        def wrapped(*operands):
            captured.append(_capture_launch(kernel, args, kw, operands))
            if inner is not None:
                return inner(*operands)
            outs = [jnp.zeros(tuple(s.shape), s.dtype)
                    for s in _flatten_shapes(kw.get("out_shape")
                                             or (args[0] if args else []))]
            return outs if len(outs) != 1 else outs[0]
        return wrapped

    pallas.pallas_call = spy
    try:
        yield captured
    finally:
        pallas.pallas_call = real


def _capture_launch(kernel, args, kw, operands) -> LaunchCapture:
    grid_spec = kw.get("grid_spec")
    if grid_spec is not None:
        grid = tuple(grid_spec.grid)
        in_specs = list(grid_spec.in_specs)
        out_specs = list(grid_spec.out_specs)
        nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
    else:
        grid = kw.get("grid") or ()
        grid = tuple(grid) if isinstance(grid, (tuple, list)) else (grid,)
        in_specs = list(kw.get("in_specs") or [])
        out_specs = list(kw.get("out_specs") or [])
        nsp = 0
    out_shapes = _flatten_shapes(kw.get("out_shape")
                                 or (args[0] if args else []))
    blocks: List[BlockCapture] = []
    data_operands = operands[nsp:]
    for i, spec in enumerate(in_specs):
        shape, imap = _spec_fields(spec)
        op = data_operands[i] if i < len(data_operands) else None
        blocks.append(BlockCapture(
            block_shape=shape, index_map=imap,
            operand_shape=tuple(getattr(op, "shape", ()) or ()),
            dtype_bytes=_dtype_bytes(op), is_output=False,
            label=f"in[{i}]"))
    for i, spec in enumerate(out_specs):
        shape, imap = _spec_fields(spec)
        o = out_shapes[i] if i < len(out_shapes) else None
        blocks.append(BlockCapture(
            block_shape=shape, index_map=imap,
            operand_shape=tuple(getattr(o, "shape", ()) or ()),
            dtype_bytes=_dtype_bytes(o), is_output=True,
            label=f"out[{i}]"))
    aliases = dict(kw.get("input_output_aliases") or {})
    return LaunchCapture(
        kernel_name=_kernel_name(kernel), grid=grid, blocks=blocks,
        num_scalar_prefetch=nsp, aliases=aliases,
        interpret=kw.get("interpret"))


# ---------------------------------------------------------------------------
# Checks over a captured launch
# ---------------------------------------------------------------------------

def _grid_points(grid: Tuple[int, ...], limit: int = 4096):
    """Row-major (minor axis fastest) visit order, truncated defensively."""
    pts = itertools.product(*(range(g) for g in grid))
    return list(itertools.islice(pts, limit))


def _eval_index(block: BlockCapture, point, prefetch) -> Optional[Tuple]:
    if block.index_map is None:
        return None
    idx = block.index_map(*point, *prefetch)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(i) for i in idx)


def _index_sequence(block: BlockCapture, grid, prefetch
                    ) -> Optional[List[Tuple]]:
    try:
        return [_eval_index(block, p, prefetch) for p in _grid_points(grid)]
    except Exception:
        return None        # arity errors are reported by the rank check


def _default_prefetch(cap: LaunchCapture, operand_shapes) -> Tuple:
    """Zero-filled stand-ins for scalar-prefetch operands when the probe
    does not supply concrete plan arrays."""
    import numpy as np
    return tuple(np.zeros(s, np.int32) for s in operand_shapes)


def check_launch(cap: LaunchCapture, *,
                 prefetch: Optional[Tuple] = None,
                 prefetch_shapes: Sequence[Tuple[int, ...]] = (),
                 ndist=None,
                 budget: int = VMEM_BUDGET_BYTES,
                 total_vmem: int = TOTAL_VMEM_BYTES,
                 path: str = "<pallas_call>") -> List[Finding]:
    """Run every budget/consistency rule over one captured launch."""
    findings: List[Finding] = []
    name = cap.kernel_name
    if prefetch is None:
        prefetch = _default_prefetch(cap, prefetch_shapes) \
            if cap.num_scalar_prefetch else ()

    def flag(rule, msg):
        findings.append(Finding(rule=rule, path=path, line=0, symbol=name,
                                message=msg))

    # -- GRID-RANK ---------------------------------------------------------
    origin = tuple(0 for _ in cap.grid)
    for blk in cap.blocks:
        if blk.index_map is None:
            continue
        try:
            idx = _eval_index(blk, origin, prefetch)
        except TypeError as e:
            flag("GRID-RANK",
                 f"{blk.label} index_map arity mismatch for grid "
                 f"{cap.grid} + {cap.num_scalar_prefetch} prefetch "
                 f"operand(s): {e}")
            continue
        if len(idx) != len(blk.block_shape):
            flag("GRID-RANK",
                 f"{blk.label} index_map returns rank {len(idx)} for "
                 f"block shape {blk.block_shape} (rank "
                 f"{len(blk.block_shape)})")
        if blk.operand_shape and \
                len(blk.block_shape) != len(blk.operand_shape):
            flag("GRID-RANK",
                 f"{blk.label} block rank {len(blk.block_shape)} != "
                 f"operand rank {len(blk.operand_shape)} "
                 f"({blk.operand_shape})")
        elif blk.operand_shape and any(
                b > o for b, o in zip(blk.block_shape, blk.operand_shape)):
            flag("GRID-RANK",
                 f"{blk.label} block {blk.block_shape} exceeds operand "
                 f"{blk.operand_shape}")

    # -- VMEM-BUDGET -------------------------------------------------------
    footprint = 0
    largest = 0
    detail = []
    for blk in cap.blocks:
        nelems = 1
        for d in blk.block_shape:
            nelems *= int(d)
        nbytes = nelems * blk.dtype_bytes
        seq = _index_sequence(blk, cap.grid, prefetch)
        varying = bool(seq) and any(a != b for a, b in zip(seq, seq[1:]))
        buffers = 2 if varying else 1
        footprint += nbytes * buffers
        largest = max(largest, nbytes)
        detail.append(f"{blk.label}={nbytes}B x{buffers}")
    if largest > budget:
        flag("VMEM-BUDGET",
             f"largest tile {largest} B exceeds the index-tile budget "
             f"{budget} B ({'; '.join(detail)})")
    if footprint > total_vmem:
        flag("VMEM-BUDGET",
             f"modeled per-grid-step footprint {footprint} B (double-"
             f"buffered streamed blocks) exceeds VMEM {total_vmem} B "
             f"({'; '.join(detail)})")

    # -- ALIAS-HAZARD ------------------------------------------------------
    n_in = sum(1 for b in cap.blocks if not b.is_output)
    ins = [b for b in cap.blocks if not b.is_output]
    outs = [b for b in cap.blocks if b.is_output]
    for i, o in cap.aliases.items():
        if not (0 <= i < n_in and 0 <= o < len(outs)):
            flag("ALIAS-HAZARD",
                 f"input_output_aliases maps in[{i}]->out[{o}] outside the "
                 f"operand range ({n_in} inputs, {len(outs)} outputs)")
            continue
        bi, bo = ins[i], outs[o]
        if tuple(bi.block_shape) != tuple(bo.block_shape):
            flag("ALIAS-HAZARD",
                 f"aliased in[{i}]/out[{o}] block shapes differ "
                 f"({bi.block_shape} vs {bo.block_shape}): in-place reuse "
                 "writes a differently-tiled buffer a later step re-reads")
            continue
        si = _index_sequence(bi, cap.grid, prefetch)
        so = _index_sequence(bo, cap.grid, prefetch)
        if si is not None and so is not None and si != so:
            step = next(k for k, (a, b) in enumerate(zip(si, so)) if a != b)
            flag("ALIAS-HAZARD",
                 f"aliased in[{i}]/out[{o}] index maps diverge at grid "
                 f"step {step} ({si[step]} vs {so[step]}): the output "
                 "write lands in a tile a later grid step still reads "
                 "(write-after-read)")

    # -- DMA-SKIP ----------------------------------------------------------
    if cap.num_scalar_prefetch and ndist is not None:
        import numpy as np
        nd = np.asarray(ndist)
        pts = _grid_points(cap.grid)
        for blk in cap.blocks:
            seq = _index_sequence(blk, cap.grid, prefetch)
            if seq is None:
                continue
            for t in range(1, len(pts)):
                j, k = pts[t][0], pts[t][-1]
                if k == 0 or k < int(nd[j]):
                    continue                    # a routed (live) slot
                if seq[t] != seq[t - 1]:
                    flag("DMA-SKIP",
                         f"{blk.label}: padding slot (j={j}, k={k}) "
                         f"selects tile {seq[t]} != resident {seq[t - 1]} "
                         "— unrouted slots must coalesce onto the "
                         "already-resident tile (no DMA)")
                    break
    return findings


# ---------------------------------------------------------------------------
# Repo probe: drive every kernel wrapper at production-maximal shapes
# ---------------------------------------------------------------------------

def probe_repo_kernels() -> Tuple[List[Finding], List[str]]:
    """Capture and check every ``pallas_call`` wrapper in ``kernels/``.

    Two sweeps per sharded/clustered wrapper: a small concrete sweep with a
    real ``cluster_queries`` plan (exercises the DMA-skip invariant with
    genuine padding slots) and a production-maximal sweep at the largest
    tile ``auto_shards``/``shard_capacity_for`` can emit under the budget
    (exercises the footprint rule where it binds).  Everything runs in
    ``capture_only`` mode: no kernel executes.
    """
    import importlib

    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.core import sharded as shd
    from repro.kernels import ops as kops
    from repro.kernels.validated_traverse import validated_traverse

    # the package re-exports the foresight_traverse FUNCTION over the
    # module attribute, so fetch the module itself
    ft = importlib.import_module("repro.kernels.foresight_traverse")

    jax.clear_caches()     # jit trace caches would swallow the capture
    findings: List[Finding] = []
    checked: List[str] = []
    QBLK = ft.QBLK
    path = "src/repro/kernels"

    def run(fn, *args, plan=None, prefetch=None, ndist=None, **kw):
        # the wrappers are jitted, so prefetch operands are tracers at
        # capture time — the probe keeps its own concrete copies (either
        # the ClusterPlan or explicit arrays) for index_map evaluation
        caps: List[LaunchCapture] = []
        with capture_pallas_calls(caps, capture_only=True):
            fn(*args, interpret=True, **kw)
        if plan is not None:
            prefetch = (np.asarray(plan.block_sids), np.asarray(plan.ndist))
            ndist = np.asarray(plan.ndist)
        for cap in caps:
            checked.append(cap.kernel_name)
            pf = prefetch if cap.num_scalar_prefetch else None
            findings.extend(check_launch(
                cap, prefetch=pf,
                ndist=ndist if cap.num_scalar_prefetch else None,
                path=path))

    # ---- small concrete sweep (clustered plan with padding slots) --------
    levels, S = 4, 4
    n = 40
    keys = jnp.arange(1, n + 1, dtype=jnp.int32) * 7
    vals = jnp.arange(n, dtype=jnp.int32)
    for foresight in (True, False):
        shl = shd.build_sharded(keys, vals, n_shards=S, levels=levels,
                                foresight=foresight, seed=0)
        # skewed queries: most blocks stay on one shard -> real padding
        q = jnp.concatenate([jnp.full((3 * QBLK,), 14, jnp.int32),
                             keys[-QBLK:] if n >= QBLK else
                             jnp.full((QBLK,), int(keys[-1]), jnp.int32)])
        plan = kops.cluster_queries(shl.boundaries, q, k_shards=2)
        sid = shd.route(shl.boundaries, q)
        if foresight:
            run(ft.foresight_traverse_clustered, shl.shards.fused,
                plan.block_sids, plan.ndist, plan.sid_sorted, plan.q_sorted,
                plan=plan)
            run(ft.foresight_traverse_sharded, shl.shards.fused, sid, q)
        else:
            run(ft.base_traverse_clustered, shl.shards.nxt, shl.shards.keys,
                plan.block_sids, plan.ndist, plan.sid_sorted, plan.q_sorted,
                plan=plan)
            run(ft.base_traverse_sharded, shl.shards.nxt, shl.shards.keys,
                sid, q)

    # ---- production-maximal sweep (the budget rule where it binds) -------
    L = 16
    B = 2 * QBLK
    q = jnp.zeros((B,), jnp.int32)
    for foresight in (True, False):
        cap_max = max_capacity_under_budget(L, foresight)
        if foresight:
            fused1 = jnp.zeros((L, cap_max, 2), jnp.int32)
            run(ft.foresight_traverse, fused1, q)
            run(validated_traverse, fused1,
                jnp.zeros((cap_max,), jnp.int32), q)
            fusedS = jnp.zeros((2, L, cap_max, 2), jnp.int32)
            run(ft.foresight_traverse_sharded, fusedS,
                jnp.zeros((B,), jnp.int32), q)
            bs = np.asarray([[0, 1], [1, 1]], np.int32)
            nd = np.asarray([2, 1], np.int32)
            run(ft.foresight_traverse_clustered, fusedS, jnp.asarray(bs),
                jnp.asarray(nd), jnp.zeros((B,), jnp.int32), q,
                prefetch=(bs, nd), ndist=nd)
        else:
            nxt1 = jnp.zeros((L, cap_max), jnp.int32)
            keys1 = jnp.zeros((cap_max,), jnp.int32)
            run(ft.base_traverse, nxt1, keys1, q)
            nxtS = jnp.zeros((2, L, cap_max), jnp.int32)
            keysS = jnp.zeros((2, cap_max), jnp.int32)
            run(ft.base_traverse_sharded, nxtS, keysS,
                jnp.zeros((B,), jnp.int32), q)
            bs = np.asarray([[0, 1], [1, 1]], np.int32)
            nd = np.asarray([2, 1], np.int32)
            run(ft.base_traverse_clustered, nxtS, keysS, jnp.asarray(bs),
                jnp.asarray(nd), jnp.zeros((B,), jnp.int32), q,
                prefetch=(bs, nd), ndist=nd)

    # ---- fat-node sweeps (node_width > 1): the run tile rides along ------
    # Small concrete sweep: a real clustered plan over a fat sharded index
    # (exercises the fat [1, cap, nw] BlockSpec + DMA-skip on padding).
    nw = 8
    keys8 = jnp.arange(1, 41, dtype=jnp.int32) * 7
    vals8 = jnp.arange(40, dtype=jnp.int32)
    for foresight in (True, False):
        shl = shd.build_sharded(keys8, vals8, n_shards=4, levels=4,
                                foresight=foresight, seed=0, node_width=nw)
        qf = jnp.concatenate([jnp.full((3 * QBLK,), 14, jnp.int32),
                              jnp.full((QBLK,), int(keys8[-1]), jnp.int32)])
        plan = kops.cluster_queries(shl.boundaries, qf, k_shards=2)
        sidf = shd.route(shl.boundaries, qf)
        if foresight:
            run(ft.foresight_traverse_clustered, shl.shards.fused,
                plan.block_sids, plan.ndist, plan.sid_sorted, plan.q_sorted,
                shl.shards.fat_keys, plan=plan)
            run(ft.foresight_traverse_sharded, shl.shards.fused, sidf, qf,
                shl.shards.fat_keys)
        else:
            run(ft.base_traverse_clustered, shl.shards.nxt, shl.shards.keys,
                plan.block_sids, plan.ndist, plan.sid_sorted, plan.q_sorted,
                shl.shards.fat_keys, plan=plan)
            run(ft.base_traverse_sharded, shl.shards.nxt, shl.shards.keys,
                sidf, qf, shl.shards.fat_keys)

    # Production-maximal fat sweep at node_width = QBLK.  Sized to the tile
    # budget, so it fits the TOTAL even double-buffered: capacity counts
    # node slots, so a fitting fat tile still serves node_width-fold more
    # elements than the scalar maximal tile above — the fat layout's whole
    # point — and the gate stays green with no new baselined findings.
    nw = QBLK
    for foresight in (True, False):
        cap_f = max_capacity_under_budget(L, foresight, VMEM_BUDGET_BYTES,
                                          node_width=nw)
        fatk1 = jnp.zeros((cap_f, nw), jnp.int32)
        fatkS = jnp.zeros((2, cap_f, nw), jnp.int32)
        bs = np.asarray([[0, 1], [1, 1]], np.int32)
        nd = np.asarray([2, 1], np.int32)
        if foresight:
            fused1 = jnp.zeros((L, cap_f, 2), jnp.int32)
            run(ft.foresight_traverse, fused1, q, fatk1)
            fusedS = jnp.zeros((2, L, cap_f, 2), jnp.int32)
            run(ft.foresight_traverse_sharded, fusedS,
                jnp.zeros((B,), jnp.int32), q, fatkS)
            run(ft.foresight_traverse_clustered, fusedS, jnp.asarray(bs),
                jnp.asarray(nd), jnp.zeros((B,), jnp.int32), q, fatkS,
                prefetch=(bs, nd), ndist=nd)
        else:
            nxt1 = jnp.zeros((L, cap_f), jnp.int32)
            keys1 = jnp.zeros((cap_f,), jnp.int32)
            run(ft.base_traverse, nxt1, keys1, q, fatk1)
            nxtS = jnp.zeros((2, L, cap_f), jnp.int32)
            keysS = jnp.zeros((2, cap_f), jnp.int32)
            run(ft.base_traverse_sharded, nxtS, keysS,
                jnp.zeros((B,), jnp.int32), q, fatkS)
            run(ft.base_traverse_clustered, nxtS, keysS, jnp.asarray(bs),
                jnp.asarray(nd), jnp.zeros((B,), jnp.int32), q, fatkS,
                prefetch=(bs, nd), ndist=nd)
    return findings, checked

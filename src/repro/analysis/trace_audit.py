"""Jaxpr trace auditor for the public jitted entry points.

For each entry point the auditor:

1. builds representative abstract arguments (two sizes per shape bucket),
2. ``jax.make_jaxpr``-traces the function and walks every nested
   sub-jaxpr via ``walker.iter_eqns`` to flag

   * ``TRACE-CALLBACK`` — host-callback primitives (``pure_callback``,
     ``io_callback``, ``debug_callback``, ``callback``, ``outside_call``,
     ``host_callback``...) anywhere in the trace: each one is a device->
     host round trip per execution, the very miss class Foresight exists
     to skip;
   * ``TRACE-DYNSHAPE`` — output avals whose shapes are not all static
     ints (polymorphic/dynamic dims force re-lowering per shape),

3. jit-executes the entry point across the bucket's sizes and asserts the
   compiled function retraced at most once per shape bucket
   (``TRACE-RETRACE``) — the generalization of PR 5's ad-hoc
   ``_cache_size() == 1`` test: sizes inside one bucket that differ only
   by padded batch must hit the same trace.

Entry points audited (the ISSUE list):

* ``kernels.ops.search_kernel_sharded`` (clustered + plain, fg/base)
* ``core.rebalance_traced.watermark_rebalance_traced`` /
  ``exhaustion_guard_traced``
* the kvcache ``_apply`` path (``PageTable._jit_apply`` = jitted
  ``core.sharded.apply_ops_sharded`` with donation)
* ``core.versioned`` publish/read (``VersionedIndex.search`` /
  ``update`` per read view)

Everything runs on CPU, where the kernels run interpreted, so the
audit is hardware-independent and CI-safe.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.analysis.walker import iter_eqns

#: primitive names that are host round-trips when they appear in a trace
HOST_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback", "host_local_array_to_global_array",
    "global_array_to_host_local_array", "xla_python_cpu_callback",
}


def _flag_prims(jaxpr, path: str, symbol: str) -> List[Finding]:
    out: List[Finding] = []
    seen: set = set()
    for visit in iter_eqns(jaxpr):
        name = visit.prim_name
        if name in HOST_CALLBACK_PRIMS and name not in seen:
            seen.add(name)
            via = " via " + ">".join(visit.path) if visit.path else ""
            out.append(Finding(
                rule="TRACE-CALLBACK", path=path, line=0, symbol=symbol,
                message=f"host-callback primitive `{name}`{via} — one "
                        "device->host round trip per execution"))
    return out


def _flag_dynshape(jaxpr, path: str, symbol: str) -> List[Finding]:
    out: List[Finding] = []
    for var in jaxpr.jaxpr.outvars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", ())
        if not all(isinstance(d, int) for d in shape):
            out.append(Finding(
                rule="TRACE-DYNSHAPE", path=path, line=0, symbol=symbol,
                message=f"output aval shape {shape} is not static — "
                        "forces re-lowering per concrete shape"))
            break
    return out


@dataclasses.dataclass
class EntryPoint:
    """One audited entry point.

    ``make_cases`` returns, per shape bucket, a list of positional-arg
    tuples that must all share ONE trace; ``fn`` is the already-jitted
    callable (a fresh instance per audit so cache counts start at zero).
    """

    name: str
    path: str
    build: Callable[[], Tuple[Callable, Dict[str, List[Tuple]]]]


def _cache_size(jitted) -> Optional[int]:
    try:
        return jitted._cache_size()
    except Exception:
        return None


def audit_entry(ep: EntryPoint) -> List[Finding]:
    import jax

    findings: List[Finding] = []
    try:
        fn, buckets = ep.build()
    except Exception as e:  # surface broken builders as audit failures
        findings.append(Finding(
            rule="TRACE-CALLBACK", path=ep.path, line=0, symbol=ep.name,
            message=f"entry point failed to build for audit: {e!r}"))
        return findings

    first_bucket = next(iter(buckets.values()))
    jaxpr = jax.make_jaxpr(fn)(*first_bucket[0])
    findings.extend(_flag_prims(jaxpr, ep.path, ep.name))
    findings.extend(_flag_dynshape(jaxpr, ep.path, ep.name))

    jitted = jax.jit(fn)
    traces_before = 0
    for bucket_name, cases in buckets.items():
        for args in cases:
            out = jitted(*args)
            jax.block_until_ready(out)
        size = _cache_size(jitted)
        if size is None:
            continue
        traced_here = size - traces_before
        traces_before = size
        if traced_here > 1:
            findings.append(Finding(
                rule="TRACE-RETRACE", path=ep.path, line=0, symbol=ep.name,
                message=f"shape bucket `{bucket_name}` retraced "
                        f"{traced_here}x across {len(cases)} calls "
                        "(expected a single trace per bucket)"))
    return findings


# ---------------------------------------------------------------------------
# Repo entry points
# ---------------------------------------------------------------------------

def _build_search_sharded(foresight: bool, cluster: bool,
                          node_width: int = 1):
    import jax.numpy as jnp
    from repro.core import sharded as shd
    from repro.kernels import ops as kops

    n, levels, S = 64, 4, 4
    keys = jnp.arange(1, n + 1, dtype=jnp.int32) * 5
    vals = jnp.arange(n, dtype=jnp.int32)
    shl = shd.build_sharded(keys, vals, n_shards=S, levels=levels,
                            foresight=foresight, seed=0,
                            node_width=node_width)

    def fn(q):
        return kops.search_kernel_sharded(shl, q, cluster=cluster)

    buckets = {
        "qblk": [(jnp.full((128,), 30, jnp.int32),),
                 (jnp.full((128,), 95, jnp.int32),)],
        "2qblk": [(jnp.full((256,), 30, jnp.int32),)],
    }
    return fn, buckets


def _rebalance_state():
    import jax.numpy as jnp
    from repro.core import sharded as shd
    from repro.core import rebalance_traced as rt

    n, levels, S = 64, 4, 4
    keys = jnp.arange(1, n + 1, dtype=jnp.int32) * 5
    vals = jnp.arange(n, dtype=jnp.int32)
    shl = shd.build_sharded(keys, vals, n_shards=S, levels=levels,
                            foresight=True, seed=0)
    return rt.pad_shards(shl, max_shards=8)


def _build_rebalance(which: str):
    import jax
    import jax.numpy as jnp
    from repro.core import rebalance_traced as rt

    shl = _rebalance_state()
    shl2 = jax.tree.map(jnp.array, shl)   # same shapes, fresh buffers

    if which == "watermark":
        def fn(s):
            return rt.watermark_rebalance_traced(s, seed=0)

        return fn, {"padded8": [(shl,), (shl2,)]}

    def fn(s, op_types, keys):
        return rt.exhaustion_guard_traced(s, op_types, keys, seed=0)

    from repro.core import skiplist as sl
    b = 16
    ops = jnp.full((b,), sl.OP_INSERT, jnp.int32)
    k1 = jnp.arange(1000, 1000 + b, dtype=jnp.int32)
    k2 = jnp.arange(2000, 2000 + b, dtype=jnp.int32)
    return fn, {"padded8-b16": [(shl, ops, k1), (shl2, ops, k2)]}


def _build_kvcache_apply():
    """The PageTable._apply content: jitted ``apply_ops_sharded`` with
    rebalance baked in, at the static shard ceiling, pow2-padded batches.
    Donation is an arg-lifetime property, not a trace property, so the
    audit traces the undonated partial over the same state pytree."""
    import functools
    import jax.numpy as jnp
    from repro.core import skiplist as sl
    from repro.serving.kvcache import PagedCacheConfig, PageTable

    pt = PageTable(PagedCacheConfig(n_pages=256, levels=4, n_shards=2,
                                    rebalance=True, max_shards=4))
    from repro.core import sharded as shd
    base = functools.partial(shd.apply_ops_sharded, rebalance=True, seed=0)
    shl = pt.index

    def fn(op_types, keys, vals):
        return base(shl, op_types, keys, vals)

    k = jnp.arange(1, 9, dtype=jnp.int32)
    v = jnp.arange(8, dtype=jnp.int32)
    ins = jnp.full((8,), sl.OP_INSERT, jnp.int32)
    rd = jnp.full((8,), sl.OP_READ, jnp.int32)
    return fn, {"b8": [(ins, k, v), (ins, k + 100, v), (rd, k, v)]}


def _versioned_index():
    import jax.numpy as jnp
    from repro.core import skiplist as sl
    from repro.core.versioned import VersionedIndex

    n = 64
    keys = jnp.arange(1, n + 1, dtype=jnp.int32) * 3
    vals = jnp.arange(n, dtype=jnp.int32)
    state = sl.build(keys, vals, capacity=256, levels=8, foresight=True,
                     seed=0)
    return VersionedIndex(state)


def _build_versioned(which: str):
    import jax.numpy as jnp
    from repro.core import skiplist as sl
    from repro.core.validated import search_validated

    vi = _versioned_index()

    if which == "read":
        # publish a second version so lag=1 yields a genuinely mixed view
        # (stale fused pointers + fresh authoritative keys): the validated
        # read path the paper's optimistic concurrency depends on
        st2, _ = sl.apply_ops(
            vi.current, jnp.full((4,), sl.OP_INSERT, jnp.int32),
            jnp.arange(500, 504, dtype=jnp.int32),
            jnp.arange(4, dtype=jnp.int32))
        vi.publish(st2)
        view = vi.read_view(lag=1)

        def fn(q):
            return search_validated(view.fused, view.auth_keys, view.vals,
                                    q)

        return fn, {"q128": [(jnp.full((128,), 33, jnp.int32),),
                             (jnp.full((128,), 99, jnp.int32),)]}

    # publish path: the traced content of VersionedIndex.update is one
    # apply_ops fold producing the next version (the publish itself is a
    # host-side list append, deliberately outside the trace)
    state = vi.current

    def fn(op_types, keys, vals):
        return sl.apply_ops(state, op_types, keys, vals)

    k = jnp.arange(200, 208, dtype=jnp.int32)
    v = jnp.arange(8, dtype=jnp.int32)
    ops = jnp.full((8,), sl.OP_INSERT, jnp.int32)
    return fn, {"b8": [(ops, k, v), (ops, k + 50, v)]}


def _mesh_fixture():
    """A 1-device index mesh + small mesh index: the collective data path
    traces identically at any D, and D=1 runs on the default CPU device,
    so the audit stays hardware-independent."""
    import jax.numpy as jnp
    from repro.core import mesh_index as mi
    from repro.launch.mesh import make_index_mesh

    mesh = make_index_mesh(1)
    n = 64
    keys = jnp.arange(1, n + 1, dtype=jnp.int32) * 5
    vals = jnp.arange(n, dtype=jnp.int32)
    mx = mi.build_mesh_index(keys, vals, mesh=mesh, n_devices=1, n_shards=4,
                             levels=4)
    return mesh, mx


def _build_mesh(which: str):
    import jax.numpy as jnp
    from repro.core import mesh_index as mi

    mesh, mx = _mesh_fixture()

    if which == "search":
        def fn(local, db, q):
            return mi.search_mesh(mi.MeshShardedIndex(local, db), q,
                                  mesh=mesh)

        return fn, {
            "q128": [(mx.local, mx.device_boundaries,
                      jnp.full((128,), 30, jnp.int32)),
                     (mx.local, mx.device_boundaries,
                      jnp.full((128,), 95, jnp.int32))],
            "q64": [(mx.local, mx.device_boundaries,
                     jnp.full((64,), 30, jnp.int32))],
        }

    if which == "kernel":
        from repro.kernels import mesh_launch as ml

        def fn(local, db, q):
            return ml.search_kernel_mesh(mi.MeshShardedIndex(local, db), q,
                                         mesh=mesh)

        return fn, {
            "q128": [(mx.local, mx.device_boundaries,
                      jnp.full((128,), 30, jnp.int32)),
                     (mx.local, mx.device_boundaries,
                      jnp.full((128,), 95, jnp.int32))],
        }

    # apply path, with device-local rebalancing on (the serving config)
    from repro.core import skiplist as sl
    emp = mi.empty_mesh_index(mesh=mesh, n_devices=1, n_shards=4, capacity=64,
                              levels=4, key_span=1 << 20)

    def fn(local, db, op_types, keys, vals):
        return mi.apply_ops_mesh(mi.MeshShardedIndex(local, db),
                                 op_types, keys, vals, mesh=mesh,
                                 rebalance=True, seed=0)

    k = jnp.arange(1, 9, dtype=jnp.int32)
    v = jnp.arange(8, dtype=jnp.int32)
    ins = jnp.full((8,), sl.OP_INSERT, jnp.int32)
    rd = jnp.full((8,), sl.OP_READ, jnp.int32)
    return fn, {"b8": [
        (emp.local, emp.device_boundaries, ins, k, v),
        (emp.local, emp.device_boundaries, ins, k + 100, v),
        (emp.local, emp.device_boundaries, rd, k, v)]}


def default_entry_points() -> List[EntryPoint]:
    import functools
    eps = [
        EntryPoint("search_kernel_sharded[fg,clustered]",
                   "src/repro/kernels/ops.py",
                   functools.partial(_build_search_sharded, True, True)),
        EntryPoint("search_kernel_sharded[fg,plain]",
                   "src/repro/kernels/ops.py",
                   functools.partial(_build_search_sharded, True, False)),
        EntryPoint("search_kernel_sharded[base,clustered]",
                   "src/repro/kernels/ops.py",
                   functools.partial(_build_search_sharded, False, True)),
        EntryPoint("search_kernel_sharded[fg,clustered,fat]",
                   "src/repro/kernels/ops.py",
                   functools.partial(_build_search_sharded, True, True, 8)),
        EntryPoint("search_kernel_sharded[fg,plain,fat]",
                   "src/repro/kernels/ops.py",
                   functools.partial(_build_search_sharded, True, False, 8)),
        EntryPoint("watermark_rebalance_traced",
                   "src/repro/core/rebalance_traced.py",
                   functools.partial(_build_rebalance, "watermark")),
        EntryPoint("exhaustion_guard_traced",
                   "src/repro/core/rebalance_traced.py",
                   functools.partial(_build_rebalance, "exhaustion")),
        EntryPoint("PageTable._apply", "src/repro/serving/kvcache.py",
                   _build_kvcache_apply),
        EntryPoint("VersionedIndex.read_view().search",
                   "src/repro/core/versioned.py",
                   functools.partial(_build_versioned, "read")),
        EntryPoint("VersionedIndex.update",
                   "src/repro/core/versioned.py",
                   functools.partial(_build_versioned, "update")),
        EntryPoint("search_mesh[jnp]", "src/repro/core/mesh_index.py",
                   functools.partial(_build_mesh, "search")),
        EntryPoint("apply_ops_mesh[rebalance]",
                   "src/repro/core/mesh_index.py",
                   functools.partial(_build_mesh, "apply")),
        EntryPoint("search_kernel_mesh[fg,clustered]",
                   "src/repro/kernels/mesh_launch.py",
                   functools.partial(_build_mesh, "kernel")),
    ]
    return eps


def run_trace_audit(entry_points: Optional[Sequence[EntryPoint]] = None
                    ) -> Tuple[List[Finding], List[str]]:
    import jax
    jax.clear_caches()
    findings: List[Finding] = []
    audited: List[str] = []
    for ep in (entry_points if entry_points is not None
               else default_entry_points()):
        audited.append(ep.name)
        findings.extend(audit_entry(ep))
    return findings, audited


# ---------------------------------------------------------------------------
# Audit-coverage lint (AUDIT-GAP): the hand-listed entry points must not
# silently fall behind the code
# ---------------------------------------------------------------------------

#: jitted public symbols in core// kernels/ that are deliberately NOT audit
#: entry points — each with the reason the audit does not need them directly
AUDIT_EXEMPT = {
    "build": "bulk constructor — one call per index lifetime, not a "
             "serving-path entry point",
    "build_sharded": "bulk constructor — one call per index lifetime",
    "shard_state": "one-shot monolithic->sharded converter, build-time only",
    "foresight_traverse": "kernel wrapper launched (and trace-audited) via "
                          "search_kernel",
    "base_traverse": "kernel wrapper launched via search_kernel",
    "foresight_traverse_sharded": "kernel wrapper launched via the audited "
                                  "search_kernel_sharded entry points",
    "base_traverse_sharded": "kernel wrapper launched via the audited "
                             "search_kernel_sharded entry points",
    "foresight_traverse_clustered": "kernel wrapper launched via the "
                                    "audited search_kernel_sharded entry "
                                    "points",
    "base_traverse_clustered": "kernel wrapper launched via the audited "
                               "search_kernel_sharded entry points",
    "validated_traverse": "kernel wrapper launched via the audited "
                          "VersionedIndex.read_view().search entry point",
}

#: directories whose @jax.jit publics must be audited or exempted
AUDIT_SCOPE = ("src/repro/core", "src/repro/kernels")


def audited_symbols() -> set:
    """Entry-point names with their ``[variant]`` suffixes stripped."""
    return {ep.name.split("[")[0].split("(")[0].rstrip(".")
            for ep in default_entry_points()}


def audit_coverage(root: str) -> List[Finding]:
    """AUDIT-GAP: flag ``@jax.jit`` public symbols missing from the audit.

    The trace audit runs over a HAND-LISTED set of entry points, so its
    coverage silently shrinks as jitted entry points are added.  This
    pure-AST pass scans ``core/`` and ``kernels/`` for public (non-
    underscore) functions whose decorators mention ``jax.jit`` (either
    ``@jax.jit`` or ``@functools.partial(jax.jit, ...)``) and fails when
    one is neither in ``default_entry_points()`` (bracket variants
    stripped) nor in ``AUDIT_EXEMPT`` with a recorded reason.  Method
    qualnames match on their trailing name (the entry-point list names
    ``PageTable._apply``-style paths).
    """
    import ast
    import os

    covered = audited_symbols()
    covered_tails = {c.split(".")[-1] for c in covered}
    out: List[Finding] = []
    for scope in AUDIT_SCOPE:
        base = os.path.join(root, scope)
        if not os.path.isdir(base):
            continue
        for fname in sorted(os.listdir(base)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(base, fname)
            rel = os.path.join(scope, fname)
            with open(path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if node.name.startswith("_"):
                    continue
                if not any("jax.jit" in ast.unparse(d)
                           for d in node.decorator_list):
                    continue
                if (node.name in covered or node.name in covered_tails
                        or node.name in AUDIT_EXEMPT):
                    continue
                out.append(Finding(
                    rule="AUDIT-GAP", path=rel, line=node.lineno,
                    symbol=node.name,
                    message=f"public @jax.jit symbol `{node.name}` is not "
                            "in trace_audit.default_entry_points() — add "
                            "an EntryPoint or an AUDIT_EXEMPT entry with "
                            "a reason"))
    return out

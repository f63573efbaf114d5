"""Every call's device work ends inside its span: the table that a write
leaves behind is ready when the span closes, even where the program
dispatches part of the call after its own host sync."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax import lax

STORE_D, PAGES = "ycsb_store_4m.ycsb_d", "kv_pages_64k.decode_churn"


@jax.jit
def _late(x):
    """``x`` again, ready only after a few milliseconds of device work."""
    seed = jnp.sum(x.reshape(-1)[:1]).astype(jnp.float32)
    spin = lax.fori_loop(0, 200_000, lambda i, a: a * 1.0000001 + 1e-7, seed)
    return jnp.where(spin < -1e30, jnp.zeros_like(x), x)


def _slowed(apply):
    def go(index, *args):
        index, results = apply(index, *args)
        return jax.tree.map(_late, index), results
    return go


@pytest.mark.parametrize("workload", [PAGES, STORE_D])
def test_write_calls_end_inside_their_spans(run_tiny, monkeypatch,
                                            workload):
    from bench.systems import page_table, store
    made = []
    if workload == PAGES:
        module = page_table

        def make(config):
            pt = page_table.program_page_table(config)
            pt._jit_apply = _slowed(pt._jit_apply)
            made.append(pt)
            return pt
    else:
        import repro.data.store as program_store
        module = store
        monkeypatch.setattr(program_store, "_apply_donated",
                            _slowed(program_store._apply_donated))

        def make(*args):
            made.append(store.program_store(*args))
            return made[-1]

    late, writes = [], [0]
    span = module.span

    @contextlib.contextmanager
    def checked(name, kind, ops=0):
        with span(name, kind, ops):
            yield
        if kind == "write":
            writes[0] += 1
            late.extend(name for leaf in jax.tree.leaves(made[-1].index)
                        if not leaf.is_ready())

    monkeypatch.setattr(module, "span", checked)
    res = run_tiny(workload, make_system=make, seconds=0.5)
    assert res["correct"], res["compared"]
    assert writes[0] > 0 and not late, (writes, late[:5])

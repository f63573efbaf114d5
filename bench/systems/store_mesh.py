"""YCSB traffic on the sample store served over a mesh of chips
(``IndexedSampleStore`` with ``StoreConfig.mesh_devices``).

The unit, the answers kept and the check are ``bench.systems.store``'s:
the same reference (``SortedIndex``) and the same limits.  What differs is
where the table lives: each chip's block of rows is made on that chip
(``mesh_rows``, word for word ``generate.row_hash``'s rows), so no chip
ever holds the whole table, and the store partitions its index the same
way (``core.mesh_index``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate as gen
from bench.systems import store


def mesh_rows(mesh, n: int, width: int, words: tuple[int, int]):
    """The table as one ``[D * m, width]`` array over the mesh's ``index``
    axis in blocks of ``m = ceil(n / D)``, each block made on its chip by
    ``store.make_rows``'s formula; rows past ``n`` are padding, never
    read."""
    from jax.sharding import NamedSharding, PartitionSpec
    D = mesh.devices.size
    m = -(-n // D)
    make = jax.jit(store.make_rows.__wrapped__, static_argnums=(0, 1),
                   out_shardings=NamedSharding(
                       mesh, PartitionSpec(*mesh.axis_names)))
    return make(D * m, width, *(jnp.uint32(x) for x in words))


def mesh_store(cfg, rows, sorted_keys: np.ndarray):
    from repro.data.store import IndexedSampleStore
    s = IndexedSampleStore(cfg, rows=rows, keys=sorted_keys.astype(np.int32))
    jax.block_until_ready((s.index, s.rows))
    return s


class Cell(store.Cell):
    """One deployment of the store over ``mesh_devices`` chips under one
    YCSB mix."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 make_system=mesh_store):
        super().__init__(config, mix, seed, make_system=make_system)

    def setup(self, log) -> None:
        # the store's configuration comes before anything large, so that a
        # program without the mesh store fails here at once
        from repro.data.store import StoreConfig
        from repro.launch.mesh import make_index_mesh
        cfg = StoreConfig(n_samples=self.records, seq_len=self.width - 1,
                          index_levels=self.config["index_levels"],
                          foresight=self.config["foresight"],
                          use_kernel=self.config["use_kernel"],
                          seed=self.seed % 2**31,
                          mesh_devices=self.config["mesh_devices"])
        mesh = make_index_mesh(cfg.mesh_devices)
        self.rng = np.random.default_rng(gen.seed_words(self.seed, 4))
        self.keys = gen.KeyStream(self.seed)
        self.keys.extend(self.records)
        self.initial = self.keys.sorted_keys
        self.inserted = 0
        self.words = gen.row_words(self.seed)
        rows = mesh_rows(mesh, self.records, self.width, self.words)
        self.system = self.make_system(cfg, rows, self.initial)
        del rows
        self.chooser = gen.request_distribution(
            self.mix["request_distribution"], self.records,
            self.mix.get("zipfian_constant", gen.ZIPFIAN_CONSTANT))
        self.read_u = np.empty((0, self.n_read))
        log(f"store_mesh: records={self.records} chips={cfg.mesh_devices} "
            f"row_bytes={self.width * 4} levels={cfg.index_levels} "
            f"batch={self.mix['batch']} (reads {self.n_read}, inserts "
            f"{self.n_ins})")
        for _ in range(2):      # warm every shape the window uses
            self.unit()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in mesh.devices.flat]
        log(f"store_mesh: peak_bytes_in_use per chip after set-up {peaks}")

"""A decode loop's page-table work on ``repro.serving.kvcache.PageTable``.

A unit of the window is one decode step of ``sessions`` live sessions:

1. allocate the step's new pages: a block for each session whose next
   token starts one, and the prompt blocks of sessions admitted in the
   step before;
2. look up the block each session writes this step (one call);
3. every session appends a token; a session that has produced its output
   releases all its blocks and a new one takes its slot.

Calls are issued at power-of-two sizes of at most ``MAX_CALL`` ops: a
batch of allocations or one session's release is split into its binary
parts, the way a TPU server buckets shapes so that a new size never
compiles while it serves.  Set-up warms each of those sizes.

The seed orders the work and does not change its amount: the live
sessions at the start are one set of lengths and progress for every
seed, in slots the seed orders, and admitted sessions come in groups of
``SESSION_GROUP``, each group the same set in an order the seed draws.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import generate as gen
from bench.reference import PageMap
from bench.timing import span

MAX_CALL = 1024
SESSION_GROUP = 64        # sessions admitted from one set, in a drawn order
WARM_SEQ = (1 << 18) - 1  # session ids counted down from here for warm-up


def binary_parts(n: int) -> list[int]:
    """Sizes, each a power of two <= ``MAX_CALL``, that sum to ``n``."""
    parts = [MAX_CALL] * (n // MAX_CALL)
    rest = n % MAX_CALL
    parts += [1 << b for b in range(rest.bit_length() - 1, -1, -1)
              if rest >> b & 1]
    return parts


def program_page_table(config: dict):
    from repro.serving.kvcache import PagedCacheConfig, PageTable
    return PageTable(PagedCacheConfig(
        n_pages=config["n_pages"], page_tokens=config["page_tokens"],
        levels=config["levels"], foresight=config["foresight"],
        use_kernel=config["use_kernel"], max_shards=config["max_shards"],
        seed=config["program_seed"], mesh_devices=1))


class Cell:
    """One page-table configuration under one decode mix."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 make_system=program_page_table):
        self.config, self.mix, self.seed = config, mix, seed
        self.make_system = make_system
        self.page_tokens = config["page_tokens"]
        self.n = mix["sessions"]
        self.log: list = []
        self.failed = 0
        self.live_pages = 0

    # -- calls -------------------------------------------------------------------

    def _alloc(self, seqs: np.ndarray, blocks: np.ndarray) -> int:
        at = 0
        for size in binary_parts(len(seqs)):
            s, b = seqs[at:at + size], blocks[at:at + size]
            with span("alloc", "write", size):
                pages = self.system.alloc(s, b)
                jax.block_until_ready(self.system.index)
            self.log.append(("alloc", s, b, pages))
            at += size
        self.live_pages += len(seqs)
        return len(seqs)

    def _release(self, seq: int, n_blocks: int) -> int:
        at = 0
        for size in binary_parts(n_blocks):
            b = np.arange(at, at + size, dtype=np.int64)
            with span("release", "write", size):
                freed = self.system.release_blocks(seq, b)
                # the delete is dispatched after the call's host sync
                jax.block_until_ready(self.system.index)
            self.log.append(("release", seq, b, freed))
            at += size
        self.live_pages -= n_blocks
        return n_blocks

    def _lookup(self, seqs: np.ndarray, blocks: np.ndarray) -> int:
        with span("lookup", "read", len(seqs)):
            found, pages = self.system.lookup(seqs, blocks)
            jax.block_until_ready((found, pages))
        self.log.append(("lookup", seqs, blocks, found, pages))
        return len(seqs)

    # -- set-up ------------------------------------------------------------------

    def setup(self, log) -> None:
        self.rng = np.random.default_rng(gen.seed_words(self.seed, 4))
        self.system = self.make_system(self.config)
        self.lengths = np.empty((0, 2), np.int64)
        # warm every call size: one throwaway session per size
        for i, size in enumerate(binary_parts(2 * MAX_CALL - 1)):
            self._alloc(np.full(size, WARM_SEQ - i, np.int64),
                        np.arange(size, dtype=np.int64))
            self._release(WARM_SEQ - i, size)
        # the live sessions, each part way through its output: one set of
        # lengths and progress for every seed, in slots the seed orders,
        # so every seed starts from the same live state
        tokens = gen.sessions(self.mix["prompt_tokens"],
                              self.mix["output_tokens"], self.n)
        progress = np.random.default_rng(self.n + 1).permutation(
            (np.arange(self.n) + 0.5) / self.n)
        done = (progress * tokens[:, 1]).astype(np.int64)
        order = self.rng.permutation(self.n)
        self.seq = np.arange(self.n, dtype=np.int64)
        self.next_seq = self.n
        self.tokens = (tokens[:, 0] + done)[order]
        self.left = (tokens[:, 1] - done)[order]
        self.pending = list(range(self.n))  # slots admitted, not allocated
        self.unit()
        log(f"page_table: pages={self.config['n_pages']} "
            f"sessions={self.n} live_pages={self.live_pages}")
        self.unit()

    def _admit(self, slot: int) -> None:
        if not len(self.lengths):
            with span("generate", "host"):
                group = gen.sessions(self.mix["prompt_tokens"],
                                     self.mix["output_tokens"],
                                     SESSION_GROUP)
                self.lengths = group[self.rng.permutation(SESSION_GROUP)]
        (prompt, output), self.lengths = self.lengths[0], self.lengths[1:]
        self.seq[slot] = self.next_seq
        self.next_seq += 1
        self.tokens[slot] = prompt
        self.left[slot] = output
        self.pending.append(slot)

    # -- the window's unit ---------------------------------------------------------

    def live(self) -> int:
        return self.live_pages

    def unit(self) -> int:
        pt = self.page_tokens
        # 1. the step's new pages: prompts admitted last step, then blocks
        #    that this step's token opens
        s_new, b_new = [], []
        for slot in self.pending:
            nb = -(-int(self.tokens[slot]) // pt)
            s_new.append(np.full(nb, self.seq[slot]))
            b_new.append(np.arange(nb))
        self.pending = []
        opens = np.flatnonzero(self.tokens % pt == 0)
        s_new.append(self.seq[opens])
        b_new.append(self.tokens[opens] // pt)
        ops = self._alloc(np.concatenate(s_new).astype(np.int64),
                          np.concatenate(b_new).astype(np.int64))
        # 2. the block each session writes this step
        ops += self._lookup(self.seq.copy(), self.tokens // pt)
        # 3. one token each; finished sessions leave
        self.tokens += 1
        self.left -= 1
        for slot in np.flatnonzero(self.left == 0).tolist():
            ops += self._release(int(self.seq[slot]),
                                 -(-int(self.tokens[slot]) // pt))
            self._admit(slot)
        return ops

    # -- the check -------------------------------------------------------------------

    def fetch(self) -> None:
        self.n_live = int(self.system.n_live)
        self.log = [ev[:3] + tuple(np.asarray(x) for x in ev[3:])
                    for ev in self.log]
        self.system = None

    def check(self, log) -> dict:
        ref = PageMap(self.config["n_pages"])
        n = {"page_mismatch": 0, "double_mapped": 0, "release_mismatch": 0}
        compared = {"lookups": 0, "allocs": 0, "releases": 0}
        for ev in self.log:
            if ev[0] == "alloc":
                _, s, b, pages = ev
                n["double_mapped"] += ref.alloc(s, b, pages)
                compared["allocs"] += len(s)
            elif ev[0] == "lookup":
                _, s, b, found, pages = ev
                want_f, want_p = ref.lookup(s, b)
                n["page_mismatch"] += int(np.sum(
                    (found != want_f) | (want_f & (pages != want_p))))
                compared["lookups"] += len(s)
            else:
                _, seq, b, freed = ev
                n["release_mismatch"] += int(freed != ref.release(seq, b))
                compared["releases"] += len(b)
        n["live_mismatch"] = abs(self.n_live - len(ref))
        log(f"check: compared {compared}, n_live={self.n_live} against "
            f"bench.reference.PageMap ({len(ref)} mapped)")
        return {name: (v, 0) for name, v in n.items()}

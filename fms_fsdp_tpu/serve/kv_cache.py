"""Paged KV-cache: a fixed-size-page pool with per-sequence page tables.

The serving engine never materializes a (B, S_max, Nkv, H) cache per
sequence — that layout wastes HBM on every request shorter than S_max
and couples batch membership to memory layout. Instead the cache is a
shared pool of fixed-size pages, one pool for each thing a position
leaves behind. What that is, the family declares (``pools``: name ->
the shape of one position's entry). By default keys and values per kv
head:

    pools["k"]: (L, P, page_size, Nkv, H)   P = num_pages

and for latent attention (models/sarvam.py) one pool and no head axis,
``pools["latent"]: (L, P, page_size, latent_dim)``. A pool may hold fewer
rows a page than positions (``page_rows``: the index cache of
models/minicpm_sala.py keeps one compressed key for every 16 positions,
``pools["kc"]: (L, P, 4, H)`` beside pages of 64), so one allocator and
one table serve caches of more than one grain. The allocator, the
page tables, ``write_prompt``, the page export and import and ``defrag``
work on whatever pools were declared. Each sequence owns an ordered list
of page ids; logical cache
position ``t`` of a sequence lives at (pages[t // page_size],
t % page_size). The page table handed to the decode step is the padded
(B, max_pages) int32 matrix of those lists.

Reserved pages (the allocator never hands them out):

- page 0, the **zero page**: every unallocated page-table slot points
  here. It is never written, so gathering a sequence's table yields
  exactly the dense cache layout — real pages then zeros — which is
  what makes the reference paged-attention path bit-identical to the
  dense decode path (ops/paged_attention.py).
- page 1, the **scratch page**: idle batch slots in the fixed-shape
  decode step still execute a write; their page-table rows point every
  slot here so the garbage lands where no live sequence ever reads.

Allocation is host-side Python (deterministic, lowest-index-first via a
heap) with all-or-nothing semantics: ``ensure`` either extends a
sequence to the requested capacity or changes nothing and returns False
— the scheduler turns False into defer-or-evict. ``defrag`` compacts
allocated pages onto the lowest indices (a gather permutation applied
to the device pools, page tables rewritten) — paged attention needs no
contiguity, so this is a locality / pool-shrink maintenance op, with
moves counted for the obs registry.

Quantized page storage (``quant="int8"|"fp8"``) stores 1-byte values
plus fp32 per-row scales via the ops/quant.py kv wire format
(per-(position, kv-head) absmax along the head dim), cutting resident
KV bytes ~2x at bf16 compute; the reference read path dequantizes only
the gathered pages, never the pool.
"""

import functools
import heapq
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from fms_fsdp_tpu.ops.quant import kv_quantize

ZERO_PAGE = 0
SCRATCH_PAGE = 1
RESERVED_PAGES = 2

_QUANT_STORE_DTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_pages(pool, ids, pages):
    """``pages`` (L, n, page_size, *entry) into pool pages ``ids``."""
    return pool.at[:, ids].set(pages.astype(pool.dtype))


class PagedKVCache:
    """Device pools + the host-side page allocator."""

    def __init__(
        self,
        n_layers: int,
        num_pages: int,
        page_size: int,
        n_kv_heads: int = 0,
        head_dim: int = 0,
        dtype=jnp.bfloat16,
        quant: str = "none",
        shardings: Optional[Dict] = None,
        pools: Optional[Dict[str, tuple]] = None,
        page_rows: Optional[Dict[str, int]] = None,
        scratch_tail: bool = False,
    ):
        assert num_pages > RESERVED_PAGES, (
            f"num_pages={num_pages}: pages 0/1 are reserved (zero/scratch), "
            "the pool needs at least one allocatable page"
        )
        if quant not in ("none", "int8", "fp8"):
            raise ValueError(f"unknown kv cache quant: {quant!r}")
        self.n_layers = n_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant = quant
        # a family whose prefill program is longer than the prompt it was
        # given (serve/families/minicpm_sala.py: one program a doubling of
        # the bucket) asks for this: ``write_prompt`` then takes values that
        # cover more pages than the sequence holds and lands the tail,
        # the prefill's zero padding, on the scratch page, so that a
        # write's shape follows the program and not the prompt
        self.scratch_tail = scratch_tail

        # name -> shape of one position's entry, in write_prompt's order
        self.entry_shapes = dict(pools) if pools else {
            "k": (n_kv_heads, head_dim),
            "v": (n_kv_heads, head_dim),
        }
        if quant != "none" and pools:
            raise ValueError(
                "quantized page storage is the k/v pools' wire format "
                f"(per kv head): pools {sorted(pools)} store full-width"
            )
        # name -> rows a page of that pool holds: a row a position unless
        # the family says otherwise (``write_prompt`` takes that many rows
        # a page of such a pool)
        self.page_rows = {
            name: int((page_rows or {}).get(name, page_size))
            for name in self.entry_shapes
        }
        store = _QUANT_STORE_DTYPE.get(quant, dtype)
        self.pools = {
            name: jnp.zeros(
                (n_layers, num_pages, self.page_rows[name]) + tuple(e), store
            )
            for name, e in self.entry_shapes.items()
        }
        if quant != "none":
            sshape = (n_layers, num_pages, page_size, n_kv_heads, 1)
            self.pools["k_scale"] = jnp.zeros(sshape, jnp.float32)
            self.pools["v_scale"] = jnp.zeros(sshape, jnp.float32)
        if shardings:
            # serving-layout placement (leaf name -> jax Sharding):
            # pools born sharded stay sharded — every later .at[].set /
            # gather propagates the operand's sharding under GSPMD
            self.pools = {
                name: (
                    jax.device_put(pool, shardings[name])
                    if name in shardings
                    else pool
                )
                for name, pool in self.pools.items()
            }

        self._free: List[int] = list(range(RESERVED_PAGES, num_pages))
        heapq.heapify(self._free)
        self._seq_pages: Dict[int, List[int]] = {}
        self._seq_tokens: Dict[int, int] = {}
        # accounting (drained into serve.* gauges by the engine)
        self.alloc_count = 0
        self.free_count = 0
        self.failed_allocs = 0
        self.defrag_moves = 0
        # bumped whenever any page table could have changed (alloc /
        # free / defrag) — the engine keys its cached device page-table
        # upload on it so steady-state decode steps (no allocation
        # events page_size-1 steps out of page_size) re-upload nothing
        self.table_version = 0

    # -- queries -----------------------------------------------------------

    @property
    def pages_in_use(self) -> int:
        return sum(len(p) for p in self._seq_pages.values())

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def capacity_tokens(self) -> int:
        return (self.num_pages - RESERVED_PAGES) * self.page_size

    def fragmentation(self) -> float:
        """Internal fragmentation: the fraction of allocated slots not
        holding a token (tail waste of each sequence's last page)."""
        pages = self.pages_in_use
        if pages == 0:
            return 0.0
        slots = pages * self.page_size
        tokens = sum(self._seq_tokens.values())
        return (slots - tokens) / slots

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_ensure(self, seq_id: int, n_tokens: int) -> bool:
        have = len(self._seq_pages.get(seq_id, ()))
        return self.pages_needed(n_tokens) - have <= len(self._free)

    def tokens_of(self, seq_id: int) -> int:
        return self._seq_tokens.get(seq_id, 0)

    # -- alloc / free ------------------------------------------------------

    def ensure(self, seq_id: int, n_tokens: int) -> bool:
        """Grow seq_id's allocation to hold ``n_tokens`` cache slots.
        All-or-nothing: on insufficient free pages nothing changes and
        False is returned (the scheduler defers or evicts)."""
        pages = self._seq_pages.setdefault(seq_id, [])
        need = self.pages_needed(n_tokens) - len(pages)
        if need > len(self._free):
            self.failed_allocs += 1
            return False
        for _ in range(max(0, need)):
            pages.append(heapq.heappop(self._free))
            self.alloc_count += 1
        if need > 0:
            self.table_version += 1
        self._seq_tokens[seq_id] = max(
            self._seq_tokens.get(seq_id, 0), n_tokens
        )
        return True

    def free(self, seq_id: int) -> int:
        """Release every page of seq_id; returns how many."""
        pages = self._seq_pages.pop(seq_id, [])
        self._seq_tokens.pop(seq_id, None)
        for p in pages:
            heapq.heappush(self._free, p)
        self.free_count += len(pages)
        if pages:
            self.table_version += 1
        return len(pages)

    def pages_of(self, seq_id: int) -> List[int]:
        return list(self._seq_pages.get(seq_id, ()))

    # -- page tables -------------------------------------------------------

    def page_table_row(self, seq_id: Optional[int], max_pages: int):
        """One padded page-table row: allocated pages, then the zero
        page (so gathers read zeros past the allocation). ``None`` (an
        idle batch slot) maps every slot to the scratch page — its
        fixed-shape decode writes land where nothing live reads."""
        if seq_id is None:
            return [SCRATCH_PAGE] * max_pages
        pages = self._seq_pages.get(seq_id, [])
        assert len(pages) <= max_pages, (
            f"sequence {seq_id} holds {len(pages)} pages > max_pages="
            f"{max_pages} (max_seq_len / page_size mismatch)"
        )
        return pages + [ZERO_PAGE] * (max_pages - len(pages))

    def page_table(self, seq_ids: List[Optional[int]], max_pages: int):
        import numpy as np

        return np.asarray(
            [self.page_table_row(s, max_pages) for s in seq_ids],
            dtype=np.int32,
        )

    # -- writes ------------------------------------------------------------

    def write_prompt(self, seq_id: int, *values):
        """Scatter a prefilled prompt into seq_id's pages: one (L, S_pad,
        *entry) array for each declared pool, in their order (``k, v``
        for the default pools; ``S_pad / page_size * page_rows[name]``
        rows for a pool of another grain). ``S_pad`` must be a page multiple
        covering the prompt (positions past the prompt are the prefill's
        zero padding, which keeps page tails dense-identical; pages past
        the sequence's own go to the scratch page under ``scratch_tail``).
        Call ``ensure`` first."""
        assert len(values) == len(self.entry_shapes), (
            len(values), list(self.entry_shapes))
        L, rows = values[0].shape[0], values[0].shape[1]
        first = self.page_rows[next(iter(self.entry_shapes))]
        assert rows % first == 0, (rows, first)
        n = rows // first  # pages
        pages = self._seq_pages.get(seq_id, [])
        assert n <= len(pages) or self.scratch_tail, (
            f"write_prompt needs {n} pages, sequence {seq_id} holds "
            f"{len(pages)} — call ensure() first"
        )
        ids = jnp.asarray(
            pages[:n] + [SCRATCH_PAGE] * (n - len(pages)), jnp.int32
        )
        paged = {
            name: x.reshape((L, n, self.page_rows[name]) + tuple(entry))
            for (name, entry), x in zip(self.entry_shapes.items(), values)
        }
        if self.quant == "none":
            # the pool is donated: the write moves the prompt's pages and
            # not the pool (an eager ``.at[].set`` makes a second pool
            # first: 2.4 GB beside a 2.4 GB latent pool, PERF.md PR 31)
            self.pools = {
                name: _write_pages(self.pools[name], ids, x)
                for name, x in paged.items()
            }
        else:
            qk, sk = kv_quantize(paged["k"], self.quant)
            qv, sv = kv_quantize(paged["v"], self.quant)
            self.pools = {
                "k": self.pools["k"].at[:, ids].set(qk),
                "v": self.pools["v"].at[:, ids].set(qv),
                "k_scale": self.pools["k_scale"].at[:, ids].set(sk),
                "v_scale": self.pools["v_scale"].at[:, ids].set(sv),
            }

    # -- page export / import (serve/disagg/ handoff) ----------------------

    def gather_pages(self, seq_id: int) -> Dict[str, "object"]:
        """Read seq_id's pages out of the device pools as host arrays:
        leaf name -> (L, n_pages, page_size, *entry) ndarray in the
        pool's STORAGE dtype — int8/fp8 pages come out as their 1-byte
        values plus the fp32 scale leaves, never dequantized (the
        handoff ships what the pool holds, bit for bit)."""
        import numpy as np

        pages = self._seq_pages.get(seq_id, [])
        assert pages, f"sequence {seq_id} holds no pages to gather"
        ids = jnp.asarray(pages, jnp.int32)
        return {
            name: np.asarray(pool[:, ids])
            for name, pool in self.pools.items()
        }

    def scatter_pages(self, seq_id: int, arrays: Dict, n_tokens: int) -> bool:
        """The unpack half: allocate exactly the shipped page count for
        ``seq_id`` (all-or-nothing, like ``ensure``) and write each leaf
        into the freshly allocated page ids. Reserved pages are never
        written — page 0 stays all-zero (the bit-parity root) and page 1
        stays scratch. ``n_tokens`` is the source pool's token
        accounting for the sequence (its ``tokens_of``)."""
        from fms_fsdp_tpu.serve.disagg.handoff import HandoffError

        # Wire-derived input: every structural mismatch is a typed
        # HandoffError, and every check that can run BEFORE allocation
        # does — a frame rejected after ``ensure`` would leak the
        # freshly allocated pages if the raise skipped the free.
        if set(arrays) != set(self.pools):
            raise HandoffError(
                f"handoff leaves {sorted(arrays)} do not match this "
                f"pool's {sorted(self.pools)} — kv_quant mismatch "
                f"between replicas"
            )
        n = int(next(iter(arrays.values())).shape[1])
        for name, pool in self.pools.items():
            want = (pool.shape[0], n) + tuple(pool.shape[2:])
            got = tuple(arrays[name].shape)
            if got != want:
                raise HandoffError(
                    f"handoff leaf {name!r} has shape {got}, this "
                    f"pool expects {want} — page geometry mismatch"
                )
        if not self.ensure(seq_id, n * self.page_size):
            return False
        self._seq_tokens[seq_id] = n_tokens
        pages = self._seq_pages[seq_id]
        assert len(pages) == n, (len(pages), n)
        ids = jnp.asarray(pages, jnp.int32)
        try:
            self.pools = {
                name: pool.at[:, ids].set(
                    jnp.asarray(arrays[name], pool.dtype)
                )
                for name, pool in self.pools.items()
            }
        except Exception as e:
            # free what this import just allocated before surfacing —
            # the pool must account identically to before the attempt
            self.free(seq_id)
            raise HandoffError(
                f"handoff scatter failed after page allocation "
                f"(pages freed): {e}"
            ) from e
        return True

    # -- defrag ------------------------------------------------------------

    def defrag(self) -> int:
        """Compact allocated pages onto the lowest pool indices.

        Builds the old->new permutation (sequence admission order, page
        order within each sequence), gathers the device pools through
        it, rewrites the per-sequence page lists, and resets the free
        heap to the tail. Returns the number of pages moved (also
        accumulated in ``defrag_moves``). Reserved pages never move.
        """
        import numpy as np

        perm = np.arange(self.num_pages)
        next_id = RESERVED_PAGES
        moves = 0
        new_lists: Dict[int, List[int]] = {}
        for seq_id in self._seq_pages:  # dict preserves admission order
            new_pages = []
            for old in self._seq_pages[seq_id]:
                if old != next_id:
                    moves += 1
                perm[next_id] = old
                new_pages.append(next_id)
                next_id += 1
            new_lists[seq_id] = new_pages
        if moves:
            # free pages fill the tail in any order; their content is
            # junk by contract (only table-listed pages are ever read)
            used = set(perm[:next_id])
            tail = [p for p in range(self.num_pages) if p not in used]
            perm[next_id:] = tail
            idx = jnp.asarray(perm, jnp.int32)
            self.pools = {k: p[:, idx] for k, p in self.pools.items()}
            self._seq_pages = new_lists
        self._free = list(range(next_id, self.num_pages))
        heapq.heapify(self._free)
        self.defrag_moves += moves
        if moves:
            self.table_version += 1
        return moves

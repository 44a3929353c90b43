"""Paged KV-cache for continuous-batching LM decode.

ONE preallocated device pool per pool-kind (K and V), shaped

    [n_layers, n_pages + 1, *page]

where ``page`` is what the MODEL says one page of that pool holds
(``Model.cache_spec``): ``[page_size, n_heads * head_dim]`` twice for
per-head keys and values (a position's heads side by side in one
lane-dense row), ``[page_size, kv_lora]`` and ``[qk_rope,
page_size]`` for a latent cache (one normed latent row and one rotated key
row a position, shared by all heads). The free-list, the page tables, the
scratch page and the conservation counters know nothing of the kind, so
the compiled decode/prefill programs see a FIXED shape forever: pages
are handed out and returned by a host-side free-list, and the programs
receive gather/scatter *indices* (per-sequence page tables) instead of
resized buffers. A model whose layers do not all keep pages says over how
many layers the paged pools run, and which arrays it holds a SLOT and not a
page (``slots``: a recurrent state, a row every few positions): those are
``[layers, max_seqs, ...]``, allocated, reset, placed and counted here
beside the pages (``pool_bytes_by_kind``), and ride in the second pool,
then a pytree ``{"v": the V pages, name: array}``; a slot's arrays belong
to whoever holds the slot and the programs overwrite them as a sequence
starts. Index ``n_pages`` is the SCRATCH page — never owned by
any sequence; inactive batch slots and the padding tail of a prefill
scatter are routed there, so every write in the jitted step is
unconditional (no dynamic shapes, no host-side branching) and the
garbage lands somewhere no read ever looks (reads are masked by
``seq_lens``).

Admission is worst-case: a sequence reserves
``pages_needed(prompt_len + max_new_tokens)`` pages up front, so a
running sequence can NEVER hit an out-of-pages fault mid-generation —
exhaustion is an admission-time signal (:class:`KVExhausted`), which the
scheduler turns into queueing, not corruption. Eviction (finish,
deadline, abort) returns the pages; the free-list keeps conservation
counters (``pages_out_total``/``pages_in_total``) so the chaos oracle
can assert pages_out == pages_in after drain.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


class KVExhausted(RuntimeError):
    """Admission could not reserve the sequence's worst-case pages."""


def pages_needed(total_len: int, page_size: int) -> int:
    """Pages covering ``total_len`` cache positions (ceil division)."""
    if total_len <= 0:
        return 0
    return -(-int(total_len) // int(page_size))


class FreeList:
    """Host-side page allocator over physical pages ``0..n_pages-1``.

    Not thread-safe by itself — the scheduler serializes access (one
    decode loop owns it). Double frees and foreign pages raise: a page
    accounting bug must surface as an exception, not as two sequences
    silently sharing a page.
    """

    def __init__(self, n_pages: int):
        if n_pages <= 0:
            raise ValueError(f"n_pages must be positive, got {n_pages}")
        self.n_pages = int(n_pages)
        # pop() from the tail hands out ascending page ids — makes unit
        # tests deterministic and keeps early pages hot
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._out: set = set()
        self.pages_out_total = 0
        self.pages_in_total = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Reserve ``n`` pages or raise :class:`KVExhausted` (atomic:
        either all ``n`` come out or none do)."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > len(self._free):
            raise KVExhausted(
                f"need {n} KV pages, only {len(self._free)} free of "
                f"{self.n_pages}"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._out.update(pages)
        self.pages_out_total += n
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for pg in pages:
            pg = int(pg)
            if pg not in self._out:
                raise ValueError(
                    f"page {pg} returned but not outstanding "
                    "(double free, or a page this list never issued)"
                )
            self._out.discard(pg)
            self._free.append(pg)
            self.pages_in_total += 1

    def conserved(self) -> bool:
        """True iff every page ever issued came back — the chaos
        oracle's KV-conservation invariant after drain."""
        return (
            not self._out
            and len(self._free) == self.n_pages
            and self.pages_in_total == self.pages_out_total
        )


class PagedKVCache:
    """Pools + per-slot page tables + free-list for up to ``max_seqs``
    concurrent sequences.

    The pools are jax arrays threaded FUNCTIONALLY through the jitted
    programs (each step returns updated pools; the cache just holds the
    latest reference) — nothing here ever resizes device memory. The
    page tables are a host ``int32 [max_seqs, max_pages_per_seq]``
    array, scratch-filled for unowned entries, handed to the decode
    step as a plain input every iteration (a few hundred bytes of H2D).
    """

    def __init__(
        self,
        *,
        n_layers: int,
        page_size: int,
        n_pages: int,
        max_seqs: int,
        max_pages_per_seq: int,
        k_page: Sequence[int],
        v_page: Sequence[int],
        dtype=None,
        kind: str = "kv",
        slots: Optional[dict] = None,
    ):
        """``k_page`` / ``v_page``: the shape of one page of each pool
        (``[page_size, n_heads * head_dim]`` twice for per-head K and V),
        over ``n_layers`` layers: those that keep pages. ``kind`` names what
        the pages hold. ``slots``: ``{name: {"layers", "row", "dtype"[,
        "positions_per_row"]}}``, arrays held a slot: ``[layers, max_seqs,
        *row]``, or with ``positions_per_row`` one row every so many positions
        of the longest context, ``[layers, max_seqs, rows, *row]``."""
        import jax.numpy as jnp  # deferred: FreeList stays importable sans jax

        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if max_pages_per_seq <= 0 or max_pages_per_seq > n_pages:
            raise ValueError(
                f"max_pages_per_seq={max_pages_per_seq} must be in "
                f"1..n_pages ({n_pages})"
            )
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.scratch = self.n_pages  # the sacrificial page index
        self.max_seqs = int(max_seqs)
        self.max_pages_per_seq = int(max_pages_per_seq)
        lead = (int(n_layers), self.n_pages + 1)
        self._shapes = (lead + tuple(k_page), lead + tuple(v_page))
        self._dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)
        self.kind = str(kind)
        # name -> (shape, dtype, positions a row or None)
        self._slots = {}
        for name, a in (slots or {}).items():
            per = a.get("positions_per_row")
            rows = () if per is None else (pages_needed(self.max_context, per),)
            self._slots[name] = ((int(a["layers"]), self.max_seqs, *rows, *a["row"]),
                                 jnp.dtype(a["dtype"]), per)
        self.reset_pools()
        self.free_list = FreeList(self.n_pages)
        self.page_tables = np.full(
            (self.max_seqs, self.max_pages_per_seq), self.scratch, np.int32
        )
        self._slot_pages: dict = {}

    def reset_pools(self) -> None:
        """Allocate both pools anew, zeroed (construction; and the
        engine's recovery when a program that took the pools DONATED
        raised and left them deleted — it fails every sequence that had
        rows in them, so zeros are a sound state)."""
        import jax.numpy as jnp

        self.k_pool, self.v_pool = (jnp.zeros(s, self._dtype) for s in self._shapes)
        if self._slots:
            self.v_pool = {"v": self.v_pool, **{
                name: jnp.zeros(shape, dtype) for name, (shape, dtype, _) in self._slots.items()}}

    def pools_deleted(self) -> bool:
        """Whether a program that took the pools donated has left any of
        their arrays deleted."""
        import jax

        return any(a.is_deleted() for a in jax.tree_util.tree_leaves((self.k_pool, self.v_pool)))

    @property
    def pool_bytes_by_kind(self) -> dict:
        """``{kind: bytes}``: the two paged pools under the kind the model
        gave them, every array held a slot under its name."""
        out = {self.kind: sum(int(np.prod(s)) for s in self._shapes) * self._dtype.itemsize}
        for name, (shape, dtype, _) in self._slots.items():
            out[name] = int(np.prod(shape)) * dtype.itemsize
        return out

    @property
    def pool_bytes(self) -> int:
        """Bytes of everything held here together."""
        return sum(self.pool_bytes_by_kind.values())

    @property
    def bytes_per_position_by_kind(self) -> dict:
        """``{kind: bytes}`` one more position takes over all layers: a page
        of each pool holds ``page_size`` positions, whatever its kind; an
        array held a slot counts where it grows with the context (a row
        every few positions), a recurrent state does not."""
        by_kind = self.pool_bytes_by_kind
        out = {self.kind: by_kind[self.kind] // ((self.n_pages + 1) * self.page_size)}
        for name, (shape, _, per) in self._slots.items():
            if per is not None:
                out[name] = by_kind[name] // (self.max_seqs * shape[2] * per)
        return out

    @property
    def bytes_per_position(self) -> int:
        return sum(self.bytes_per_position_by_kind.values())

    @property
    def max_context(self) -> int:
        """Longest sequence (prompt + generated) a slot can hold."""
        return self.max_pages_per_seq * self.page_size

    def reserve(self, slot: int, total_len: int) -> List[int]:
        """Reserve worst-case pages for a sequence of ``total_len``
        positions into ``slot``. Raises :class:`KVExhausted` when the
        free-list cannot cover it; raises ValueError for a slot already
        holding pages (the scheduler must release first)."""
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already holds pages")
        need = pages_needed(total_len, self.page_size)
        if need > self.max_pages_per_seq:
            raise KVExhausted(
                f"sequence needs {need} pages "
                f"({total_len} positions / page_size {self.page_size}) "
                f"but a slot holds at most {self.max_pages_per_seq}"
            )
        pages = self.free_list.alloc(need)
        self.page_tables[slot, :] = self.scratch
        self.page_tables[slot, :need] = pages
        self._slot_pages[slot] = pages
        return pages

    def release(self, slot: int) -> int:
        """Return ``slot``'s pages to the free-list (idempotent for a
        slot holding none). Returns how many pages came back."""
        pages = self._slot_pages.pop(slot, None)
        self.page_tables[slot, :] = self.scratch
        if not pages:
            return 0
        self.free_list.free(pages)
        return len(pages)

    def release_all(self) -> int:
        """Drain-time sweep: return every outstanding slot's pages."""
        return sum(self.release(s) for s in list(self._slot_pages))

    @property
    def pages_used(self) -> int:
        return self.free_list.n_used

    @property
    def pages_free(self) -> int:
        return self.free_list.n_free

"""Iteration-level scheduler for continuous-batching decode.

Pure host-side logic between decode steps — the policy half of the
subsystem, kept free of jax/threading so it unit-tests in microseconds:

* **Admission** (continuous mode): FIFO from the waiting queue into free
  batch slots, each admit reserving its WORST-CASE pages
  (``prompt + max_new_tokens``) so a running sequence can never die of
  page exhaustion mid-generation; a reservation that doesn't fit stops
  admission (head-of-line FIFO — no starvation of long prompts behind
  short ones). ``mode="static"`` only admits into an EMPTY batch and
  then runs it to completion — classic static batching, kept only as a
  test's reference for the iteration count.
* **Eviction**: deadline sweeps over both waiting and running
  sequences, finish-on-max-tokens, and drain-time aborts — every exit
  path releases the sequence's pages back to the free-list (the chaos
  oracle asserts conservation after drain).
* **Bucketed prefill**: a prompt of length L caches positions
  ``0..L-2`` padded into the smallest prefill bucket (each bucket is
  one compiled program; buckets must be page-size multiples); the
  prompt's LAST token enters through the regular decode step — so every
  generated token, including the first, exits via the single decode
  program and the engine keeps exactly one host drain per iteration.
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from theanompi_tpu.serve.decode.kvcache import (
    KVExhausted,
    PagedKVCache,
    pages_needed,
)

_seq_ids = itertools.count()


class DecodeSequence:
    """One request's life: waiting -> running(slot) -> finished/evicted."""

    __slots__ = (
        "seq_id", "prompt", "max_new_tokens", "temperature", "deadline",
        "future", "t_submit", "slot", "generated", "t_first_token",
        "t_submit_ns", "t_admit",
    )

    def __init__(self, prompt, *, max_new_tokens: int,
                 temperature: float = 0.0,
                 deadline: Optional[float] = None, future=None,
                 t_submit: Optional[float] = None):
        self.seq_id = next(_seq_ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} must be >= 1")
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.deadline = deadline
        self.future = future
        self.t_submit = t_submit
        self.slot: Optional[int] = None
        self.generated: List[int] = []
        self.t_first_token: Optional[float] = None
        # the engine's request spans: the span clock (time.time_ns) read
        # beside ``t_submit``, and the ``now`` of the admission pass that
        # gave the request its slot (monotonic, as ``t_submit`` is)
        self.t_submit_ns: Optional[int] = None
        self.t_admit: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.size)

    @property
    def n_cache(self) -> int:
        """Positions the prefill program caches (all but the last prompt
        token, which rides the decode step)."""
        return self.prompt_len - 1

    @property
    def total_len(self) -> int:
        """Worst-case cache positions — the admission reservation."""
        return self.prompt_len + self.max_new_tokens

    @property
    def pos(self) -> int:
        """Position of the token the NEXT decode step processes."""
        return self.prompt_len - 1 + len(self.generated)

    @property
    def last_token(self) -> int:
        return int(self.generated[-1] if self.generated else self.prompt[-1])

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


class DecodeScheduler:
    """Admission/eviction policy over one :class:`PagedKVCache`."""

    def __init__(self, cache: PagedKVCache, *,
                 prefill_buckets: Tuple[int, ...],
                 mode: str = "continuous"):
        if mode not in ("continuous", "static"):
            raise ValueError(f"mode={mode!r} (continuous|static)")
        buckets = tuple(sorted(int(b) for b in prefill_buckets))
        if not buckets:
            raise ValueError("need at least one prefill bucket")
        for b in buckets:
            if b <= 0 or b % cache.page_size:
                raise ValueError(
                    f"prefill bucket {b} must be a positive multiple of "
                    f"page_size {cache.page_size}"
                )
        self.cache = cache
        self.buckets = buckets
        self.mode = mode
        self.waiting: Deque[DecodeSequence] = deque()
        self.running: Dict[int, DecodeSequence] = {}
        self._free_slots = list(range(cache.max_seqs - 1, -1, -1))
        # the decode step's operands, packed (``split_step`` names the parts)
        self._step = np.zeros(
            (cache.max_seqs * (cache.max_pages_per_seq + 4) + 1,), np.int32)
        self.admitted_total = 0
        self.finished_total = 0
        self.evicted_total = 0
        self.expired_total = 0

    # -- capacity limits the engine validates submissions against -------

    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: its first L-1 tokens must fit the
        largest prefill bucket (+1 for the token the decode step eats)."""
        return self.buckets[-1] + 1

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)

    @property
    def n_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def removed_total(self) -> int:
        """Sequences taken out of the running set so far, either way."""
        return self.finished_total + self.evicted_total

    @property
    def occupancy(self) -> float:
        return len(self.running) / max(1, self.cache.max_seqs)

    # -- admission / eviction -------------------------------------------

    def add(self, seq: DecodeSequence) -> None:
        self.waiting.append(seq)

    def admit(self, now: float):
        """Between-steps admission pass. Returns ``(admitted, expired)``
        — ``expired`` are waiting sequences whose deadline passed before
        they ever reached a slot (the caller owns their futures)."""
        admitted: List[DecodeSequence] = []
        expired: List[DecodeSequence] = []
        still: Deque[DecodeSequence] = deque()
        for seq in self.waiting:
            if seq.deadline is not None and now >= seq.deadline:
                expired.append(seq)
                self.expired_total += 1
            else:
                still.append(seq)
        self.waiting = still
        if self.mode == "static" and self.running:
            return admitted, expired
        while self.waiting and self._free_slots:
            seq = self.waiting[0]
            slot = self._free_slots[-1]
            try:
                self.cache.reserve(slot, seq.total_len)
            except KVExhausted:
                break  # FIFO under page pressure: wait, don't starve
            self.waiting.popleft()
            self._free_slots.pop()
            seq.slot = slot
            seq.t_admit = now
            self.running[slot] = seq
            self.admitted_total += 1
            admitted.append(seq)
        return admitted, expired

    def remove(self, slot: int, reason: str) -> DecodeSequence:
        """Take a running sequence out (``finished`` | ``evicted``),
        returning its pages to the free-list."""
        seq = self.running.pop(slot)
        self.cache.release(slot)
        self._free_slots.append(slot)
        seq.slot = None
        if reason == "finished":
            self.finished_total += 1
        else:
            self.evicted_total += 1
        return seq

    def running_deadline_victims(self, now: float) -> List[int]:
        """Slots whose sequence ran past its deadline (evict these)."""
        return [
            slot for slot, seq in self.running.items()
            if seq.deadline is not None and now >= seq.deadline
        ]

    # -- jitted-program operands ----------------------------------------

    def bucket_for(self, n_cache: int) -> int:
        for b in self.buckets:
            if b >= n_cache:
                return b
        raise ValueError(
            f"prompt caches {n_cache} positions but the largest prefill "
            f"bucket is {self.buckets[-1]}"
        )

    def prefill_args(self, seq: DecodeSequence):
        """``(bucket, tokens[bucket], pages[bucket/page_size])`` for an
        admitted sequence, or None when the prompt is a single token
        (nothing to cache — the decode step handles it)."""
        n_cache = seq.n_cache
        if n_cache == 0:
            return None
        bucket = self.bucket_for(n_cache)
        toks = np.zeros((bucket,), np.int32)
        toks[:n_cache] = seq.prompt[:-1]
        pages = np.full(
            (bucket // self.cache.page_size,), self.cache.scratch, np.int32
        )
        npg = pages_needed(n_cache, self.cache.page_size)
        pages[:npg] = self.cache.page_tables[seq.slot, :npg]
        return bucket, toks, pages

    def can_admit(self) -> bool:
        """Whether a request submitted NOW would get a slot at the next
        admission pass: nobody waits ahead of it, a slot and pages are
        free and, in ``mode="static"``, the batch has emptied."""
        if self.waiting or not self._free_slots or not self.cache.pages_free:
            return False
        return self.mode != "static" or not self.running

    def step_arrays(self, it: int) -> np.ndarray:
        """The decode program's fixed-shape operands as ONE int32 buffer,
        kept here and written in place (the loop is serial with the
        device: the program that read the last iteration's has ended):
        ``split_step`` names its parts."""
        tables, seq_lens, last, active, temp, counter = self.split_step(
            self._step)
        tables[:] = self.cache.page_tables
        self._step[tables.size:] = 0
        temp = temp.view(np.float32)
        for slot, seq in self.running.items():
            seq_lens[slot] = seq.pos
            last[slot] = seq.last_token
            active[slot] = 1
            temp[slot] = seq.temperature
        counter[...] = it
        return self._step

    def split_step(self, packed):
        """The packed buffer of one decode step cut into ``(page_tables
        [S, P], seq_lens [S], last_tokens [S], active [S], temperature
        [S], iteration [])``, all int32 as packed: ``active`` is 0 or 1
        and the temperature its float32's bits. Static slices, so the
        same cut serves the host's buffer (views, written through) and
        the traced operand of the decode program."""
        S, P = self.cache.max_seqs, self.cache.max_pages_per_seq
        rows = [packed[S * P + k * S:S * P + (k + 1) * S] for k in range(4)]
        return (packed[:S * P].reshape(S, P), *rows,
                packed[S * (P + 4):].reshape(()))

"""Paged KV-cache: a block pool of fixed-size pages with free-list reuse.

The pool owns the device arrays the decode/prefill programs donate and
rebind each step (``k_pool``/``v_pool``, shape ``(L, P, ps, H*D)``:
page-major, a token's heads side by side on the lanes — the layout the
paged-attention kernel's page block reads, so the programs write a row
(decode) or whole pages (prefill) into it and hand it to the kernel with
no slice, reshape or copy between; :func:`pool_shapes`), a
host-side free list of page ids, and a *reservation* ledger used for
admission control: the scheduler reserves a sequence's worst-case page
count (prompt + max_new_tokens) before prefill so a sequence admitted
into the batch can never stall mid-decode waiting for a page.

Two kinds of layer, two lifetimes (``window_layers`` > 0): a model's
full-attention layers keep every position for as long as the sequence
lives, its sliding-window layers only the last ``window``.  The pool of
the full layers then carries a second pool, ``pool.window_pool``, of the
sliding layers' pages (its own arrays ``(Lw, Pw, ps, KVH*D)``, free list
and reservations), and one sequence's pages of both kinds are a
:class:`RowPages`: admission reckons both kinds (:meth:`PagePool.
admit_row`), and before each step :meth:`RowPages.advance` returns to
the window pool the pages that slid out of the window, so a row never
holds more than ``window`` tokens plus one page there, whatever its
context.  Both page tables are indexed by logical page
(``position // ps``); a returned page's entry is the null page again.

A third kind of holding (``state_layers`` > 0): a model's state-space
layers keep, a sequence, a state that does not grow — ``pool.state_slots``,
a :class:`StateSlots` of the layers' convolution tails and SSM states
with one **slot** a running row.  A row's slot rides in a third row of
its page table (``table[2, 0]``), so the programs' arguments stay a
table a row; admission takes a slot beside the pages or refuses
(:attr:`PagePool.last_refusal` says which kind was short), release gives
it back, and its contents are never read again: the next prefill writes
the slot whole.  Slot 0 is the null slot of padding rows.

A third payload a page (``index_dim`` > 0): a model of learned sparse
attention keeps, beside K and V, its indexer's key of every token —
``pool.index_pool`` ``(L, P, index_dim, ps)``, a page the keys of its
``ps`` tokens transposed (:mod:`..ops.paged_sparse`).  It is page for
page with K and V: the one table a row addresses both, and a page
allocated, reserved or returned is a page of both.  Such a model's K and
V are **token-major**, one pool ``pool.kv_pool`` ``(L, P*ps, 2*KVH, D)``
in which a token is its K heads and then its V heads — at Keye's widths
one ``(8, 128)`` bfloat16 tile, so that its attention kernel copies each
token it selected straight from the pool (a copy of one row of a tiled
page is refused).  Every other kernel walks whole pages and keeps the
page-major pools; position ``t`` still lives in page ``table[t // ps]``,
at token ``page * ps + t % ps`` of the pool.  ``pool.k_pool[layer,
page]`` / ``pool.v_pool[...]`` read such a pool's pages as a page-major
pool would hold them, gathering only the pages asked for.

A latent cache (``latent``: multi-head latent attention): a token's page
row is no heads' K and V but its latent, in ``k_pool`` ``(L, P, ps, R)``,
and its key's rotary-free part, in ``v_pool`` ``(L, P, ps, D_r)``
(:func:`pool_shapes`); every query head reads the same row
(:mod:`..ops.paged_mla`).  Pages, tables and admission are as above.

Page 0 is reserved as the **null page**: padding rows of a batch
bucket and the unused tail of every page table point at it, so the
programs' scatter/gather of padding lanes touch real (never-read)
storage instead of needing per-lane predication.

Observability rides the PR 14 rails: when telemetry is on, occupancy
gauges (``pt_serve_kv_pages{state=used|free|reserved}``) are updated on
every alloc/free, and the pool registers a live-buffer attribution
provider so the memory census names the pools ``kv::k_pages`` /
``kv::v_pages``.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_sparse import _listed_tokens

__all__ = ["PagePool", "RowPages", "StateSlots", "KVPoolExhausted",
           "NULL_PAGE", "kv_page_budget", "pool_shapes"]

NULL_PAGE = 0


def pool_shapes(layers: int, pages: int, page_size: int, heads: int,
                head_dim: int, latent: Tuple[int, int] = (),
                token_major: bool = False):
    """``(value_shape, scale_shape)`` of the K/V pools: values
    ``(L, P, ps, H*D)``, the int8 pools' per-(token, head) f32 scales
    ``(L, P, ps, H)``.  Position ``t`` of a sequence lives at
    ``[layer, page_table[t // ps], t % ps]`` in both — the one
    addressing every precision, program and kernel uses.  A latent
    cache (multi-head latent attention: ``latent = (R, D_r)``, the
    latent's width and its rotary-free key's) has no scales and no heads:
    ``(latent_shape (L, P, ps, R), key_shape (L, P, ps, D_r))``, the
    shapes of ``k_pool`` and ``v_pool``.  ``token_major`` (learned sparse
    attention, whose kernel copies a token at a time: module docstring):
    ``((L, P*ps, 2*H, D), None)``, one pool of K and V, position ``t`` at
    token ``page_table[t // ps] * ps + t % ps``."""
    if token_major:
        return (layers, pages * page_size, 2 * heads, head_dim), None
    if latent:
        return tuple((layers, pages, page_size, w) for w in latent)
    return ((layers, pages, page_size, heads * head_dim),
            (layers, pages, page_size, heads))


def kv_page_budget(pages: int, precision: str, head_dim: int,
                   kv_heads: int = 1) -> int:
    """Scale an fp32-denominated page budget to a precision's real cost.

    ``PT_SERVE_KV_PAGES`` is a BYTE budget expressed in fp32 pages (so
    deployments compare precisions at identical HBM spend).  A token's
    row in a page holds ``kv_heads`` heads (the heads the pool stores:
    fewer than the query heads under grouped-query attention); per
    (token, head) an fp32 page row costs ``4*D`` bytes; bf16 halves it;
    int8 costs ``D`` for the values plus 4 for the f32 scale riding in
    the scale pages.  The null page scales with everything else, so the
    *usable* count is what gets the ratio — int8 at D=16 yields 3.2x
    the admission headroom at the same byte spend.
    """
    if precision in ("fp32", "float32"):
        return pages
    fp32_cost = 4.0 * head_dim * kv_heads
    if precision in ("bf16", "bfloat16"):
        cost = 2.0 * head_dim * kv_heads
    elif precision == "int8":
        cost = (head_dim + 4.0) * kv_heads
    else:
        raise ValueError(f"unknown serve precision {precision!r}")
    return 1 + int((pages - 1) * fp32_cost / cost)


class KVPoolExhausted(RuntimeError):
    """Raised when an alloc/reserve exceeds pool headroom."""


class StateSlots:
    """The recurrent state of a model's state-space layers, a slot a
    running row: ``conv`` ``(Ls, slots, d_conv - 1, C)`` (each layer's
    last inputs, in the cache's dtype; ``C`` is ``conv_channels``, or
    ``inner`` where that is 0: the channels of every projection the
    layer convolves, side by side) and ``ssm`` float32, ``(Ls, slots, R,
    N)`` (Mamba-1: channels on the lanes) or, with ``heads`` (Mamba-2),
    ``(Ls, slots, heads, N / heads, R)`` (:mod:`.ssm`), or ``(Ls, slots,
    *per_slot)`` where ``per_slot`` gives a slot's state shape itself (a
    KDA layer's ``(heads, d_k, d_v)``: :mod:`.kda`).  Slot 0 is the null
    slot; a LIFO free list of the others, lock-guarded like the pages.
    ``slot_bytes``: what one slot holds over all the layers."""

    def __init__(self, *, layers: int, slots: int, inner: int, state: int,
                 conv: int, conv_channels: int = 0, heads: int = 0,
                 per_slot: Tuple[int, ...] = (), dtype=jnp.float32):
        if slots < 2:
            raise ValueError("slots must be >= 2 (slot 0 is the null slot)")
        self.layers, self.slots = layers, slots
        self.conv = jnp.zeros(
            (layers, slots, conv - 1, conv_channels or inner), dtype)
        if not per_slot:
            per_slot = ((heads, inner // heads, state) if heads
                        else (state, inner))
        self.ssm = jnp.zeros((layers, slots, *per_slot), jnp.float32)
        self.slot_bytes = layers * (
            int(np.prod(self.conv.shape[2:])) * self.conv.dtype.itemsize
            + int(np.prod(per_slot)) * 4)
        self._lock = threading.Lock()
        self._free: List[int] = list(range(slots - 1, 0, -1))
        self.stats = {"takes": 0, "releases": 0, "refusals": 0,
                      "high_watermark": 0}

    @property
    def held(self) -> int:
        with self._lock:
            return self.slots - 1 - len(self._free)

    def take(self) -> Optional[int]:
        """A free slot, or None (counted as a refusal)."""
        with self._lock:
            if not self._free:
                self.stats["refusals"] += 1
                return None
            slot = self._free.pop()
            self.stats["takes"] += 1
            self.stats["high_watermark"] = max(
                self.stats["high_watermark"],
                self.slots - 1 - len(self._free))
        self._gauges()
        return slot

    def release(self, slot: int) -> None:
        with self._lock:
            if not 0 < slot < self.slots:
                raise ValueError(f"slot {slot} out of range")
            if slot in self._free:
                raise ValueError(f"double release of slot {slot}")
            self._free.append(slot)
            self.stats["releases"] += 1
        self._gauges()

    def check_consistency(self, expect_all_free: bool = False) -> None:
        with self._lock:
            assert len(set(self._free)) == len(self._free), "dup free slots"
            assert all(0 < s < self.slots for s in self._free)
            if expect_all_free:
                assert len(self._free) == self.slots - 1, \
                    (f"slot leak: {self.slots - 1 - len(self._free)} of "
                     f"{self.slots - 1} state slots unaccounted for")

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            free = len(self._free)
        held = self.slots - 1 - free
        return {"slots": self.slots, "held": held, "free": free,
                "slot_bytes": self.slot_bytes,
                "held_bytes": held * self.slot_bytes, **self.stats}

    def _gauges(self) -> None:
        try:
            from ..observability.metrics import get_registry
            from ..observability.telemetry import get_telemetry
            if not get_telemetry().enabled:
                return
            snap = self.snapshot()
            g = get_registry().gauge(
                "pt_serve_state_slots",
                "Serve state slots (state-space layers) by state",
                labelnames=("state",))
            g.set(snap["held"], state="used")
            g.set(snap["free"], state="free")
            g.set(snap["high_watermark"], state="high_watermark")
            b = get_registry().gauge(
                "pt_serve_state_bytes",
                "Bytes of the state slots (state-space layers), held by "
                "running rows or free", labelnames=("state",))
            b.set(snap["held_bytes"], state="held")
            b.set(snap["free"] * self.slot_bytes, state="free")
        except Exception:
            pass


@functools.partial(jax.jit, static_argnames=("page_size", "half"))
def _read_pages(kv_pool, layer, page, *, page_size, half):
    """K (``half`` 0) or V (1) of pages ``page`` of layers ``layer`` (index
    arrays that broadcast) of a token-major pool, ``(..., ps, KVH*D)``: one
    program, the layer inside the token's index, so that nothing of the
    pool is copied (an eager slice or reshape of it would be)."""
    heads = kv_pool.shape[2] // 2
    got = _listed_tokens(kv_pool, layer[..., None],
                         page[..., None] * page_size + jnp.arange(page_size))
    got = got[..., half * heads:(half + 1) * heads, :]
    return got.reshape(*got.shape[:-2], heads * kv_pool.shape[3])


class _PageMajorView:
    """K (``half`` 0) or V (1) of a token-major pool as a page-major pool
    reads: ``view[layer, page]`` is ``(..., ps, KVH*D)``, gathered from the
    pages asked for alone."""

    def __init__(self, pool: "PagePool", half: int):
        self._pool, self._half = pool, half

    def __getitem__(self, at):
        layer, page = (jnp.asarray(a, jnp.int32) for a in at)
        return _read_pages(self._pool.kv_pool, layer, page,
                           page_size=self._pool.page_size, half=self._half)


class PagePool:
    """Block-pool allocator over the serve KV arrays (``k_pool`` /
    ``v_pool`` ``(L, P, ps, H*D)``, and ``k_scale`` / ``v_scale``
    ``(L, P, ps, H)`` beside an int8 pool; beside an index pool, the one
    token-major ``kv_pool`` ``(L, P*ps, 2*H, D)``, with ``k_pool`` /
    ``v_pool`` its page-major views: module docstring).

    Thread-safety: all bookkeeping is lock-guarded; the device arrays
    themselves are only rebound from the engine's step loop.
    """

    def __init__(self, *, layers: int, pages: int, page_size: int,
                 heads: int, head_dim: int, dtype=jnp.float32,
                 scale_pages: bool = False, window_layers: int = 0,
                 window_pages: int = 0, window: int = 0,
                 kind: str = "global", state_layers: int = 0,
                 state_slots: int = 0, state_shape=(0, 0, 0),
                 state_conv_channels: int = 0, state_heads: int = 0,
                 state_per_slot: Tuple[int, ...] = (), index_dim: int = 0,
                 latent: Tuple[int, int] = ()):
        if pages < 2:
            raise ValueError("pages must be >= 2 (page 0 is the null page)")
        self.kind = kind            # "global" | "window": names the gauges
        self.window = int(window)
        # the state-space layers' slots: ``state_shape`` is (inner
        # channels, state size, convolution width), beside the
        # convolution's channels and the heads of a Mamba-2 state
        self.state_slots: Optional[StateSlots] = None
        if state_layers:
            if scale_pages:
                raise ValueError("an int8 pool has no state-space layers")
            inner, state, conv = state_shape
            self.state_slots = StateSlots(
                layers=state_layers, slots=state_slots, inner=inner,
                state=state, conv=conv, conv_channels=state_conv_channels,
                heads=state_heads, per_slot=state_per_slot, dtype=dtype)
        # which kind of holding the last refused admission lacked:
        # "kv" (pages of either pool) or "state" (a slot)
        self.last_refusal: Optional[str] = None
        # the sliding layers' pool: same page size and row, its own
        # arrays, free list and reservations
        self.window_pool: Optional[PagePool] = None
        if window_layers:
            if scale_pages:
                raise ValueError("an int8 pool has no sliding layers")
            if window < 1:
                raise ValueError("window_layers need window >= 1")
            self.window_pool = PagePool(
                layers=window_layers, pages=window_pages,
                page_size=page_size, heads=heads, head_dim=head_dim,
                dtype=dtype, window=window, kind="window")
        self.layers = layers
        self.pages = pages
        self.page_size = page_size
        self.heads = heads
        self.head_dim = head_dim
        self.dtype = dtype
        # quantized pools carry per-(token, head) f32 scales in shadow
        # "scale pages" addressed by the same page table (the scale
        # travels with the tensor — the TPU022 contract)
        self.scale_pages = bool(scale_pages)
        # a latent cache (multi-head latent attention): k_pool holds a
        # token's latent, v_pool its rotary-free key part
        self.latent = tuple(latent)
        if self.latent and (scale_pages or window_layers or index_dim):
            raise ValueError("a latent cache has no int8, sliding or "
                             "index pool")
        if index_dim and scale_pages:
            raise ValueError("an int8 pool has no index pool")
        # learned sparse attention (an index pool) copies a token at a
        # time: its K and V are one token-major pool
        self.token_major = bool(index_dim)
        shape, sshape = pool_shapes(layers, pages, page_size, heads,
                                    head_dim, self.latent, self.token_major)
        if self.token_major:
            self.kv_pool = jnp.zeros(shape, dtype)
            self.k_pool, self.v_pool = (_PageMajorView(self, h)
                                        for h in (0, 1))
        else:
            self.k_pool = jnp.zeros(shape, dtype)
            self.v_pool = jnp.zeros(sshape if self.latent else shape, dtype)
        self.k_scale = jnp.zeros(sshape, jnp.float32) \
            if self.scale_pages else None
        self.v_scale = jnp.zeros(sshape, jnp.float32) \
            if self.scale_pages else None
        # the indexer's keys (learned sparse attention), page for page
        # with K and V: a page is (index_dim, page_size)
        self.index_pool = jnp.zeros(
            (layers, pages, index_dim, page_size), dtype) \
            if index_dim else None
        self._lock = threading.Lock()
        # LIFO free list: hot pages get reused while still cache/HBM warm
        self._free: List[int] = list(range(pages - 1, 0, -1))
        self._reserved = 0
        self.stats = {
            "allocs": 0, "frees": 0, "alloc_failures": 0,
            "reserve_refusals": 0, "high_watermark": 0,
        }
        if kind == "window":
            # pages that slid out of a running row's window and went
            # back to the free list, and the most one row ever held
            self.stats.update(pages_returned=0, row_pages_max=0)
        self._register_memory_provider()

    # -- capacity ----------------------------------------------------------

    @property
    def usable_pages(self) -> int:
        return self.pages - 1  # minus the null page

    def pages_needed(self, tokens: int) -> int:
        return max(1, -(-int(tokens) // self.page_size))

    def window_pages_needed(self, tokens: int) -> int:
        """Most pages of a sliding layer a row of up to ``tokens``
        positions ever holds at once: its window's span plus the page
        the window starts inside."""
        return min(self.pages_needed(tokens),
                   -(-self.window // self.page_size) + 1)

    def first_window_page(self, length: int) -> int:
        """Logical page of the oldest position a row of ``length``
        positions still reads in a sliding layer."""
        return max(0, int(length) - self.window) // self.page_size

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return self.usable_pages - len(self._free)

    @property
    def reserved_pages(self) -> int:
        with self._lock:
            return self._reserved

    def headroom(self) -> int:
        """Pages available to NEW admissions (free minus already promised)."""
        with self._lock:
            return len(self._free) - self._reserved

    # -- admission-control reservations ------------------------------------

    def can_admit(self, n_pages: int, n_window: int = 0) -> bool:
        """Headroom for ``n_pages`` here and ``n_window`` pages of the
        sliding layers' pool."""
        if n_window and not self.window_pool.can_admit(n_window):
            return False
        return self.headroom() >= n_pages

    def reserve(self, n_pages: int, n_window: int = 0) -> None:
        """Promise ``n_pages`` (and ``n_window`` of the sliding layers'
        pool) to a sequence about to be admitted: both or neither."""
        if n_window:
            self.window_pool.reserve(n_window)
            try:
                self.reserve(n_pages)
            except KVPoolExhausted:
                self.window_pool.release_reservation(n_window)
                raise
            return
        with self._lock:
            if len(self._free) - self._reserved < n_pages:
                self.stats["reserve_refusals"] += 1
                raise KVPoolExhausted(
                    f"reserve({n_pages}): only "
                    f"{len(self._free) - self._reserved} unreserved pages")
            self._reserved += n_pages
        self._gauges()

    def release_reservation(self, n_pages: int) -> None:
        """Return unused promised pages (sequence finished early)."""
        with self._lock:
            self._reserved = max(0, self._reserved - n_pages)
        self._gauges()

    # -- alloc / free -------------------------------------------------------

    def alloc(self, n_pages: int = 1, *, reserved: bool = False) -> List[int]:
        """Pop ``n_pages`` page ids off the free list.

        ``reserved=True`` draws down a prior :meth:`reserve` promise
        (the scheduler's path); an unreserved alloc can fail even when
        pages are free if they are all promised elsewhere.
        """
        with self._lock:
            avail = len(self._free) if reserved \
                else len(self._free) - self._reserved
            if avail < n_pages:
                self.stats["alloc_failures"] += 1
                raise KVPoolExhausted(
                    f"alloc({n_pages}): {avail} pages available")
            ids = [self._free.pop() for _ in range(n_pages)]
            if reserved:
                self._reserved = max(0, self._reserved - n_pages)
            self.stats["allocs"] += n_pages
            used = self.usable_pages - len(self._free)
            self.stats["high_watermark"] = max(
                self.stats["high_watermark"], used)
        self._gauges()
        return ids

    def free(self, page_ids: Sequence[int]) -> None:
        """Return a retired sequence's pages to the free list."""
        with self._lock:
            for pid in page_ids:
                if pid == NULL_PAGE:
                    raise ValueError("cannot free the null page")
                if not (0 < pid < self.pages):
                    raise ValueError(f"page id {pid} out of range")
                if pid in self._free:
                    raise ValueError(f"double free of page {pid}")
                self._free.append(pid)
            self.stats["frees"] += len(page_ids)
        self._gauges()

    def recycle(self, page_ids: Sequence[int]) -> None:
        """Return pages a running row no longer reads and promise as
        many back to it: what slid out of its window is what its next
        pages draw on, so the row's hold on the pool does not grow."""
        self.free(page_ids)
        with self._lock:
            self._reserved += len(page_ids)
            self.stats["pages_returned"] = \
                self.stats.get("pages_returned", 0) + len(page_ids)
        self._gauges()

    def admit_row(self, prompt_len: int, max_new_tokens: int,
                  max_pages: int) -> Optional["RowPages"]:
        """Reserve a sequence's worst case in both kinds of layer, take
        its state slot where the model has state-space layers, and
        allocate its prompt's pages; None (nothing taken, and
        ``last_refusal`` says which kind was short) when any lacks the
        headroom."""
        total = int(prompt_len) + int(max_new_tokens)
        worst = self.pages_needed(total)
        worst_w = (self.window_pool.window_pages_needed(total)
                   if self.window_pool else 0)
        self.last_refusal = "kv"
        if not self.can_admit(worst, worst_w):
            return None
        try:
            self.reserve(worst, worst_w)
        except KVPoolExhausted:
            return None
        slot = None
        if self.state_slots is not None:
            slot = self.state_slots.take()
            if slot is None:
                self.last_refusal = "state"
                self.release_reservation(worst)
                if worst_w:
                    self.window_pool.release_reservation(worst_w)
                return None
        self.last_refusal = None
        return RowPages(self, prompt_len, worst, worst_w, max_pages, slot)

    def check_consistency(self, expect_all_free: bool = False) -> None:
        """Invariant check used by tests and the serve chaos drills:
        no duplicate/lost pages.  ``expect_all_free=True`` additionally
        proves a clean slate — every usable page back on the free list
        and zero outstanding reservations (the post-drain / post-storm
        zero-leak assertion)."""
        with self._lock:
            assert len(set(self._free)) == len(self._free), "dup free ids"
            assert all(0 < p < self.pages for p in self._free)
            assert 0 <= self._reserved <= len(self._free), \
                f"reserved {self._reserved} > free {len(self._free)}"
            if expect_all_free:
                assert len(self._free) == self.usable_pages, \
                    (f"page leak: {self.usable_pages - len(self._free)} "
                     f"of {self.usable_pages} pages unaccounted for")
                assert self._reserved == 0, \
                    f"{self._reserved} pages still reserved"
        if self.window_pool is not None:
            self.window_pool.check_consistency(expect_all_free)
            assert self.window_pool.stats["row_pages_max"] <= \
                self.window_pool.window_pages_needed(1 << 62), \
                "a row held more than its window plus a page"
        if self.state_slots is not None:
            self.state_slots.check_consistency(expect_all_free)

    # -- device state -------------------------------------------------------

    def swap(self, *pools) -> None:
        """Rebind the pools to a program's donated outputs, in program
        order: the value pools (the token-major pool), then the scale
        pools of a quantized pool, or the index pool, or the sliding
        layers' two pools, then the state-space layers' two."""
        if self.token_major:
            self.kv_pool, *rest = pools
        else:
            self.k_pool, self.v_pool, *rest = pools
        if self.index_pool is not None:
            if not rest:
                raise ValueError("swap requires the index pool")
            self.index_pool, *rest = rest
        if self.state_slots is not None:
            if len(rest) < 2:
                raise ValueError("swap requires the state pools")
            *rest, self.state_slots.conv, self.state_slots.ssm = rest
        if self.scale_pages:
            if len(rest) != 2 or rest[0] is None or rest[1] is None:
                raise ValueError(
                    "quantized pool swap requires k_scale and v_scale")
            self.k_scale, self.v_scale = rest
        elif self.window_pool is not None:
            if len(rest) != 2:
                raise ValueError(
                    "swap requires the sliding layers' k and v pools")
            self.window_pool.swap(*rest)

    def state(self):
        """The donated arrays in program argument order (see
        :meth:`swap`)."""
        state = ((self.kv_pool,) if self.token_major
                 else (self.k_pool, self.v_pool))
        if self.scale_pages:
            state += (self.k_scale, self.v_scale)
        if self.index_pool is not None:
            state += (self.index_pool,)
        if self.window_pool is not None:
            state += (self.window_pool.k_pool, self.window_pool.v_pool)
        if self.state_slots is not None:
            state += (self.state_slots.conv, self.state_slots.ssm)
        return state

    def utilization(self) -> float:
        with self._lock:
            return (self.usable_pages - len(self._free)) / \
                max(1, self.usable_pages)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            free = len(self._free)
            return {
                "pages": self.pages,
                "dtype": np.dtype(self.dtype).name,
                "layout": "token_major" if self.token_major
                else "page_major",
                "scale_pages": self.scale_pages,
                # pages of the index pool: the same pages, a third array
                "index_pages": (self.pages if self.index_pool is not None
                                else 0),
                # pages of a latent cache (latent and rotary-free key)
                "latent_pages": self.pages if self.latent else 0,
                "usable_pages": self.usable_pages,
                "free_pages": free,
                "used_pages": self.usable_pages - free,
                "reserved_pages": self._reserved,
                "utilization": (self.usable_pages - free) /
                max(1, self.usable_pages),
                **self.stats,
                **({"window": self.window_pool.snapshot()}
                   if self.window_pool is not None else {}),
                **({"state": self.state_slots.snapshot()}
                   if self.state_slots is not None else {}),
            }

    # -- observability ------------------------------------------------------

    def _gauges(self) -> None:
        """Occupancy gauges; inert while telemetry is off (registry must
        stay empty then — the record_dispatch contract)."""
        try:
            from ..observability.metrics import get_registry
            from ..observability.telemetry import get_telemetry
            if not get_telemetry().enabled:
                return
            with self._lock:
                free = len(self._free)
                reserved = self._reserved
            window = self.kind == "window"
            g = get_registry().gauge(
                "pt_serve_kv_window_pages" if window
                else "pt_serve_kv_pages",
                "Serve KV page-pool occupancy by state"
                + (", sliding-window layers" if window else ""),
                labelnames=("state",))
            g.set(self.usable_pages - free, state="used")
            g.set(free, state="free")
            g.set(reserved, state="reserved")
            if window:
                get_registry().gauge(
                    "pt_serve_kv_window_pages_returned",
                    "Pages that slid out of a running row's window and "
                    "went back to the pool").set(
                    self.stats["pages_returned"])
                return
            get_registry().gauge(
                "pt_serve_kv_utilization",
                "Fraction of usable KV pages in use").set(
                (self.usable_pages - free) / max(1, self.usable_pages))
        except Exception:
            pass

    def _register_memory_provider(self) -> None:
        try:
            from ..observability import memory as _memory
            mon = _memory.get_memory_monitor()
            if mon.enabled:
                mon.register_provider(self._memory_named)
        except Exception:
            pass

    def _memory_named(self):
        """Live-buffer attribution for the PR 14 census: the pools
        (and, for quantized pools, their scale shadows) under ``kv::``
        paths."""
        if self.kind == "window":
            return {"kv::k_window_pages": self.k_pool,
                    "kv::v_window_pages": self.v_pool}
        named = ({"kv::kv_tokens": self.kv_pool} if self.token_major else
                 {"kv::k_pages": self.k_pool, "kv::v_pages": self.v_pool})
        if self.scale_pages:
            named["kv::k_scales"] = self.k_scale
            named["kv::v_scales"] = self.v_scale
        if self.index_pool is not None:
            named["kv::index_pages"] = self.index_pool
        if self.state_slots is not None:
            named["kv::ssm_conv_state"] = self.state_slots.conv
            named["kv::ssm_state"] = self.state_slots.ssm
        return named

    def table_shape(self, max_pages: int):
        """Shape of one row's page table as the programs take it: a row
        of the table a kind of holding (class docstring of
        :class:`RowPages`)."""
        if self.state_slots is not None:
            return (3, max_pages)
        return (2, max_pages) if self.window_pool else (max_pages,)

    def null_padded_table(self, page_ids: Sequence[int],
                          max_pages: int) -> np.ndarray:
        """Host-side page table row: ids then null-page padding."""
        if len(page_ids) > max_pages:
            raise ValueError(
                f"{len(page_ids)} pages exceed table width {max_pages}")
        row = np.full((max_pages,), NULL_PAGE, np.int32)
        row[:len(page_ids)] = np.asarray(page_ids, np.int32)
        return row


class RowPages:
    """One sequence's pages in both kinds of layer and its state slot,
    and the page table the programs take: ``(max_pages,)`` of the full
    layers' pages, or ``(2, max_pages)`` with the sliding layers' below
    them, or ``(3, max_pages)`` with the slot first in a third row.
    Built by
    :meth:`PagePool.admit_row` with the worst case reserved; the owner
    calls :meth:`advance` before the step that writes position ``pos``
    and :meth:`release` when the sequence leaves, however it leaves."""

    def __init__(self, pool: PagePool, prompt_len: int, reserved: int,
                 reserved_window: int, max_pages: int,
                 slot: Optional[int] = None):
        self.pool = pool
        self.slot = slot
        wpool = pool.window_pool
        self.page_ids: List[int] = pool.alloc(
            pool.pages_needed(prompt_len), reserved=True)
        self.reserved_left = reserved - len(self.page_ids)
        self.window_ids: Dict[int, int] = {}    # logical page -> page id
        self._window_span = None    # (first, last) logical pages held
        self.window_reserved_left = reserved_window
        self.table = np.full(pool.table_shape(max_pages), NULL_PAGE,
                             np.int32)
        row = self.table[0] if self.table.ndim == 2 else self.table
        if slot is not None:
            self.table[2, 0] = slot
        if len(self.page_ids) > max_pages:
            self.release()
            raise ValueError(f"{len(self.page_ids)} pages exceed table "
                             f"width {max_pages}")
        row[:len(self.page_ids)] = self.page_ids
        if wpool:
            # what the first decode step (length prompt_len + 1) reads
            self._hold_window(wpool.first_window_page(prompt_len + 1),
                              (prompt_len - 1) // pool.page_size)

    def _hold_window(self, first: int, last: int) -> int:
        """Hold exactly the sliding layers' logical pages ``first ..
        last``: return the older ones, allocate the missing.  Returns
        how many went back."""
        wpool = self.pool.window_pool
        gone = [p for p in self.window_ids if p < first]
        if gone:
            wpool.recycle([self.window_ids.pop(p) for p in gone])
            self.window_reserved_left += len(gone)
            self.table[1, gone] = NULL_PAGE
        for p in range(first, last + 1):
            if p not in self.window_ids:
                pid, = wpool.alloc(1, reserved=True)
                self.window_reserved_left -= 1
                self.window_ids[p] = self.table[1, p] = pid
        wpool.stats["row_pages_max"] = max(wpool.stats["row_pages_max"],
                                           len(self.window_ids))
        return len(gone)

    def advance(self, pos: int) -> int:
        """Make room for position ``pos``: grow the full layers' pages
        to cover it and slide the sliding layers' window to end at it —
        all drawn from the admission-time reservation, so it cannot
        fail.  Returns the window pages that went back to the pool."""
        pool = self.pool
        page = pos // pool.page_size
        if page >= len(self.page_ids):
            new = pool.alloc(page + 1 - len(self.page_ids), reserved=True)
            row = self.table[0] if self.table.ndim == 2 else self.table
            row[len(self.page_ids):len(self.page_ids) + len(new)] = new
            self.page_ids += new
            self.reserved_left -= len(new)
        if pool.window_pool is None:
            return 0
        span = (pool.window_pool.first_window_page(pos + 1), page)
        if span == self._window_span:   # most steps: the same pages
            return 0
        self._window_span = span
        return self._hold_window(*span)

    def release(self) -> None:
        """Everything back: pages to the free lists, what is left of the
        reservations released, the state slot.  Safe to call twice."""
        pool = self.pool
        if self.slot is not None:
            pool.state_slots.release(self.slot)
            self.slot = None
        pool.free(self.page_ids)
        pool.release_reservation(self.reserved_left)
        self.page_ids, self.reserved_left = [], 0
        if pool.window_pool is not None:
            pool.window_pool.free(list(self.window_ids.values()))
            pool.window_pool.release_reservation(self.window_reserved_left)
            self.window_ids, self.window_reserved_left = {}, 0

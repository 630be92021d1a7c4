"""Paged KV pool and per-slot state — the serving engine's only mutable
device state, built from the model's :class:`CacheSpec` (which of its
layers hold pages, which hold blocks of per-slot state).

:class:`PagedKVCache` is a dense pool of fixed-size pages ``[layers,
num_pages, heads, head_dim, page_len]`` (``layers`` the model's layers
that hold pages; each page held transposed,
``page_len`` in the lanes: the form the chip stores unpadded and the
kernels read as it lies — see :class:`PagedKVCache`) plus a host-side
:class:`PagePool` allocator. Storage dtype comes from the amp cast
policies (bf16 by default — the same ``half_dtype`` the O2/O3 tables
resolve). A request owns a *page list*: its logical positions
``[0, L)`` live on pages ``table[0] .. table[ceil(L/page_len)-1]`` at
in-page offsets ``pos % page_len``. The engine materialises the
per-slot lists as a ``[slots, max_pages]`` int32 page-table operand
each call; the attention kernels gather K/V through it.

Slot semantics (the continuous-batching contract):

- **chunked prefill** ingests a prompt one chunk per decode heartbeat:
  the chunk's K/V lands on the slot's pages at ``[offset, offset + C)``
  as whole pages; positions past the true prompt length hold pad
  garbage that is *never attended* (length masking) and is overwritten
  position-by-position as decode advances.
- **decode** writes each slot's new token at its length and then
  attends ``[0, length]`` — write-then-attend, so garbage can never
  enter a softmax.

Everything is functional: updates return a new :class:`PagedKVCache`
whose buffers alias the old ones under jit donation (the engine donates
the cache to its compiled programs). What the indirection buys:

- **no per-slot max_len reservation** — a 40-token request holds
  ``ceil(40/page_len)`` pages, not ``max_len`` positions, so the same
  pool bytes serve far more logical requests;
- **copy-on-write prefix sharing** — a prefix-cache hit bumps the
  refcount of the donor's pages and writes their ids into the new
  slot's table: zero data movement. Shares are always
  whole-page (matches are chunk-aligned and ``chunk_len % page_len ==
  0``), so a shared page is never written: the first write past the
  shared prefix lands on a freshly allocated page by construction;
- **immediate reclamation** — a finished request's pages return to the
  free list the moment its slot is released (refcount permitting).

Page 0 is the **sentinel/garbage page**: never allocated, it absorbs
the fixed-shape decode program's writes for inactive slots (their page
tables point at it) so a dead slot's discarded write can never land on
a live request's page. Allocation is all-or-nothing with a reservation
ledger (:meth:`PagePool.reserve`): the scheduler reserves a request's
worst-case page demand at admission, so a request that was admitted can
always grow to its budget — pool pressure is absorbed at the admission
boundary (requests queue; prefix entries are evicted LRU-first), never
mid-decode.
"""

from __future__ import annotations

import dataclasses
from typing import (Any, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache", "PagePool", "SlotState", "SlotAddr",
           "CacheSpec", "StateBlock"]


@dataclasses.dataclass(frozen=True)
class StateBlock:
    """One named block of per-slot state: ``layers`` of the model's
    layers keep ``shape`` values a slot in ``dtype`` (None: the engine's
    half dtype), stored ``[layers, slots, *shape]``."""

    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Any = None


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a model asks the serving engine to hold for it, PER KIND OF
    LAYER: ``page_layers`` of its layers keep paged K/V (``kv_heads``
    heads of ``head_dim``; the model maps a layer to its index among
    them), and each :class:`StateBlock` is a fixed block per slot that
    some of its layers keep beside or instead of pages (a convolution's
    last inputs, a recurrent matrix). ``counter_layers`` x
    ``num_experts`` is the shape of the device-side counter of tokens
    routed (0 experts: no expert layer).

    ``value_dim`` > 0 states the LATENT page kind (multi-head latent
    attention): a token keeps ONE row of ``head_dim`` values a page layer
    (``kv_heads`` 1) that is key and value both, for all the query heads -
    the value is the row's first ``value_dim`` columns (the latent), the
    columns after it are key only (the rotary key). There is then one
    pool and no V pool (:class:`PagedKVCache` holds a V of zero heads),
    and the model's kernels read a page once for both products.

    A model states it as ``model.cache_spec()`` -> a plain dict with
    these keys (``state`` a list of ``(name, layers, shape, dtype)``);
    the engine builds the pool and the state from it alone, so a new
    kind of layer is a new spec, not a branch in the engine."""

    page_layers: int
    kv_heads: int
    head_dim: int
    state: Tuple[StateBlock, ...] = ()
    counter_layers: int = 0
    num_experts: int = 0
    value_dim: int = 0

    def __post_init__(self):
        if self.value_dim and not (self.kv_heads == 1
                                   and 0 < self.value_dim <= self.head_dim):
            raise ValueError(
                "cache_spec: a latent page (value_dim > 0) is one row a "
                f"token, its value a prefix of it; got kv_heads="
                f"{self.kv_heads}, head_dim={self.head_dim}, value_dim="
                f"{self.value_dim}")

    @classmethod
    def of(cls, model) -> "CacheSpec":
        if not hasattr(model, "cache_spec"):
            # a dense stand-in that states geometry attributes only: pages
            # of all its heads on every layer, no per-slot state
            heads = int(model.num_heads)
            return cls(page_layers=int(model.num_layers), kv_heads=heads,
                       head_dim=int(model.hidden) // heads)
        d = dict(model.cache_spec())
        blocks = tuple(StateBlock(str(n), int(l), tuple(int(x) for x in sh),
                                  dt)
                       for n, l, sh, dt in d.pop("state", ()))
        if len({b.name for b in blocks}) != len(blocks):
            raise ValueError("cache_spec: state block names must differ")
        return cls(state=blocks, **{k: int(v) for k, v in d.items()})


@flax.struct.dataclass
class SlotAddr:
    """Which slots a program's batch rows are, for the models that read
    and write :class:`SlotState` blocks. The chunk program runs ONE row:
    ``slot`` is its slot and ``fresh`` says the chunk is at offset 0 (the
    request is admitted here and starts from zeros, whatever the slot's
    last tenant left). The decode program runs every slot, row ``b``
    being slot ``b`` (``slot`` None), and ``active`` marks the rows that
    decode: an idle slot's state is dead until admission zeroes it, a
    slot mid-prefill rides the batch with its state live, so neither may
    move."""

    slot: Optional[jnp.ndarray] = None      # int32 scalar (chunk)
    fresh: Optional[jnp.ndarray] = None     # bool scalar (chunk)
    active: Optional[jnp.ndarray] = None    # [slots] bool (decode)

    def read(self, block):
        """The rows of ``block [layers, slots, ...]`` this program's
        batch reads: ``[layers, B, ...]``."""
        if self.slot is None:
            return block
        rows = jax.lax.dynamic_slice_in_dim(block, self.slot, 1, axis=1)
        return jnp.where(self.fresh, jnp.zeros_like(rows), rows)

    def write(self, block, rows):
        """``block`` with the batch's ``rows [layers, B, ...]`` in: the
        one slot's, or the active rows' (a select over the block: for
        the SMALL blocks; a large one is updated by its kernel)."""
        rows = jnp.asarray(rows, block.dtype)
        if self.slot is not None:
            return jax.lax.dynamic_update_slice_in_dim(block, rows,
                                                       self.slot, axis=1)
        mask = self.active.reshape((1, -1) + (1,) * (block.ndim - 2))
        return jnp.where(mask, rows, block)


@flax.struct.dataclass
class SlotState:
    """What a model keeps PER SLOT beside (or instead of) its paged K/V:
    named blocks ``[layers_of_that_kind, slots, *shape]`` of values that
    the next token's step needs of this one - a convolution's last
    inputs, a shifted projection, a recurrent matrix
    (:class:`CacheSpec`; e.g. :class:`~apex_tpu.models.zaya.ZayaLM`,
    :class:`~apex_tpu.models.qwen3_next.Qwen3NextLM`). Unlike a page it
    belongs to the slot, not to a position: it is overwritten every
    step, cannot be shared copy-on-write, and means nothing without the
    exact position it was left at - which is why prefix retention, swap
    and preemption (all of which re-enter a request mid-stream from
    PAGES) are refused for such models until this state is snapshotted
    with them.

    Lives in the :class:`PagedKVCache` pytree, so it is donated with
    the pool and written in place by the same programs (a small block
    through :meth:`SlotAddr.write`, a large one by its kernel, aliased).
    The program that admits a request (the chunk program at offset 0)
    starts the slot from zeros; nothing on the host ever clears it."""

    blocks: Dict[str, jnp.ndarray]   # name -> [layers_k, slots, *shape]
    # tokens routed to each expert since the engine was built (or
    # `Engine.moe_reset_counts`), accumulated by the programs on the
    # device and read once when asked; [layers, 0] for a model with no
    # expert layer
    expert_tokens: jnp.ndarray   # [layers, num_experts] int32

    @classmethod
    def create(cls, spec: CacheSpec, *, slots: int,
               dtype: Any = jnp.bfloat16):
        return cls(
            blocks={b.name: jnp.zeros((b.layers, slots) + b.shape,
                                      b.dtype or dtype)
                    for b in spec.state},
            expert_tokens=jnp.zeros((spec.counter_layers, spec.num_experts),
                                    jnp.int32))

    def bytes_per_slot(self) -> int:
        return sum(int(b.size // b.shape[1] * b.dtype.itemsize)
                   for b in self.blocks.values())

    def nbytes(self) -> int:
        return sum(int(b.size * b.dtype.itemsize)
                   for b in self.blocks.values())


@flax.struct.dataclass
class PagedKVCache:
    """Paged KV pool pytree: ``[layers, num_pages, heads, head_dim,
    page_len]`` K and V. Pure device storage — lengths and page tables
    are host state (the engine's :class:`PagePool` + numpy tables,
    passed as per-call operands), so the donated pytree is exactly the
    two hot arrays.

    **Written in place.** The decode, chunk and verify programs take
    the donated pool, write each layer's new K/V into it where it lies
    and hand the same buffer back: the model threads the whole stacked
    pool through its layers (no layer is sliced out or restacked) and
    the paged kernels read their pages straight out of it
    (:class:`~apex_tpu.models.transformer_lm.SelfAttention`). That
    only holds if the program, the kernels' page DMA and the chip's own
    storage agree on ONE physical form, which is why a page is held
    ``[head_dim, page_len]``: with ``page_len`` (a multiple of 128) in
    the lanes every tile is full, so the chip's default layout is the
    plain row-major one the kernels read; with a 64-wide ``head_dim``
    there instead the compiler stores the pool the other way round to
    dodge the padding and re-lays a layer of it out and back around
    every kernel call (``Engine.program_memory`` reads the difference
    as the programs' temporaries)."""

    k: jnp.ndarray        # [layers, num_pages, heads, head_dim, page_len]
    # the same shape, or ZERO heads under the latent page kind
    # (CacheSpec.value_dim): the one pool is `k`, value and key both
    v: jnp.ndarray
    # quantized storage tier (kv_quant): per-[layer, head] fp32 dequant
    # scales; None on the bf16 default. Per-head — NOT per-page — so a
    # copy-on-write share never copies scale state alongside its pages.
    k_scale: Optional[jnp.ndarray] = None   # [layers, heads] fp32
    v_scale: Optional[jnp.ndarray] = None   # [layers, heads] fp32
    # per-slot state beside the pages (a model whose CacheSpec has state);
    # None — no leaf, the pytree the programs always had — otherwise
    state: Optional[SlotState] = None

    # ------------------------------------------------------------- geometry
    @property
    def layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def heads(self) -> int:
        return self.k.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k.shape[3]

    @property
    def page_len(self) -> int:
        return self.k.shape[4]

    @property
    def page_shape(self) -> Tuple[int, int, int]:
        """One page as stored: ``(heads, head_dim, page_len)`` — the
        trailing dims of the pool and of every swapped page block."""
        return self.k.shape[2:]

    @property
    def dtype(self):
        return self.k.dtype

    def nbytes(self) -> int:
        """Device bytes held by the pool (both K and V)."""
        return int((self.k.size + self.v.size) * self.k.dtype.itemsize)

    def bytes_per_token(self) -> int:
        """Pool bytes one cached position takes, over all page layers."""
        return self.nbytes() // (self.num_pages * self.page_len)

    @classmethod
    def create(cls, *, layers: int, num_pages: int, heads: int,
               page_len: int, head_dim: int, dtype: Any = jnp.bfloat16,
               k_scale=None, v_scale=None, state=None,
               value_dim: int = 0) -> "PagedKVCache":
        """Allocate a zeroed pool (``dtype`` normally the amp half
        dtype, or int8 with the scale pair under the engine's
        ``kv_quant`` tier). ``num_pages`` INCLUDES the page-0 sentinel,
        so the usable capacity is ``(num_pages - 1) * page_len``
        positions. ``value_dim`` > 0: the latent page kind, one pool
        (:class:`CacheSpec`)."""
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "sentinel/garbage page)")
        shape = (layers, num_pages, heads, head_dim, page_len)
        v_shape = (layers, num_pages, 0 if value_dim else heads, head_dim,
                   page_len)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(v_shape, dtype),
                   k_scale=k_scale, v_scale=v_scale, state=state)

    def layer_view(self):
        """The ``(k, v)`` pool pair the paged model path consumes."""
        return self.k, self.v


class PagePool:
    """Host-side page allocator for a :class:`PagedKVCache`.

    Three pieces of state, all numpy/python (no device work ever):

    - a **free list** of allocatable page ids (page 0 — the sentinel —
      is never on it);
    - **refcounts** per page: a page is held once per slot whose table
      references it plus once per prefix-cache entry retaining it;
      :meth:`release` returns it to the free list only at refcount 0 —
      a shared page is never freed while anything can still read it;
    - a **reservation ledger**: :meth:`reserve` sets aside capacity
      without naming pages, so the scheduler can guarantee at admission
      that a request's worst-case growth (prompt + ``max_new_tokens``)
      will find pages mid-decode. :meth:`alloc` draws down the caller's
      reservation when one exists.

    ``cow_shares`` (pages with refcount > 1) is the copy-on-write
    telemetry signal: every such page is serving >= 2 readers for the
    price of one.
    """

    def __init__(self, num_pages: int, page_len: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "sentinel/garbage page)")
        if page_len < 1:
            raise ValueError("page_len must be >= 1")
        self.num_pages = int(num_pages)
        self.page_len = int(page_len)
        self.refcount = np.zeros(self.num_pages, np.int32)
        # LIFO free list: recently-freed pages are re-used first (their
        # HBM is most likely still warm in whatever cache hierarchy sits
        # above it); ids descend so fresh pools allocate low pages first
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self.reserved_total = 0

    # ------------------------------------------------------------- capacity
    @property
    def free_pages(self) -> int:
        """Pages on the free list (ignores reservations)."""
        return len(self._free)

    @property
    def available(self) -> int:
        """Pages an admission may still reserve: free minus already-
        promised reservations (never negative)."""
        return max(0, len(self._free) - self.reserved_total)

    @property
    def pages_in_use(self) -> int:
        """Allocatable pages currently referenced (excludes sentinel)."""
        return self.num_pages - 1 - len(self._free)

    @property
    def cow_shares(self) -> int:
        """Pages shared by more than one reader — each is a prefix-cache
        copy the paged layout never had to materialise."""
        return int(np.sum(self.refcount > 1))

    def pages_for(self, positions: int) -> int:
        """Pages covering ``positions`` logical positions."""
        return -(-int(positions) // self.page_len)

    def free_list(self) -> Tuple[int, ...]:
        """Snapshot of the free list (page ids, allocation order not
        guaranteed) — the :class:`~apex_tpu.serving.PoolAuditor`'s view
        for free-list hygiene checks (no duplicates, refcount 0 only,
        disjoint from referenced pages)."""
        return tuple(self._free)

    # ----------------------------------------------------------- allocation
    def reserve(self, n: int) -> bool:
        """Promise ``n`` pages to a future caller (no pages named yet).
        False — and no state change — when the pool cannot cover the
        promise on top of existing reservations."""
        n = int(n)
        if n < 0:
            raise ValueError("reserve expects n >= 0")
        if n > self.available:
            return False
        self.reserved_total += n
        return True

    def unreserve(self, n: int) -> None:
        """Return unused reservation (a finished request rarely used its
        worst case)."""
        self.reserved_total = max(0, self.reserved_total - int(n))

    def alloc(self, *, reserved: bool = False) -> Optional[int]:
        """One page off the free list (refcount -> 1), or None when the
        list is empty. ``reserved=True`` draws down the ledger — the
        caller is consuming a promise made at admission."""
        if not self._free:
            return None
        page = self._free.pop()
        self.refcount[page] = 1
        if reserved:
            self.reserved_total = max(0, self.reserved_total - 1)
        return page

    def share(self, pages: Iterable[int]) -> None:
        """One more reader per page (copy-on-write: a prefix hit or a
        prefix-cache registration shares pages instead of copying)."""
        for p in pages:
            p = int(p)
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} out of range (1, "
                                 f"{self.num_pages})")
            if self.refcount[p] <= 0:
                raise ValueError(f"page {p} is free — cannot share")
            self.refcount[p] += 1

    def release(self, pages: Iterable[int]) -> None:
        """One fewer reader per page; pages reaching refcount 0 return
        to the free list immediately (the paged layout's instant
        reclamation)."""
        for p in pages:
            p = int(p)
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} out of range (1, "
                                 f"{self.num_pages})")
            if self.refcount[p] <= 0:
                raise ValueError(f"page {p} already free")
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)

    # ------------------------------------------------------------ reporting
    def fragmentation(self, lengths: Sequence[int],
                      pages_per_slot: Sequence[int]) -> float:
        """Internal fragmentation: the fraction of allocated SLOT
        positions holding no valid token (last-page slack + padded
        prefill windows). Prefix-entry pages held at refcount but
        referenced by no slot are the caller's to exclude — this is the
        per-slot view."""
        alloc = int(np.sum(np.asarray(pages_per_slot, np.int64))) \
            * self.page_len
        if alloc == 0:
            return 0.0
        used = int(np.sum(np.asarray(lengths, np.int64)))
        return max(0.0, 1.0 - used / alloc)

    def stats(self) -> dict:
        """Snapshot for telemetry / bench rows."""
        return {
            "num_pages": self.num_pages,
            "page_len": self.page_len,
            "pages_in_use": self.pages_in_use,
            "pages_free": self.free_pages,
            "pages_reserved": self.reserved_total,
            "cow_shares": self.cow_shares,
        }

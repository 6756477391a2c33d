"""Paged KV cache for serving: block pool, prefix trie, paged programs.

The slot engine (``serving/engine.py``) holds one contiguous ring buffer
``[L, window, slots, H, Dh]`` — every slot owns a window-sized region
for its whole lifetime, and the only prefix reuse is ONE registered
system prompt.  This module replaces that memory story with the
production paged layout (the vLLM design point, adapted to the repo's
static-shape TPU rules):

* **Block pool.**  K/V live in ``[L, num_blocks, block_size, H, Dh]``
  pools.  A request's cache is a *block table* — the list of physical
  blocks backing its logical token positions — so freed requests return
  blocks to the pool immediately instead of holding a slot-shaped
  region, and total KV memory is sized to live tokens, not
  ``slots x window``.
* **Refcounted sharing + COW.**  Blocks are refcounted
  (:class:`BlockPool`): a full prompt block can back many requests at
  once.  Sharing is read-only by construction — the trie never shares a
  request's *last* prompt block, so every write a request performs
  (suffix prefill, decode appends) lands in blocks it owns alone —
  and :meth:`BlockPool.cow` is the guarded write path for anything
  else: writing a shared block first clones it.
* **Prefix trie.**  :class:`PrefixTrie` maps chains of full token
  blocks to cached pool blocks (copy-on-write semantics over the
  refcounts): a request whose prompt starts with a cached chain skips
  recomputing those blocks entirely — its prefill runs only over the
  suffix, attending the cached blocks through its block table.  This
  generalizes the old single ``set_prefix`` slot to arbitrary
  multi-tenant shared prefixes; refcount-zero cached blocks are LRU
  material when the pool runs dry.
* **Paged device programs.**  ``_paged_chunk_program`` /
  ``_paged_prefill_program`` mirror the slot engine's programs with the
  block table as a TRACED input: per-tick K/V writes scatter through
  ``(table[pos // bs], pos % bs)`` and attention gathers each slot's
  window from the pool.  The indirection costs a gather per layer per
  tick (the ring design's uniform contiguous write is exactly what
  paging gives up — on real TPUs this is where a paged-attention
  kernel goes); what it buys is admission decoupled from memory shape:
  any free slot plus enough free blocks admits any request, and block
  tables never force a recompile (they are data, not shape).  With
  ``AUTODIST_FUSED_KERNELS=paged_attention`` the decode program drops
  the gather entirely: the fused Pallas kernel
  (``ops/fused_kernels.py``, docs/kernels.md) reads K/V straight
  through the block table via scalar-prefetch index maps with the
  flash-attention online-softmax structure; off-TPU the gather path
  stays, with a shared drop-reason WARN (on a TPU a kernel that cannot
  lower raises).

Numerics are the same single-definition ``TransformerLayer`` math as
training/decode (the ``attn_fn`` seam), so greedy paged output equals
the per-request ``generate`` oracle exactly — pinned in
``tests/test_serving_scheduler.py``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from autodist_tpu.models.generate import unpack_lm_params
from autodist_tpu.models.quantize import (embed_lookup, head_logits,
                                          quant_interceptor)
from autodist_tpu.models.transformer import TransformerLayer
from autodist_tpu.ops.quant import Quantized
from autodist_tpu.serving.engine import _sample_per_slot

#: physical block 0 is reserved as the scratch target: device programs
#: redirect every masked-out write (dead slots, pad rows) there, so a
#: freed block can be handed to a new owner between dispatches without
#: any risk of a stale slot scribbling on it.
SCRATCH_BLOCK = 0

_paged_kernel_warned = False


def _use_fused_paged_attention() -> bool:
    """Does this trace lower decode attention through the fused Pallas
    paged-attention kernel (``ops/fused_kernels.py``, opted in via
    ``AUTODIST_FUSED_KERNELS=paged_attention``)?  Resolved at TRACE
    time — the jit cache pins the decision per program, like every
    other static knob of ``_paged_chunk_program``.  A requested kernel
    that cannot lower raises on a TPU; off-TPU it falls back to the
    gather-per-layer path with one shared drop-reason WARN."""
    global _paged_kernel_warned
    from autodist_tpu.ops import fused_kernels as fk

    active, why = fk.paged_attention_status()
    if why is not None and not _paged_kernel_warned:
        _paged_kernel_warned = True
        fk.drop_or_raise("paged decode", fk.KERNEL_PAGED_ATTENTION, why)
    return active


class BlockPoolExhausted(RuntimeError):
    """alloc() could not be satisfied even after trie eviction."""


@dataclass
class BlockPoolStats:
    allocs: int = 0               # blocks handed out
    frees: int = 0                # blocks returned to the free list
    cow_copies: int = 0           # shared-block writes that cloned
    exhaustions: int = 0          # alloc() failures (pool dry)
    high_water: int = 0           # max blocks simultaneously in use


class BlockPool:
    """Host-side allocator over the physical KV blocks.

    Pure bookkeeping — the device arrays live with the engine; the pool
    tracks which physical block indices are free, each block's
    refcount, and the alloc/free/COW invariants the tests pin:

    * a block is either free (refcount 0, on the free list) or held
      (refcount >= 1), never both;
    * ``release`` frees exactly when the last reference drops;
    * ``cow`` returns the block itself when exclusively held and a
      fresh block (dropping one reference on the shared one) when not;
    * block 0 (:data:`SCRATCH_BLOCK`) is reserved and never allocated.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 blocks (one is the "
                             "reserved scratch block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # LIFO free list: recently freed blocks are re-used first (their
        # pool regions are most likely still resident in cache).
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: List[int] = [0] * num_blocks
        self.stats = BlockPoolStats()

    # -- capacity ----------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the scratch block)."""
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.capacity - self.free_count

    def occupancy(self) -> float:
        return self.used_count / self.capacity if self.capacity else 0.0

    def blocks_for_tokens(self, tokens: int) -> int:
        return -(-int(tokens) // self.block_size)

    # -- alloc / refcount --------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` blocks (each at refcount 1) or raise
        :class:`BlockPoolExhausted` allocating NONE (all-or-nothing, so
        a failed admission never leaks a partial allocation)."""
        if n < 0:
            raise ValueError("alloc needs n >= 0")
        if n > len(self._free):
            self.stats.exhaustions += 1
            raise BlockPoolExhausted(
                f"need {n} blocks, {len(self._free)} free "
                f"(capacity {self.capacity})")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        self.stats.allocs += n
        self.stats.high_water = max(self.stats.high_water, self.used_count)
        return out

    def retain(self, block: int) -> None:
        if self._refs[block] < 1:
            raise ValueError(f"retain on unallocated block {block}")
        self._refs[block] += 1

    def release(self, block: int) -> bool:
        """Drop one reference; True when this freed the block."""
        if block == SCRATCH_BLOCK:
            raise ValueError("release on the reserved scratch block")
        if self._refs[block] < 1:
            raise ValueError(f"release on free block {block} "
                             "(double free)")
        self._refs[block] -= 1
        if self._refs[block] == 0:
            self._free.append(block)
            self.stats.frees += 1
            return True
        return False

    def refcount(self, block: int) -> int:
        return self._refs[block]

    def cow(self, block: int) -> Tuple[int, bool]:
        """Copy-on-write guard for writing ``block``: exclusively held
        blocks are returned as-is; shared blocks allocate a fresh block
        (the caller must copy the device data), dropping one reference
        on the shared original.  Returns ``(writable_block, copied)``."""
        if self._refs[block] < 1:
            raise ValueError(f"cow on unallocated block {block}")
        if self._refs[block] == 1:
            return block, False
        (fresh,) = self.alloc(1)
        self.release(block)
        self.stats.cow_copies += 1
        return fresh, True

    def verify(self) -> None:
        """Leak/corruption check: every block is exactly free or held,
        and the free list is duplicate-free.  Raises AssertionError."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate blocks on free list"
        assert SCRATCH_BLOCK not in free, "scratch block on free list"
        for b in range(1, self.num_blocks):
            if b in free:
                assert self._refs[b] == 0, \
                    f"block {b} free but refcount {self._refs[b]}"
            else:
                assert self._refs[b] >= 1, \
                    f"block {b} leaked (not free, refcount 0)"


@dataclass
class _TrieNode:
    key: Tuple[int, ...]                    # the block's tokens
    block: int
    parent: Optional["_TrieNode"]
    children: Dict[Tuple[int, ...], "_TrieNode"] = field(
        default_factory=dict)
    last_used: float = field(default_factory=time.monotonic)


@dataclass
class PrefixTrieStats:
    hit_blocks: int = 0           # cached blocks handed to requests
    hit_tokens: int = 0
    lookups: int = 0
    lookup_hits: int = 0          # lookups that matched >= 1 block
    inserts: int = 0              # blocks newly cached
    evictions: int = 0            # cached blocks dropped under pressure


class PrefixTrie:
    """Radix cache over FULL prompt blocks.

    Each node caches one block's worth of tokens; a path from the root
    is a prompt prefix whose K/V already live in the pool.  The trie
    holds one pool reference per cached block (so a cached block
    survives its computing request); a matching request retains each
    matched block again for its own lifetime.  Only chains of FULL
    blocks are cached, and a match never covers the whole prompt
    (``match`` caps at ``floor((P-1)/bs)`` blocks) so every request
    prefills at least one suffix token — which also guarantees no
    request ever WRITES a shared block: its writes start at or after
    its suffix, which begins past the shared region.

    Eviction is LRU over refcount-1 leaf nodes — blocks only the trie
    still holds ("refcount-zero" from the requests' point of view);
    interior nodes wait for their children (a chain must stay
    root-connected to be matchable).
    """

    def __init__(self, pool: BlockPool):
        self._pool = pool
        self._root_children: Dict[Tuple[int, ...], _TrieNode] = {}
        self._count = 0
        self.stats = PrefixTrieStats()

    def __len__(self) -> int:
        return self._count

    def _chunks(self, tokens, limit_blocks: int):
        bs = self._pool.block_size
        out = []
        for i in range(limit_blocks):
            out.append(tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
        return out

    def match(self, tokens) -> Tuple[int, List[int]]:
        """Longest cached chain covering ``tokens`` (capped to leave at
        least one suffix token uncovered).  Returns ``(n_cached_tokens,
        block_ids)`` with each returned block RETAINED for the caller —
        symmetric with the caller releasing every block of its table on
        completion."""
        bs = self._pool.block_size
        p = len(tokens)
        limit = max((p - 1) // bs, 0)
        self.stats.lookups += 1
        now = time.monotonic()
        blocks: List[int] = []
        children = self._root_children
        for key in self._chunks(tokens, limit):
            node = children.get(key)
            if node is None:
                break
            node.last_used = now
            self._pool.retain(node.block)
            blocks.append(node.block)
            children = node.children
        if blocks:
            self.stats.lookup_hits += 1
            self.stats.hit_blocks += len(blocks)
            self.stats.hit_tokens += len(blocks) * bs
        return len(blocks) * bs, blocks

    def insert(self, tokens, table: List[int]) -> int:
        """Cache the full prompt blocks of a request whose K/V for
        ``tokens`` now live in ``table`` (its block table, in logical
        order).  Blocks newly cached are retained by the trie; chunks
        already cached are skipped (first writer wins — the duplicate
        block stays owned by its request alone and frees with it).
        Returns how many blocks were newly cached."""
        limit = max((len(tokens) - 1) // self._pool.block_size, 0)
        limit = min(limit, len(table))
        added = 0
        children = self._root_children
        parent: Optional[_TrieNode] = None
        for i, key in enumerate(self._chunks(tokens, limit)):
            node = children.get(key)
            if node is None:
                node = _TrieNode(key=key, block=table[i], parent=parent)
                self._pool.retain(table[i])
                children[key] = node
                self._count += 1
                added += 1
            children = node.children
            parent = node
        self.stats.inserts += added
        return added

    def evict(self, n: int) -> int:
        """Release up to ``n`` cached blocks, LRU-first among leaf
        nodes whose block only the trie still references.  Returns how
        many blocks were actually freed to the pool."""
        freed = 0
        while freed < n:
            victim = None
            for node in self._iter_nodes():
                if node.children:
                    continue                        # interior: keep chain
                if self._pool.refcount(node.block) != 1:
                    continue                        # pinned by a request
                if victim is None or node.last_used < victim.last_used:
                    victim = node
            if victim is None:
                break
            self._remove(victim)
            self._pool.release(victim.block)
            self.stats.evictions += 1
            freed += 1
        return freed

    def clear(self) -> int:
        """Drop the whole cache (releases every trie reference —
        blocks still pinned by in-flight requests stay alive until
        those requests finish).  Returns blocks released."""
        n = 0
        for node in list(self._iter_nodes()):
            self._pool.release(node.block)
            n += 1
        self._root_children.clear()
        self._count = 0
        return n

    def cached_blocks(self) -> List[int]:
        return [node.block for node in self._iter_nodes()]

    def _iter_nodes(self):
        stack = list(self._root_children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    def _remove(self, node: _TrieNode) -> None:
        siblings = (node.parent.children if node.parent is not None
                    else self._root_children)
        del siblings[node.key]
        self._count -= 1


# ---------------------------------------------------------------------------
# device programs (module scope: the jit cache is shared across engines)
# ---------------------------------------------------------------------------

def _paged_token_step(layer_params, ln_final_scale, embed, x, kc, vc,
                      bt, blk, off, rel):
    """One decode position through all layers over the PAGED cache.

    ``kc``/``vc``: [L, NB, BS, H, Dh] pools; ``bt``: [B, MAXB] block
    table; ``blk``/``off``: [B] physical write coordinates for this
    tick (pre-masked: dead slots point at the scratch block); ``rel``:
    [B] logical sequence position.  Same shared ``TransformerLayer``
    block math as ``generate._token_step`` — only the cache addressing
    differs: the write scatters through the table and attention gathers
    each slot's logical window ``take(pool, bt)`` before the usual
    masked softmax (extra masked positions contribute exactly-zero
    weight, so numerics match the contiguous layouts)."""
    heads, hd = kc.shape[-2], kc.shape[-1]
    bs = kc.shape[2]
    b, maxb = bt.shape
    w = maxb * bs
    d_ff = layer_params[0]["mlp"]["wi"]["kernel"].shape[1]
    quantized = isinstance(layer_params[0]["mlp"]["wi"]["kernel"],
                           Quantized)
    x = x[:, None, :]                                   # [B, 1, D]
    mask = jnp.arange(w)[None, None, :] <= rel[:, None, None]  # [B,1,W]
    fused_attn = _use_fused_paged_attention()
    for i, lp in enumerate(layer_params):
        cache_out = {}

        def paged_attn(q, k, v, causal, _i=i, _out=cache_out):
            kcn = kc.at[_i, blk, off].set(k[:, 0].astype(kc.dtype))
            vcn = vc.at[_i, blk, off].set(v[:, 0].astype(vc.dtype))
            _out["k"], _out["v"] = kcn, vcn
            if fused_attn:
                # Fused paged-attention kernel (docs/kernels.md): the
                # block table drives scalar-prefetch index maps, so the
                # kernel DMAs exactly the physical blocks each slot's
                # window names — no [B, W, H, Dh] gather materialized
                # per layer per tick.
                from autodist_tpu.ops.fused_kernels import paged_attention
                return paged_attention(q[:, 0], kcn[_i], vcn[_i], bt,
                                       rel)[:, None]
            # each slot's logical window, gathered from the pool
            kb = jnp.take(kcn[_i], bt, axis=0).reshape(b, w, heads, hd)
            vb = jnp.take(vcn[_i], bt, axis=0).reshape(b, w, heads, hd)
            depth = q.shape[-1]
            logits = jnp.einsum("bhk,bwhk->bhw", q[:, 0],
                                kb.astype(q.dtype)) \
                / jnp.sqrt(jnp.asarray(depth, q.dtype))
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
            probs = jax.nn.softmax(logits.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
            return jnp.einsum("bhw,bwhk->bhk", probs,
                              vb.astype(q.dtype))[:, None]

        layer = TransformerLayer(heads, hd, d_ff, causal=True,
                                 attn_fn=paged_attn)
        if quantized:
            with nn.intercept_methods(quant_interceptor(lp)):
                x = layer.apply({"params": lp}, x)
        else:
            x = layer.apply({"params": lp}, x)
        kc, vc = cache_out["k"], cache_out["v"]
    x = nn.LayerNorm(use_bias=False).apply(
        {"params": {"scale": ln_final_scale}}, x)
    return head_logits(embed, x[:, 0]), kc, vc


@functools.partial(jax.jit, static_argnums=(0, 1),
                   donate_argnums=(3, 4, 5))
def _paged_chunk_program(n, knobs, params, tokens, kc, vc, bt, start,
                         p_end, end, done, active, temp, eos, tick0,
                         key):
    """``n`` decode ticks of all slots in lockstep over the paged pool.

    The paged analog of ``engine._chunk_program``: positions are
    LOGICAL (``rel = tick - start``, no ring — the block table is the
    indirection), token reads/writes index each slot's row at its own
    ``rel``, and K/V writes route through the table with dead slots
    redirected to the scratch block (a freed block may already belong
    to someone else).  ``knobs`` = (top_k, top_p, block_size)."""

    top_k, top_p, bs = knobs
    num_layers = kc.shape[0]
    slots, w = tokens.shape
    embed, pos_embed, layer_params, ln_final = unpack_lm_params(
        params, num_layers)
    rows = jnp.arange(slots)

    def one_tick(carry, i):
        tokens, kc, vc, done, key = carry
        t = tick0 + i
        rel = jnp.clip(t - start, 0, w - 1)               # [B] logical pos
        tok = jnp.take_along_axis(tokens, rel[:, None], 1)[:, 0]
        x = embed_lookup(embed, tok, pos_embed.dtype) + pos_embed[rel]
        live = active & ~done
        blk = jnp.where(
            live,
            jnp.take_along_axis(bt, rel[:, None] // bs, 1)[:, 0],
            SCRATCH_BLOCK)
        logits, kc, vc = _paged_token_step(
            layer_params, ln_final, embed, x, kc, vc, bt, blk,
            jnp.mod(rel, bs), rel)
        key, sub = jax.random.split(key)
        raw = _sample_per_slot(logits, sub, temp, top_k,
                               top_p).astype(tokens.dtype)
        busy = jnp.sum(live.astype(jnp.int32))
        w_pos = jnp.clip(rel + 1, 0, w - 1)
        cur = jnp.take_along_axis(tokens, w_pos[:, None], 1)[:, 0]
        in_gen = t + 1 >= p_end
        nxt = jnp.where(in_gen & live, raw, cur)
        tokens = tokens.at[rows, w_pos].set(nxt)
        done = done | (in_gen & live & (raw == eos))
        done = done | (t + 2 >= end)
        return (tokens, kc, vc, done, key), busy

    (tokens, kc, vc, done, key), busy = lax.scan(
        one_tick, (tokens, kc, vc, done, key), jnp.arange(n))
    return tokens, kc, vc, done, jnp.sum(busy)


@functools.partial(jax.jit, static_argnums=(0,),
                   donate_argnums=(2, 3, 4))
def _paged_prefill_program(knobs, params, tokens, kc, vc, chunk_kpb,
                           bt_rows, slot_ids, n_shared, c_lens,
                           is_final, temp, key):
    """One prefill CHUNK for K rows: a [K, Pb]-parallel causal forward
    over each row's next ``c_lens[k]`` uncharged prompt tokens, with
    everything already charged — trie-cached prefix blocks AND earlier
    chunks of the same prompt, both addressed by the row's block table
    masked to ``n_shared[k]`` tokens — attended as cached context.
    That one traced mask is what makes prefix reuse and chunked
    prefill the SAME program: a cold prompt runs with ``n_shared=0``, a
    prefix hit starts at the cached length, and a long prompt walks
    ``n_shared`` forward chunk by chunk between decode ticks.

    Suffix K/V scatter into the pool at logical positions
    ``n_shared + j`` through the block table (pad columns to the
    scratch block); rows with ``is_final`` (their last chunk) also
    sample their first generated token from the chunk's last position
    and deposit it at ``n_shared + c_len``.  Duplicate ``slot_ids``
    (pow-2 padding repeats the last row) are resolved by reading back
    the LANDED token, as in the slot engine's prefill.

    Besides the landed token, the program returns ``preds`` — the
    greedy argmax at EVERY chunk position (``preds[k, j]`` is the
    model's token for logical position ``n_shared[k] + j + 1``).
    Prefill callers ignore it; it is what makes multi-token
    speculative VERIFY this same traced program: the scheduler feeds
    the gamma+1 candidate tokens as a chunk with ``n_shared`` at the
    request's committed length, and greedy acceptance falls out of
    comparing ``preds`` against the drafts on the host — no extra
    program cache entries beyond the (gamma-bucketed) chunk length."""

    top_k, top_p, bs = knobs
    num_layers = kc.shape[0]
    heads, hd = kc.shape[-2], kc.shape[-1]
    k_rows, pb = chunk_kpb.shape
    maxb = bt_rows.shape[1]
    w = maxb * bs
    embed, pos_embed, layer_params, ln_final = unpack_lm_params(
        params, num_layers)
    d_ff = layer_params[0]["mlp"]["wi"]["kernel"].shape[1]
    quantized = isinstance(layer_params[0]["mlp"]["wi"]["kernel"],
                           Quantized)
    pos_ids = jnp.clip(n_shared[:, None] + jnp.arange(pb)[None, :], 0,
                       pos_embed.shape[0] - 1)
    x = embed_lookup(embed, chunk_kpb, pos_embed.dtype) \
        + pos_embed[pos_ids]
    ctx_mask = jnp.arange(w)[None, None, None, :] \
        < n_shared[:, None, None, None]                  # [K,1,1,W]
    ks, vs = [], []

    def capture_attn(q, k, v, causal):
        i = len(ks)
        ks.append(k)
        vs.append(v)
        depth = q.shape[-1]
        scale = jnp.sqrt(jnp.asarray(depth, q.dtype))
        sl = jnp.einsum("bqhd,bkhd->bhqk", q, k) / scale
        causal_m = jnp.tril(jnp.ones((pb, pb), bool))
        sl = jnp.where(causal_m, sl, jnp.finfo(sl.dtype).min)
        kb = jnp.take(kc[i], bt_rows, axis=0).reshape(
            k_rows, w, heads, hd).astype(q.dtype)
        vb = jnp.take(vc[i], bt_rows, axis=0).reshape(
            k_rows, w, heads, hd).astype(q.dtype)
        pl = jnp.einsum("bqhd,bphd->bhqp", q, kb) / scale
        pl = jnp.where(ctx_mask, pl, jnp.finfo(sl.dtype).min)
        probs = jax.nn.softmax(
            jnp.concatenate([pl, sl], axis=-1).astype(jnp.float32),
            axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqp,bphd->bqhd", probs[..., :w], vb)
        return out + jnp.einsum("bhqk,bkhd->bqhd", probs[..., w:], v)

    for lp in layer_params:
        layer = TransformerLayer(heads, hd, d_ff, causal=True,
                                 attn_fn=capture_attn)
        if quantized:
            with nn.intercept_methods(quant_interceptor(lp)):
                x = layer.apply({"params": lp}, x)
        else:
            x = layer.apply({"params": lp}, x)
    x = nn.LayerNorm(use_bias=False).apply(
        {"params": {"scale": ln_final}}, x)

    ksl = jnp.stack(ks)                                  # [L, K, Pb, H, Dh]
    vsl = jnp.stack(vs)
    pos = n_shared[:, None] + jnp.arange(pb)[None, :]    # [K, Pb]
    valid = jnp.arange(pb)[None, :] < c_lens[:, None]
    blk = jnp.where(
        valid,
        jnp.take_along_axis(bt_rows, jnp.clip(pos // bs, 0, maxb - 1), 1),
        SCRATCH_BLOCK)
    off = jnp.mod(pos, bs)
    kc = kc.at[:, blk, off].set(ksl.astype(kc.dtype))
    vc = vc.at[:, blk, off].set(vsl.astype(vc.dtype))

    # Every position's logits: the last position's row feeds sampling
    # (the prefill path), the full [K, Pb] argmax is the verify surface.
    all_logits = head_logits(embed, x.reshape(k_rows * pb, -1)) \
        .reshape(k_rows, pb, -1)                         # [K, Pb, V]
    preds = jnp.argmax(all_logits, axis=-1).astype(tokens.dtype)
    logits = jnp.take_along_axis(
        all_logits,
        jnp.clip(c_lens - 1, 0, pb - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]                                    # [K, V]
    temp_k = jnp.take(temp, slot_ids)
    toks = _sample_per_slot(logits, key, temp_k, top_k, top_p)
    w_pos = jnp.clip(n_shared + c_lens, 0, tokens.shape[1] - 1)
    cur = tokens[slot_ids, w_pos]
    tokens = tokens.at[slot_ids, w_pos].set(
        jnp.where(is_final, toks.astype(tokens.dtype), cur))
    landed = tokens[slot_ids, w_pos]
    return tokens, kc, vc, landed, preds


@functools.partial(jax.jit, donate_argnums=(0,))
def _commit_tokens_program(tokens, rows, pos, vals):
    """Batched point-writes into the device token buffer: one token per
    ``(rows[i], pos[i])`` pair.  The speculative round's bonus-token
    commit — pow-2 padded by repeating the last entry (duplicate writes
    of the same value are idempotent)."""
    return tokens.at[rows, pos].set(vals.astype(tokens.dtype))

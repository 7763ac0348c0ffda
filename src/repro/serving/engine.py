"""In-process LLM serving engine — the in-database inference backend that
the PREDICT operator drives (DESIGN.md §2: iPDB's "local model executor" on
a TPU pod).

Features mapped from the paper's optimizations:
  * batched prefill + decode with jit-compiled bucketed steps
  * grammar-constrained decoding (per-step masks from serving.grammar,
    applied by the fused constrained_logits kernel or the jnp ref)
  * shared-prefix KV reuse: the instruction prefix of a marshaled prompt is
    prefilled once and extended — the compute-side realization of multi-row
    prompt marshaling (§6.2).  Two layouts:
      - kv_layout="dense": per-row contiguous caches; the memoized prefix
        KV is broadcast (physically replicated) across the row batch
      - kv_layout="paged": one global pool of fixed-size KV pages plus
        per-row block tables; shared FULL prefix pages are referenced —
        not copied — by every row (O(1) memory, zero per-row device
        copies) and decode attention walks only the pages a row occupies
  * continuous batching (scheduler.py) with per-row cache indices (dense)
    or page-table slot lifecycle (paged)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MDL
from repro.models.config import ModelConfig
from repro.serving import tokenizer as TOK
from repro.serving.grammar import JsonGrammar
from repro.serving.radix import RadixPrefixCache

NEG_INF = -1e30


def _bucket(n: int, buckets=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


@dataclasses.dataclass
class GenStats:
    calls: int = 0
    input_tokens: int = 0
    output_tokens: int = 0
    prefill_tokens: int = 0        # actually prefit through the model
    decode_steps: int = 0
    wall_s: float = 0.0
    prefix_hits: int = 0
    radix_hit_tokens: int = 0      # prompt tokens served from the radix tree
    cow_copies: int = 0            # pages privatized by copy-on-write forks
    kv_bytes: int = 0              # peak KV-cache footprint (high-water)

    def add(self, other: "GenStats") -> None:
        for f in dataclasses.fields(self):
            if f.name == "kv_bytes":       # high-water mark, not a flow
                self.kv_bytes = max(self.kv_bytes, other.kv_bytes)
            else:
                setattr(self, f.name,
                        getattr(self, f.name) + getattr(other, f.name))


@dataclasses.dataclass
class GenResult:
    texts: List[str]
    stats: GenStats


class PageAllocator:
    """Host-side bookkeeping for the global KV page pool: a free list plus
    per-page refcounts (shared instruction-prefix pages are referenced by
    the prefix memo AND by every running batch that uses them) and a
    high-water mark — the `peak cache bytes` number the benchmarks report."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))       # pop() → 0 first
        self._ref = np.zeros(num_pages, np.int64)
        self.peak_in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def resident_pages(self) -> int:
        """Pages currently referenced by anyone (memo, radix tree, runs)."""
        return self.in_use

    @property
    def high_water(self) -> int:
        return self.peak_in_use

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, free {len(self._free)}"
                f" of {self.num_pages}")
        ids = [self._free.pop() for _ in range(n)]
        for p in ids:
            self._ref[p] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return ids

    def retain(self, ids: Sequence[int]) -> None:
        for p in ids:
            self._ref[p] += 1

    def refs(self, page: int) -> int:
        return int(self._ref[page])

    def release(self, ids: Sequence[int]) -> None:
        for p in ids:
            self._ref[p] -= 1
            assert self._ref[p] >= 0, f"double free of page {p}"
            if self._ref[p] == 0:
                self._free.append(p)

    def grow(self, extra: int) -> None:
        start = self.num_pages
        self.num_pages += extra
        self._ref = np.concatenate([self._ref, np.zeros(extra, np.int64)])
        self._free.extend(range(self.num_pages - 1, start - 1, -1))


@dataclasses.dataclass
class _PrefixEntry:
    """Memoized shared-prefix KV: the host copy (layout-independent source
    of truth; decode steps donate their buffers and must never alias it)
    plus, in paged mode, the pool pages it is currently resident in."""
    host_kv: dict
    off: int                        # bucketed prefix length (dense slots)
    real_len: int                   # true token count
    pages: Optional[List[int]] = None


class InferenceEngine:
    """Single-host engine around one model. Smoke configs run the real JAX
    forward on the CPU; the same code serves published configs on one TPU
    chip (see chip_smoke.py)."""

    def __init__(self, cfg: ModelConfig, params=None, *,
                 seed: int = 0, max_len: int = 1024,
                 use_pallas_sampler: bool = False,
                 kv_layout: str = "dense", page_size: int = 64,
                 page_pool_pages: Optional[int] = None,
                 prefix_memo_entries: int = 16,
                 use_pallas_decode: bool = False,
                 prefix_cache_mode: str = "radix",
                 kv_quant: str = "none"):
        assert cfg.supports_decode, f"{cfg.name} cannot generate"
        assert kv_layout in ("dense", "paged"), kv_layout
        assert prefix_cache_mode in ("exact", "radix"), prefix_cache_mode
        assert kv_quant in ("none", "int8"), kv_quant
        if kv_layout == "paged":
            assert cfg.has_attention, "paged KV layout needs attention"
        self.cfg = cfg
        self.max_len = max_len
        self.params = params if params is not None else \
            MDL.init_params(cfg, jax.random.PRNGKey(seed))
        self.use_pallas_sampler = use_pallas_sampler
        self.use_pallas_decode = use_pallas_decode
        self.kv_layout = kv_layout
        self.page_size = int(page_size)
        self.page_pool_pages = page_pool_pages
        self.prefix_memo_entries = int(prefix_memo_entries)
        #: "radix": partial-overlap prefix reuse through a refcounted radix
        #: tree over token sequences; "exact": PR-5 exact-string prefix memo
        self.prefix_cache_mode = prefix_cache_mode
        #: "int8": frozen (tree-committed) pages are quantized on commit to
        #: an int8 shadow pool with per-page scales; live pages stay fp
        self.kv_quant = kv_quant
        #: per-row block-table width: max_len tokens worth of pages
        self.num_table_blocks = max(1, -(-max_len // self.page_size))
        self._prefill_cache: Dict[Tuple, object] = {}
        self._decode_fns: Dict[object, object] = {}
        #: LRU memo of shared-prefix KV (touch-on-get, capped — mirrors
        #: PromptCache semantics); evicting a paged-resident entry releases
        #: its pool pages
        self._prefix_kv: Dict[Tuple[str, int], _PrefixEntry] = {}
        self._rng = np.random.default_rng(seed)
        #: session-cumulative stats (EXPLAIN `-- dispatch --` surfacing)
        self.total = GenStats()
        # paged-layout state (lazy): device page pool + host allocator +
        # radix prefix tree + host-side frozen-page quant flags
        self._pool: Optional[Dict[str, jax.Array]] = None
        self._alloc: Optional[PageAllocator] = None
        self._radix: Optional[RadixPrefixCache] = None
        self._quant_flags: Optional[np.ndarray] = None
        #: running peak of the pool's logical KV bytes, counting quantized
        #: pages at 1 byte/element — the `kv_bytes` number runs report
        self.kv_peak_bytes = 0

    # ----------------------------- compiled steps -----------------------------
    def _prefill_fn(self, batch: int, length: int, offset: int):
        key = (batch, length, offset)
        if key not in self._prefill_cache:
            cfg = self.cfg

            def prefill_step(params, tokens, positions, cache):
                logits, cache = MDL.forward(
                    cfg, params, {"tokens": tokens, "positions": positions},
                    mode="prefill", cache=cache, remat=False,
                    extend_offset=offset, last_only=True)
                return logits[:, -1], cache

            self._prefill_cache[key] = jax.jit(prefill_step)
        return self._prefill_cache[key]

    def _decode_fn(self):
        if "fn" not in self._decode_fns:
            cfg = self.cfg
            datt = None
            if self.use_pallas_decode:
                from repro.kernels import ops as KOPS
                datt = KOPS.decode_attention

            def decode_step(params, tokens, positions, cache):
                logits, cache = MDL.forward(
                    cfg, params, {"tokens": tokens, "positions": positions},
                    mode="decode", cache=cache, remat=False,
                    decode_attn_fn=datt)
                return logits[:, 0], cache

            self._decode_fns["fn"] = jax.jit(decode_step, donate_argnums=(3,))
        return self._decode_fns["fn"]

    def _decode_fn_paged(self, num_blocks: int):
        """Decode step against the page pool; jit-cached per block-table
        width, so the attention grid covers only the blocks the batch
        actually occupies (the caller buckets `num_blocks`)."""
        key = ("paged", num_blocks)
        if key not in self._decode_fns:
            cfg = self.cfg
            datt = None
            if self.use_pallas_decode:
                from repro.kernels import ops as KOPS
                datt = KOPS.decode_attention_paged

            def paged_decode_step(params, tokens, positions, cache, bt, qf):
                cache = dict(cache, block_tables=bt)
                if qf is not None:
                    cache["quant_flags"] = qf
                logits, cache = MDL.forward(
                    cfg, params, {"tokens": tokens, "positions": positions},
                    mode="decode", cache=cache, remat=False,
                    decode_attn_fn=datt)
                return logits[:, 0], cache

            self._decode_fns[key] = jax.jit(paged_decode_step,
                                            donate_argnums=(3,))
        return self._decode_fns[key]

    # ------------------------------- prefill ----------------------------------
    def _prefill(self, token_lists: List[List[int]], *, offset: int = 0,
                 pos_offset: Optional[int] = None,
                 cache: Optional[dict] = None, row_idx_mode: bool = False):
        """offset = cache slot offset (bucketed prefix length);
        pos_offset = absolute position offset (REAL prefix length — RoPE
        positions must not jump over the prefix bucket padding)."""
        if pos_offset is None:
            pos_offset = offset
        B = len(token_lists)
        L = _bucket(max(len(t) for t in token_lists))
        toks = np.full((B, L), TOK.PAD_ID, np.int32)
        pos = np.zeros((B, L), np.int32)
        for i, t in enumerate(token_lists):
            pad = L - len(t)
            toks[i, pad:] = t                                # left padding
            pos[i] = np.arange(L) - pad + pos_offset
            pos[i, :pad] = -1      # pads masked (never overlap the prefix)
        if cache is None:
            cache = MDL.init_cache(self.cfg, B, self.max_len)
            if row_idx_mode:
                cache["row_idx"] = jnp.zeros((B,), jnp.int32)
        logits, cache = self._prefill_fn(B, L, offset)(
            self.params, jnp.asarray(toks), jnp.asarray(pos), cache)
        if "row_idx" in cache or row_idx_mode:
            cache = dict(cache)
            cache["row_idx"] = jnp.full((B,), offset + L, jnp.int32)
        lens = np.array([pos_offset + len(t) for t in token_lists], np.int32)
        return np.asarray(logits, np.float32), cache, lens, B * L

    # ------------------------------ page pool ---------------------------------
    def _page_bytes(self) -> int:
        cfg = self.cfg
        itemsize = 2 if cfg.compute_dtype in ("bfloat16", "float16") else 4
        return (2 * cfg.num_layers * self.page_size * cfg.num_kv_heads
                * cfg.head_dim * itemsize)

    def _page_bytes_quant(self) -> int:
        """Logical bytes of a frozen int8 page (scales are negligible)."""
        cfg = self.cfg
        return (2 * cfg.num_layers * self.page_size * cfg.num_kv_heads
                * cfg.head_dim)

    def _note_kv(self) -> None:
        """Fold the pool's current logical KV footprint into the running
        peak: live pages at full precision, frozen pages at int8."""
        a = self._alloc
        if a is None:
            return
        nq = 0
        if self._quant_flags is not None:
            nq = int(np.sum((self._quant_flags[:a.num_pages] > 0)
                            & (a._ref[:a.num_pages] > 0)))
        cur = (a.in_use - nq) * self._page_bytes() \
            + nq * self._page_bytes_quant()
        self.kv_peak_bytes = max(self.kv_peak_bytes, cur)

    # page lifecycle wrappers: every allocation flows through here so quant
    # flags are reset on reuse and the kv-bytes peak is tracked in one place
    def alloc_pages(self, n: int) -> List[int]:
        ids = self._alloc.alloc(n)
        if self._quant_flags is not None and ids:
            self._quant_flags[np.asarray(ids, np.int64)] = 0
        self._note_kv()
        return ids

    def retain_pages(self, ids: Sequence[int]) -> None:
        self._alloc.retain(ids)

    def release_pages(self, ids: Sequence[int]) -> None:
        self._alloc.release(ids)

    def copy_pages(self, srcs: Sequence[int], dsts: Sequence[int]) -> None:
        """Copy-on-write privatization: batched device copy of fp pages
        (COW sources are live, never-quantized pages by construction)."""
        if not srcs:
            return
        s = jnp.asarray(srcs, jnp.int32)
        d = jnp.asarray(dsts, jnp.int32)
        for kk in ("k", "v"):
            self._pool[kk] = self._pool[kk].at[:, :, d].set(
                self._pool[kk][:, :, s])

    def _quantize_pages(self, pages: Sequence[int]) -> None:
        """Quantize-on-commit: symmetric per-(layer, kv-head, page) int8
        with scale = abs-max / 127, written to the shadow pool.  Only ever
        called for freshly tree-committed (frozen) pages; the fp copy stays
        authoritative until the flag flips, and flags are host state so the
        very next device step reads the quantized form."""
        if not pages:
            return
        n = 1                       # pow-2 pad (repeat id 0 — idempotent)
        while n < len(pages):
            n *= 2
        padded = list(pages) + [pages[0]] * (n - len(pages))
        pg = jnp.asarray(padded, jnp.int32)
        for kk, qk, sk in (("k", "kq", "kscale"), ("v", "vq", "vscale")):
            src = self._pool[kk][:, :, pg].astype(jnp.float32)
            amax = jnp.max(jnp.abs(src), axis=(3, 4))      # (ln, kv, n)
            scale = jnp.maximum(amax, 1e-8) / 127.0
            qv = jnp.clip(jnp.round(src / scale[..., None, None]),
                          -127, 127).astype(jnp.int8)
            self._pool[qk] = self._pool[qk].at[:, :, pg].set(qv)
            self._pool[sk] = self._pool[sk].at[:, :, pg].set(scale)
        self._quant_flags[np.asarray(pages, np.int64)] = 1
        self._note_kv()

    # ------------------------------ radix cache --------------------------------
    def radix_match(self, ids: Sequence[int], stats: GenStats,
                    limit: Optional[int] = None) -> Tuple[List[int], int]:
        """Deepest page-aligned prefix of `ids` resident in the radix tree.
        Returned pages are retained for the caller (release when done)."""
        if self._radix is None:
            return [], 0
        pages, n = self._radix.match(ids, limit=limit)
        if n:
            stats.prefix_hits += 1
            stats.radix_hit_tokens += n
        return pages, n

    def radix_insert(self, ids: Sequence[int], pages: Sequence[int]
                     ) -> List[int]:
        """Commit the full-page span of `ids` (backed by `pages`) to the
        radix tree; newly adopted pages are frozen and, in int8 mode,
        quantized on the spot."""
        if self._radix is None:
            return []
        nfull = len(ids) // self.page_size
        if nfull == 0:
            return []
        adopted = self._radix.insert(
            list(ids[:nfull * self.page_size]), list(pages[:nfull]))
        if adopted and self.kv_quant == "int8":
            self._quantize_pages(adopted)
        return adopted

    # -- warm-state snapshots (core/snapshot.py) -------------------------
    def export_radix_state(self) -> Optional[dict]:
        """Host-side payload of the radix prefix cache: every node's full
        root-to-node token path plus the fp KV of its own pages, in
        parent-before-child order so a restore can rebuild the tree with
        plain `radix_insert` calls.  int8 shadow pages are NOT exported —
        restore re-quantizes adopted pages from the fp data, yielding the
        identical quantized form.  None when nothing is resident."""
        if self._radix is None or self._pool is None:
            return None
        entries = []
        stack = [(self._radix._root, ())]
        order = []
        while stack:
            node, path = stack.pop()
            if node.key:
                order.append((node, path + tuple(node.key)))
            for c in node.children.values():
                stack.append((c, path + tuple(node.key)))
        # DFS pop order is not parent-first for siblings' subtrees; sort
        # by path length, which is: a parent's path is a strict prefix
        # (hence strictly shorter) than any descendant's
        order.sort(key=lambda t: len(t[1]))
        for node, path in order:
            pg = np.asarray(node.pages, np.int64)
            entries.append({
                "path": list(path),
                "k": np.asarray(self._pool["k"][:, :, pg]),
                "v": np.asarray(self._pool["v"][:, :, pg]),
            })
        if not entries:
            return None
        return {"page_size": self.page_size, "entries": entries}

    def restore_radix_state(self, payload: dict) -> int:
        """Rebuild the radix tree from an `export_radix_state` payload on
        a (typically fresh) engine: alloc pages, write the KV back, and
        commit each node with `radix_insert` (which re-freezes and, in
        int8 mode, re-quantizes the adopted pages).  Returns the number
        of pages restored; a payload from a different page-size geometry
        is ignored."""
        if not payload or int(payload.get("page_size", -1)) != self.page_size:
            return 0
        ps = self.page_size
        restored = 0
        pages_for_path: Dict[Tuple[int, ...], List[int]] = {(): []}
        for ent in payload.get("entries", []):
            path = tuple(int(t) for t in ent["path"])
            k_host, v_host = ent["k"], ent["v"]
            own_np = int(k_host.shape[2])
            parent_path = path[: len(path) - own_np * ps]
            parent_pages = pages_for_path.get(parent_path)
            if parent_pages is None or len(path) % ps:
                continue               # orphaned entry: skip defensively
            if not self._ensure_pool(own_np):
                break                  # pinned pool exhausted: partial warm
            own = self.alloc_pages(own_np)
            pg = jnp.asarray(own, jnp.int32)
            self._pool["k"] = self._pool["k"].at[:, :, pg].set(
                jnp.asarray(k_host, self._pool["k"].dtype))
            self._pool["v"] = self._pool["v"].at[:, :, pg].set(
                jnp.asarray(v_host, self._pool["v"].dtype))
            full_pages = list(parent_pages) + list(own)
            self.radix_insert(list(path), full_pages)
            # the tree now holds its own reference to the adopted pages;
            # drop ours so restored nodes are plain LRU-evictable leaves
            self.release_pages(own)
            pages_for_path[path] = full_pages
            restored += own_np
        self._note_kv()
        return restored

    def _dense_cache_bytes(self, cache: dict) -> int:
        return int(cache["k"].size * cache["k"].dtype.itemsize
                   + cache["v"].size * cache["v"].dtype.itemsize) \
            if "k" in cache else 0

    def _ensure_pool(self, need_pages: int) -> bool:
        """Make `need_pages` allocatable: create the pool lazily, then free
        pages by dropping LRU prefix residencies, then grow the device
        arrays — unless the operator pinned `page_pool_pages`, in which
        case the pool is a hard memory bound and False is returned when the
        demand cannot fit (callers wait for slot frees or raise)."""
        quant = self.kv_quant == "int8"
        if self._pool is None:
            n = self.page_pool_pages or \
                max(2 * need_pages, 2 * self.num_table_blocks)
            n = max(n, 1)
            if self.page_pool_pages is None:
                n = max(n, need_pages)
            full = MDL.init_paged_cache(self.cfg, n, self.page_size,
                                        quant=quant)
            keys = ("k", "v") + (("kq", "vq", "kscale", "vscale")
                                 if quant else ())
            self._pool = {kk: full[kk] for kk in keys}
            self._alloc = PageAllocator(n)
            if quant:
                self._quant_flags = np.zeros(n, np.int8)
            if self.prefix_cache_mode == "radix":
                self._radix = RadixPrefixCache(self._alloc, self.page_size)
            return self._alloc.free_pages >= need_pages
        a = self._alloc
        if a.free_pages >= need_pages:
            return True
        for key in list(self._prefix_kv):      # LRU-first residency drop
            if a.free_pages >= need_pages:
                break
            ent = self._prefix_kv[key]
            # skip entries whose pages an in-flight run still retains:
            # releasing the memo's reference would free nothing while
            # permanently discarding the zero-copy residency
            if ent.pages is not None and \
                    all(a.refs(p) == 1 for p in ent.pages):
                a.release(ent.pages)
                ent.pages = None
        if a.free_pages < need_pages and self._radix is not None:
            # radix eviction: LRU leaf nodes with no outside readers
            self._radix.evict(need_pages - a.free_pages)
        if a.free_pages >= need_pages:
            return True
        if self.page_pool_pages is not None:
            return False                       # pinned pool: hard bound
        extra = max(need_pages - a.free_pages, a.num_pages // 2)
        for kk in self._pool:
            pool = self._pool[kk]
            # page axis of the folded (ln, KV, P, ...) layout
            pad = jnp.zeros(pool.shape[:2] + (extra,) + pool.shape[3:],
                            pool.dtype)
            self._pool[kk] = jnp.concatenate([pool, pad], axis=2)
        if self._quant_flags is not None:
            self._quant_flags = np.concatenate(
                [self._quant_flags, np.zeros(extra, np.int8)])
        a.grow(extra)
        return True

    def _ssm_state(self, batch: int) -> Dict[str, jax.Array]:
        """Per-row SSM state for paged runs (shapes owned by
        model.paged_cache_specs — single source of truth)."""
        if not self.cfg.has_ssm:
            return {}
        specs = MDL.paged_cache_specs(self.cfg, 1, self.page_size, batch)
        return {k: jnp.zeros(specs[k].shape, specs[k].dtype)
                for k in ("conv", "h")}

    # ----------------------------- prefix memo --------------------------------
    def _prefix_memo_get(self, key) -> Optional[_PrefixEntry]:
        ent = self._prefix_kv.get(key)
        if ent is not None:
            del self._prefix_kv[key]           # touch-on-get: move to MRU end
            self._prefix_kv[key] = ent
        return ent

    def _prefix_memo_put(self, key, ent: _PrefixEntry) -> None:
        while len(self._prefix_kv) >= max(1, self.prefix_memo_entries):
            k0 = next(iter(self._prefix_kv))
            old = self._prefix_kv.pop(k0)
            if old.pages is not None and self._alloc is not None:
                self._alloc.release(old.pages)   # refcounted: in-flight
                old.pages = None                 # users keep them alive
        self._prefix_kv[key] = ent

    def _prefix_entry_for(self, prefix_text: str, stats: GenStats
                          ) -> _PrefixEntry:
        """Memo lookup; on miss the prefix is prefilled ONCE (batch=1) and
        its KV kept on host."""
        ids = TOK.encode(prefix_text)
        key = (prefix_text, self.max_len)
        ent = self._prefix_memo_get(key)
        if ent is None:
            _, cache1, _, _ = self._prefill([ids])
            ent = _PrefixEntry(
                host_kv=jax.tree.map(lambda x: np.asarray(x), cache1),
                off=int(np.asarray(cache1["idx"])), real_len=len(ids))
            self._prefix_memo_put(key, ent)
            stats.prefill_tokens += len(ids)
        else:
            stats.prefix_hits += 1
        return ent

    # ----------------------------- shared prefix ------------------------------
    def prefix_cache_for(self, prefix_text: str, batch: int):
        """Dense layout: prefill the shared instruction prefix ONCE
        (batch=1), memoize, broadcast to the row batch. Returns
        (cache, offset, real_len, new_prefill_tokens, hit)."""
        ids = TOK.encode(prefix_text)
        probe = GenStats()
        ent = self._prefix_entry_for(prefix_text, probe)
        hit = probe.prefix_hits > 0
        cache1, off, real_len = ent.host_kv, ent.off, ent.real_len

        def rep(x):
            x = jnp.asarray(x)
            if x.ndim >= 2 and x.shape[1] == 1:     # (L, 1, ...) layer caches
                return jnp.repeat(x, batch, axis=1)
            if x.ndim >= 1 and x.shape[0] == 1:     # (1, lc) slot_pos
                return jnp.repeat(x, batch, axis=0)
            return x
        cache = {k: (rep(v) if k not in ("idx",) else v)
                 for k, v in cache1.items()}
        return cache, off, real_len, (0 if hit else len(ids)), hit

    def prefix_pages_for(self, prefix_text: str, stats: GenStats
                         ) -> Tuple[List[int], int, List[int]]:
        """Paged layout: resolve the shared prefix to pool pages.  Only
        FULL pages are shared (every referencing row reads them in place);
        the sub-page tail rides with each row's suffix so rows never write
        into a shared page.  Returns (page_ids, shared_token_count,
        tail_token_ids)."""
        ids = TOK.encode(prefix_text)
        ps = self.page_size
        n_share = (len(ids) // ps) * ps
        if n_share == 0:
            return [], 0, ids
        npre = n_share // ps
        peek = self._prefix_kv.get((prefix_text, self.max_len))
        if (peek is None or peek.pages is None) and not self._ensure_pool(npre):
            # pinned pool too small to ever share: bail BEFORE the memo so
            # no batch=1 prefill is wasted and no phantom prefix_hits are
            # counted for reuse that cannot physically happen
            return [], 0, ids
        ent = self._prefix_entry_for(prefix_text, stats)
        if ent.pages is None:
            pages = self.alloc_pages(npre)
            cfg = self.cfg
            k1 = jnp.asarray(ent.host_kv["k"])        # (ln, 1, lc, kv, hd)
            v1 = jnp.asarray(ent.host_kv["v"])
            # prefill wrote the bucketed sequence at slots 0..off-1 with the
            # left padding first: token t lives at slot (off - len) + t
            pad = ent.off - len(ids)
            dp = MDL.padded_head_dim(cfg.head_dim)
            shp = (cfg.num_layers, npre, ps, cfg.num_kv_heads, cfg.head_dim)

            def fold(src):
                # (ln, npre, ps, kv, hd) → (ln, kv, npre, ps, Dp)
                src = src.reshape(shp).transpose(0, 3, 1, 2, 4)
                return jnp.pad(src, [(0, 0)] * 4
                               + [(0, dp - cfg.head_dim)])
            ksrc = fold(k1[:, 0, pad:pad + n_share])
            vsrc = fold(v1[:, 0, pad:pad + n_share])
            pg = jnp.asarray(pages, jnp.int32)
            self._pool["k"] = self._pool["k"].at[:, :, pg].set(
                ksrc.astype(self._pool["k"].dtype))
            self._pool["v"] = self._pool["v"].at[:, :, pg].set(
                vsrc.astype(self._pool["v"].dtype))
            ent.pages = pages
        return list(ent.pages), n_share, ids[n_share:]

    # ----------------------------- paged prefill ------------------------------
    def paged_prefill(self, token_lists: List[List[int]], table_rows,
                      prefix_pages: Sequence[int], prefix_len: int, *,
                      extra: Optional[dict] = None):
        """Prefill suffixes straight into their block-table pages, reading
        shared prefix pages in place (no per-row replication).  table_rows:
        np.ndarray (B, NB) page ids.  Returns (logits, lens, prefill_token
        count, extra_out) — extra carries per-row SSM state for hybrid
        models."""
        B = len(token_lists)
        L = _bucket(max(len(t) for t in token_lists))
        toks = np.full((B, L), TOK.PAD_ID, np.int32)
        pos = np.zeros((B, L), np.int32)
        for i, t in enumerate(token_lists):
            pad = L - len(t)
            toks[i, pad:] = t
            pos[i] = np.arange(L) - pad + prefix_len
            pos[i, :pad] = -1
        npre = len(prefix_pages)
        cache = dict(self._pool, idx=jnp.int32(0))
        if extra:
            cache.update(extra)
        key = ("paged", B, L, table_rows.shape[1], npre)
        if key not in self._prefill_cache:
            cfg = self.cfg

            # block table / prefix table / quant flags ride OUTSIDE the
            # donated cache: they are rebuilt host-side every call,
            # donation buys nothing
            def paged_prefill_step(params, tokens, positions, cache, bt,
                                   ptab, plen, qf):
                cache = dict(cache, block_tables=bt, prefix_table=ptab,
                             prefix_len=plen)
                if qf is not None:
                    cache["quant_flags"] = qf
                logits, cache = MDL.forward(
                    cfg, params, {"tokens": tokens, "positions": positions},
                    mode="prefill", cache=cache, remat=False, last_only=True)
                return logits[:, -1], cache

            self._prefill_cache[key] = jax.jit(paged_prefill_step,
                                               donate_argnums=(3,))
        qf = None if self._quant_flags is None \
            else jnp.asarray(self._quant_flags)
        logits, out = self._prefill_cache[key](
            self.params, jnp.asarray(toks), jnp.asarray(pos), cache,
            jnp.asarray(np.ascontiguousarray(table_rows)),
            jnp.asarray(np.asarray(prefix_pages, np.int32).reshape(npre)),
            jnp.int32(prefix_len), qf)
        for kk in self._pool:
            self._pool[kk] = out[kk]
        extra_out = {k: out[k] for k in ("conv", "h") if k in out}
        lens = np.array([prefix_len + len(t) for t in token_lists], np.int32)
        return np.asarray(logits, np.float32), lens, B * L, extra_out

    def paged_decode(self, toks, positions, table, num_blocks: int, *,
                     extra: Optional[dict] = None):
        """One lock-step decode tick against the page pool.  `table` is the
        host block table (B, NB_full); only its first `num_blocks` columns
        (the batch's actual fill, bucketed by the caller) reach the device,
        so attention work scales with occupancy, not max_len."""
        cache = dict(self._pool, idx=jnp.int32(0))
        if extra:
            cache.update(extra)
        dec = self._decode_fn_paged(num_blocks)
        qf = None if self._quant_flags is None \
            else jnp.asarray(self._quant_flags)
        lg, out = dec(self.params, jnp.asarray(toks[:, None]),
                      jnp.asarray(positions[:, None]), cache,
                      jnp.asarray(np.ascontiguousarray(table[:, :num_blocks])),
                      qf)
        for kk in self._pool:
            self._pool[kk] = out[kk]
        extra_out = {k: out[k] for k in ("conv", "h") if k in out}
        return np.asarray(lg, np.float32), extra_out

    def active_blocks(self, fills) -> int:
        """Bucketed block count covering the given fill levels (pow-2 so
        decode-step jit caches stay few)."""
        need = max(1, max((int(f) // self.page_size) + 1 for f in fills))
        nb = 1
        while nb < need:
            nb *= 2
        return min(nb, self.num_table_blocks)

    # ------------------------------- generate ---------------------------------
    @staticmethod
    def _consume_tokens(toks, gs, states, out_tokens, done,
                        stats: GenStats) -> None:
        """Apply one sampled token per not-yet-done row: grammar advance,
        EOS, completion + per-tick stats. Shared by the dense and paged
        generate loops so their semantics cannot drift."""
        for i in range(len(done)):
            if done[i]:
                continue
            t = int(toks[i])
            if gs[i] is not None:
                states[i] = gs[i].advance(states[i], t)
                if t != TOK.EOS_ID:
                    out_tokens[i].append(t)
                if gs[i].done(states[i]):
                    done[i] = True
            else:
                if t == TOK.EOS_ID:
                    done[i] = True
                else:
                    out_tokens[i].append(t)
        stats.decode_steps += 1
        stats.output_tokens += int((~done).sum())

    def generate(self, prompts: Sequence[str], *,
                 grammar: Optional[JsonGrammar] = None,
                 grammars: Optional[List[JsonGrammar]] = None,
                 max_new_tokens: int = 256, temperature: float = 0.0,
                 shared_prefix: str = "") -> GenResult:
        """Generate for a batch of prompts. If shared_prefix is given it is
        prefilled once and KV-reused across rows (prompts are then the
        suffixes). Grammar-constrained when grammar(s) provided."""
        t0 = time.time()
        stats = GenStats(calls=1)
        B = len(prompts)
        gs = grammars or ([grammar] * B if grammar else [None] * B)
        states = [g.init_state() if g else None for g in gs]

        if self.kv_layout == "paged":
            texts = self._generate_paged(prompts, gs, states, max_new_tokens,
                                         temperature, shared_prefix, stats)
            stats.wall_s = time.time() - t0
            self.total.add(stats)
            return GenResult(texts, stats)

        offset = 0
        pos_offset = None
        cache = None
        if shared_prefix:
            cache, offset, pos_offset, new_prefix_toks, hit = \
                self.prefix_cache_for(shared_prefix, B)
            stats.prefill_tokens += new_prefix_toks
            stats.prefix_hits += int(hit)
            stats.input_tokens += TOK.count_tokens(shared_prefix)

        token_lists = [TOK.encode(p, bos=not shared_prefix) for p in prompts]
        stats.input_tokens += sum(len(t) for t in token_lists)
        logits, cache, lens, pre = self._prefill(
            token_lists, offset=offset, pos_offset=pos_offset,
            cache=cache, row_idx_mode=True)
        stats.prefill_tokens += pre
        stats.kv_bytes = self._dense_cache_bytes(cache)

        decode = self._decode_fn()
        out_tokens: List[List[int]] = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        positions = lens.copy()

        for step in range(max_new_tokens):
            toks = self._sample(logits, gs, states, temperature)
            self._consume_tokens(toks, gs, states, out_tokens, done, stats)
            if done.all():
                break
            lg, cache = decode(self.params, jnp.asarray(toks[:, None]),
                               jnp.asarray(positions[:, None]), cache)
            logits = np.asarray(lg, np.float32)
            positions += 1

        stats.wall_s = time.time() - t0
        self.total.add(stats)
        return GenResult([TOK.decode(t) for t in out_tokens], stats)

    def _generate_paged(self, prompts, gs, states, max_new_tokens,
                        temperature, shared_prefix, stats: GenStats
                        ) -> List[str]:
        """Paged-layout generate: per-row block tables over the global page
        pool.  prefix_cache_mode="exact": a shared prefix resolves through
        the exact-string memo and contributes the SAME page ids to every
        row's table.  "radix": the batch-common token prefix is matched
        against the radix tree (discovering partial overlap with ANY prior
        prompt), suffix prefill starts at the deepest matched page, and the
        rows' full prompt pages are committed back to the tree."""
        B = len(prompts)
        ps = self.page_size
        NBf = self.num_table_blocks
        cap = NBf * ps

        pages_pre: List[int] = []
        n_share = 0
        if self.prefix_cache_mode == "radix":
            self._ensure_pool(0)               # materialize pool + tree
            token_lists = [TOK.encode(shared_prefix + p) if shared_prefix
                           else TOK.encode(p) for p in prompts]
            if shared_prefix:
                stats.input_tokens += TOK.count_tokens(shared_prefix)
                npre_tok = len(TOK.encode(shared_prefix))
            else:
                npre_tok = 0
            stats.input_tokens += sum(len(t) - npre_tok for t in token_lists)
            # batch-common token prefix, leaving >= 1 suffix token per row
            common = list(token_lists[0])
            for t in token_lists[1:]:
                n = 0
                while n < len(common) and n < len(t) and common[n] == t[n]:
                    n += 1
                common = common[:n]
            aligned = min(len(common), min(len(t) for t in token_lists) - 1)
            aligned = (aligned // ps) * ps
            pages_pre, n_share = self.radix_match(common, stats,
                                                  limit=aligned)
            if B >= 2 and n_share < aligned and \
                    self._ensure_pool((aligned - n_share) // ps):
                # seed prefill: materialize the still-missing span of the
                # batch-common prefix ONCE (batch=1) and commit it, so the
                # per-row prefills below all start at `aligned`
                seed = self.alloc_pages((aligned - n_share) // ps)
                st = np.full((1, NBf), -1, np.int32)
                st[0, :n_share // ps] = pages_pre
                st[0, n_share // ps:aligned // ps] = seed
                _, _, pre, _ = self.paged_prefill(
                    [common[n_share:aligned]], st, pages_pre, n_share,
                    extra=self._ssm_state(1))
                stats.prefill_tokens += pre
                self.radix_insert(common[:aligned],
                                  list(st[0, :aligned // ps]))
                pages_pre = pages_pre + seed   # run holds one ref on each
                n_share = aligned
            token_lists = [t[n_share:] for t in token_lists]
        elif shared_prefix:
            pages_pre, n_share, tail = self.prefix_pages_for(
                shared_prefix, stats)
            stats.input_tokens += TOK.count_tokens(shared_prefix)
            token_lists = [tail + TOK.encode(p, bos=False) for p in prompts]
            stats.input_tokens += sum(len(t) - len(tail)
                                      for t in token_lists)
            if self._alloc is not None and pages_pre:
                self.retain_pages(pages_pre)   # survive memo eviction
        else:
            token_lists = [TOK.encode(p) for p in prompts]
            stats.input_tokens += sum(len(t) for t in token_lists)

        npre = len(pages_pre)
        table = np.full((B, NBf), -1, np.int32)
        if npre:
            table[:, :npre] = pages_pre        # shared: same ids every row
        owned: List[List[int]] = []
        try:
            need_each = [max(0, -(-min(n_share + len(t) + max_new_tokens,
                                       cap) // ps) - npre)
                         for t in token_lists]
            if not self._ensure_pool(sum(need_each)):
                raise RuntimeError(
                    f"page pool ({self.page_pool_pages} pages) too small "
                    f"for batch of {B} rows")
            for i, need in enumerate(need_each):
                ids = self.alloc_pages(need)
                owned.append(ids)
                table[i, npre:npre + need] = ids

            extra = self._ssm_state(B)
            logits, lens, pre, extra = self.paged_prefill(
                token_lists, table, pages_pre, n_share, extra=extra)
            stats.prefill_tokens += pre
            if self.prefix_cache_mode == "radix":
                # commit every row's full-page prompt span (clamped to the
                # pages actually allocated when the row is capacity-bound);
                # identical or overlapping rows dedup inside the tree
                for i, t in enumerate(token_lists):
                    nfull = min((n_share + len(t)) // ps,
                                npre + need_each[i])
                    if nfull > npre:
                        self.radix_insert((common[:n_share] + t)[:nfull * ps],
                                          list(table[i, :nfull]))

            out_tokens: List[List[int]] = [[] for _ in range(B)]
            done = np.zeros(B, bool)
            positions = lens.copy()

            for step in range(max_new_tokens):
                toks = self._sample(logits, gs, states, temperature)
                self._consume_tokens(toks, gs, states, out_tokens, done,
                                     stats)
                if done.all():
                    break
                nb = self.active_blocks(positions[~done])
                logits, extra = self.paged_decode(toks, positions, table, nb,
                                                  extra=extra)
                positions += 1
        finally:
            # errors must not leak refcounts: a pinned pool would shrink
            # permanently
            for ids in owned:
                self.release_pages(ids)
            if pages_pre:
                self.release_pages(pages_pre)
        self._note_kv()
        stats.kv_bytes = self.kv_peak_bytes
        return [TOK.decode(t) for t in out_tokens]

    # ------------------------------- sampling ---------------------------------
    def _sample(self, logits: np.ndarray, gs, states, temperature: float
                ) -> np.ndarray:
        B, V = logits.shape
        mask = np.ones((B, V), np.int8)
        for i, (g, st) in enumerate(zip(gs, states)):
            if g is not None:
                m = g.mask(st)
                mask[i, :] = 0
                mask[i, :len(m)] = m
        noise = None
        if temperature > 0:
            u = self._rng.uniform(1e-9, 1.0, size=(B, V))
            noise = -np.log(-np.log(u))
        if self.use_pallas_sampler:
            from repro.kernels import ops as KOPS
            return np.asarray(KOPS.constrained_sample(
                jnp.asarray(logits), jnp.asarray(mask),
                None if noise is None else jnp.asarray(noise),
                temperature=max(temperature, 1e-6) if temperature > 0 else 1.0,
                block_v=256))
        x = logits / (temperature if temperature > 0 else 1.0)
        if noise is not None:
            x = x + noise
        x = np.where(mask != 0, x, NEG_INF)
        return np.argmax(x, axis=-1).astype(np.int32)

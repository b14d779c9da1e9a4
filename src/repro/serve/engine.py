"""Slot-based continuous-batching serving engine.

The engine owns a fixed grid of ``slots`` batch rows over one KV cache and
two jitted entry points:

* ``_prefill`` — full-sequence forward of ONE shape-bucketed prompt
  ((1, bucket_len); compiled once per bucket), returning the request's
  cache rows and the logits at its true last token (``last=`` gather —
  right-pad tokens are causally inert);
* ``_decode`` — one token for EVERY slot ((slots, 1)) with per-row
  positions; compiled exactly once.

Slot lifecycle: a request admitted from the scheduler is prefilled and its
cache rows are scattered into a free slot (``pos`` entries past the true
prompt length forced to −1 so pad K/V never match); the slot then rides
every decode dispatch until its token budget is spent, at which point its
device-side output row is transferred (once — no per-token host sync) and
the slot is refilled mid-stream from the queue.  Because every per-row
computation in the model is independent of the other rows, a request's
tokens are bitwise-identical no matter which slot it lands in or what else
is in flight (MoE is the one exception: expert capacity couples rows, so
under-filled tail batches can drop tokens differently than full ones).

Sampling is per-slot: each request owns a PRNG stream derived from its
``seed`` only (split once at admission, then once per decode step), so
temperature>0 outputs are also independent of batch composition.

``generate`` is kept as the lockstep-compatible wrapper: one slot per
prompt row, exact-length buckets, per-row seeds ``seed + i``.

**Paged mode** (``paged=True``): slots no longer own dense ``(max_len)``
KV rows — the attention cache is a shared pool of fixed-size pages
(:mod:`repro.serve.paged`), each slot holds a page table, and the decode
page walk happens inside one Pallas gather kernel per layer
(:mod:`repro.kernels.paged_attn`).  Capacity becomes O(live tokens)
instead of O(slots × max_len).  The two jitted entry points are
unchanged in kind: ``_prefill`` gains an optional prior-prefix K/V input
(warm shared-prefix admission skips recomputing cached pages) and
``_decode`` takes the page table as a plain device array, so admissions
and retirements never recompile anything.  Prefix sharing is refcounted
and read-only: only *full* prompt pages strictly before the first decode
-write position are shared, so a shared page is never written and
copy-on-write is structural (a divergent prompt stops matching the
fingerprint chain at its first divergent block and recomputes its tail
into pages it owns).  When the pool runs dry, admission *queues* —
``Scheduler.restore`` puts the batch back — rather than corrupting live
pages.

**Mesh-sharded serving** (``shards=`` / ``mesh=``): the slot grid and the
paged page pool split into per-shard partitions — slot ``s`` of ``nslots``
lives on shard ``s·shards // nslots``, draws pages only from that shard's
disjoint pool id range (its own scrap page included), and hits only that
shard's prefix index, so every page a slot touches is local to its shard.
Admission stays equalized *and* balanced across shards: the scheduler's
shard-aware ``take`` hands the heaviest picks to the lightest-loaded
shards.  Capacity scales with the mesh (``paged_capacity_slots`` sums the
per-shard pools) while per-row independence keeps each request's tokens
bitwise-identical to a single-shard serve.  With ``mesh=`` the persistent
pool K/V parks laid out over the mesh axis between ``serve()`` calls.

**EOS early exit**: requests carrying ``eos_token`` keep a device-side
done flag + truncation index next to the ``(slots, max_new)`` output
buffer; flags are polled every ``eos_poll`` decode steps (one tiny
transfer, no per-token host sync) and finished slots retire early,
freeing their pages mid-stream.  The final readback stays ONE transfer
per request (output row ++ truncation index, fetched together).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from .paged import PagePool, PrefixCache, ShardedPagePool, prefix_chain
from .scheduler import Scheduler, bucket_length

__all__ = ["GenRequest", "EngineStats", "Engine"]


@dataclasses.dataclass
class GenRequest:
    """One generation request.  ``seed`` alone determines the sampling
    stream (slot- and batch-independent); give concurrent requests distinct
    seeds for independent draws."""

    tokens: np.ndarray  # (S0,) int32 prompt
    max_new_tokens: int = 32
    temperature: float = 0.0
    seed: int = 0
    deadline: float | None = None
    # stop early when this token is sampled (output truncates at and
    # includes it); None keeps the fixed max_new_tokens budget
    eos_token: int | None = None


@dataclasses.dataclass
class EngineStats:
    prefill_dispatches: int = 0
    decode_dispatches: int = 0
    generated_tokens: int = 0
    padding_frac: float = 0.0
    # ("prefill", request_index) / ("decode", active_slot_count) in issue
    # order — tests assert prefill insertion happens mid-decode from this
    events: list = dataclasses.field(default_factory=list)
    sched: object | None = None  # SchedulerStats of the last serve() call
    # paged mode
    prefix_hits: int = 0        # admissions that reused >= 1 cached page
    prefix_hit_tokens: int = 0  # prompt tokens whose prefill was skipped
    page_frac: float = 0.0      # partial-last-page fragmentation (sched)
    peak_active: int = 0        # max concurrently-occupied slots
    pool_peak_pages: int = 0    # engine-lifetime peak pool occupancy
    # mesh-sharded serving: peak concurrent live cost per shard (the
    # balance the shard-aware scheduler maintains); [] for single-shard
    shard_peak_cost: list = dataclasses.field(default_factory=list)
    # EOS early exit
    early_exits: int = 0        # slots retired before their token budget

    @property
    def tokens_per_dispatch(self) -> float:
        return self.generated_tokens / max(self.decode_dispatches + self.prefill_dispatches, 1)


class Engine:
    def __init__(
        self, params, cfg: ModelConfig, *, max_len: int = 512, slots: int = 4,
        bucket: int = 1, jit_kwargs: dict | None = None,
        paged: bool = False, page_size: int | None = None,
        pool_pages: int | None = None, prefix_reuse: bool = True,
        eos_poll: int = 4, shards: int = 1, mesh=None, mesh_axis: str = "model",
    ):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.bucket = bucket
        self.paged = paged
        self.eos_poll = max(int(eos_poll), 1)
        # mesh-sharded serving: the slot grid and (paged) KV pool split into
        # `shards` disjoint partitions — slot s of nslots lives on shard
        # s·shards // nslots, every page it touches comes from that shard's
        # pool range, and admission balances live cost per shard (the
        # scheduler's shard-aware take).  Per-row model computation is
        # independent of batch composition, so each request's tokens stay
        # bitwise-identical to a single-shard serve.  Passing ``mesh=`` sets
        # shards from the mesh axis and parks the persistent page pool
        # arrays over it between serve() calls.
        if mesh is not None:
            shards = mesh.shape[mesh_axis]
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > slots:
            raise ValueError(
                f"shards ({shards}) cannot exceed slots ({slots}): a shard "
                "with no slot would idle its whole pool partition"
            )
        self.shards = int(shards)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.stats = EngineStats()
        kw = jit_kwargs or {}

        if paged:
            if cfg.sliding_window is not None:
                raise ValueError(
                    "paged KV cache does not support sliding-window archs "
                    "(the ring layout is position-modular, pages are not)"
                )
            if cfg.family == "ssm":
                raise ValueError(
                    "pure-SSM archs have no attention KV cache to page"
                )
            page_size = int(page_size or self._default_page_size(max_len))
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if prefix_reuse and cfg.family == "dense" and bucket > 1 and page_size % bucket != 0:
                raise ValueError(
                    f"prefix sharing needs page_size ({page_size}) to be a "
                    f"multiple of bucket ({bucket}) so a shared prefix plus "
                    "a bucketed tail reproduces the cold bucket length; pass "
                    "prefix_reuse=False to page without sharing"
                )
            self.page_size = page_size
            self.max_len = -(-max_len // page_size) * page_size
            self.pages_per_slot = self.max_len // page_size
            if self.shards == 1:
                self.pool = PagePool(
                    pool_pages or slots * self.pages_per_slot + 1, page_size
                )
                pools = [self.pool]
            else:
                # per-shard pools over disjoint global id ranges; pool_pages
                # (when given) is the TOTAL budget, split evenly
                per = (
                    -(-pool_pages // self.shards) if pool_pages
                    else -(-slots // self.shards) * self.pages_per_slot + 1
                )
                self.pool = ShardedPagePool(self.shards, per, page_size)
                pools = self.pool.pools
            # prefix K/V is only bitwise-reproducible for plain sequence
            # positions with no prompt offset — dense family exactly.
            # Sharded: one index per shard (hit pages must be local to the
            # admitted slot's shard — pages are never borrowed across).
            reuse = prefix_reuse and cfg.family == "dense"
            self.prefix_caches = [PrefixCache(p) if reuse else None for p in pools]
            self.prefix_cache = self.prefix_caches[0]  # single-shard alias
            self._pages = None  # persistent {"k_pages","v_pages"} device arrays

            def _prefill(params, batch, last, prior):
                return lm.prefill(params, batch, cfg, last=last, prior=prior, raw_kv=True)

            def _decode(params, caches, tokens, pos, page_table):
                return lm.decode_step(
                    params, caches, tokens, pos, cfg, page_table=page_table
                )
        else:
            self.max_len = max_len
            self.pool = None
            self.prefix_cache = None
            self.prefix_caches = [None] * self.shards

            def _prefill(params, batch, last):
                return lm.prefill(params, batch, cfg, cache_len=self.max_len, last=last)

            def _decode(params, caches, tokens, pos):
                return lm.decode_step(params, caches, tokens, pos, cfg)

        self._prefill = jax.jit(_prefill, **kw)
        self._decode = jax.jit(_decode, donate_argnums=(1,), **kw)

    def _default_page_size(self, max_len: int) -> int:
        """Autotuned page size when `scripts/autotune.py` has measured a
        transferable sweep (op="decode", structure="paged_kv"); 16 outside
        measured territory."""
        from repro.solvers.cache import get_cache
        from repro.solvers.problem import Problem

        best = get_cache().best_page_size(
            Problem(
                op="decode", structure="paged_kv", n=max_len,
                dtype=jnp.dtype(self.cfg.dtype).name,
            )
        )
        if best:  # None: no transferable measurement for this shape
            return int(best)
        return 16

    def paged_capacity_slots(self, pages_per_request: int | None = None) -> int:
        """How many concurrent slots the pool can back if every request
        needs ``pages_per_request`` pages (worst case: a full slot).
        Sharded pools sum per-shard capacity, so capacity scales with the
        mesh: each added shard brings its own page partition."""
        per = max(pages_per_request or self.pages_per_slot, 1)
        if self.shards > 1:
            # pages never cross shards: count whole requests per shard
            return sum(p.capacity // per for p in self.pool.pools)
        return max(self.pool.capacity // per, 0)

    # ------------------------------------------------------------------
    # shard layout helpers
    # ------------------------------------------------------------------
    def _slot_shard(self, slot: int, nslots: int) -> int:
        """Contiguous slot→shard partition: slot s of nslots lives on shard
        ``s·shards // nslots`` (block layout — what a PartitionSpec over the
        slot axis would place per device)."""
        return min(slot * self.shards // max(nslots, 1), self.shards - 1)

    def _scrap_id(self, slot: int, nslots: int) -> int:
        """The scrap page id for ``slot``'s shard (0 when single-shard)."""
        if self.shards == 1:
            return 0
        return self.pool.scrap(self._slot_shard(slot, nslots))

    def _alloc_pages(self, n: int, shard: int) -> list[int] | None:
        if self.shards == 1:
            return self.pool.alloc(n)
        return self.pool.alloc(n, shard)

    # ------------------------------------------------------------------
    # paged-cache helpers
    # ------------------------------------------------------------------
    def _request_pages(self, s0: int, lb: int, max_new: int) -> int:
        """Pages a request occupies end-to-end: the padded prefill width or
        the final sequence length, whichever rounds to more pages."""
        off = self._prompt_offset
        return -(-max(lb + off, s0 + off + max_new) // self.page_size)

    def _paged_caches(self, nslots: int, enc_len: int):
        """Fresh per-serve cache pytree over the persistent page pool: the
        K/V pool arrays survive across serve() calls (prefix-cache hits read
        pages written by earlier calls); per-slot parts (SSM state, cross
        K/V) are rebuilt for the current slot count."""
        caches = lm.init_paged_caches(
            self.cfg, nslots, self.pool.num_pages, self.page_size, enc_len=enc_len
        )
        if self._pages is not None:
            pages = dict(self._pages)
            if self.mesh is not None:
                # The pool parks laid out over the mesh between serve()
                # calls; canonicalize placement for the jitted dispatches
                # (the same stance as repro.kernels.spike) so the
                # bitwise-per-request contract holds against a
                # single-device serve.
                pages = jax.device_put(pages, jax.devices()[0])
            caches["attn"] = pages
        return caches

    def _gather_prior(self, caches, pages: list[int]):
        """Assemble the prior-prefix K/V (L, 1, Sp, KV, Dh) for a warm
        prefill from the hit pool pages (read-only gather)."""
        idx = jnp.asarray(pages, jnp.int32)
        kp = caches["attn"]["k_pages"]  # (L, NP, pg, KV, Dh)
        nl, _, pg, kv, dh = kp.shape

        def sel(pool):
            return pool[:, idx].reshape(nl, 1, len(pages) * pg, kv, dh)

        return {"k": sel(kp), "v": sel(caches["attn"]["v_pages"])}

    def _scatter_pages(self, caches, raw, pages: list[int]):
        """Write fresh prefill K/V ({"k","v"}: (L, 1, S, KV, Dh)) into pool
        ``pages`` (page j of the suffix → pages[j]).  Pad-position K/V past
        the true prompt is scattered too but never read: decode overwrites
        position ``cur`` before attending with length ``cur + 1``."""
        if not pages:
            return caches
        idx = jnp.asarray(pages, jnp.int32)
        pg = self.page_size

        def put(pool, fresh):
            nl, _, s, kv, dh = fresh.shape
            pad = len(pages) * pg - s
            if pad:
                fresh = jnp.pad(fresh, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            blocks = fresh.reshape(nl, len(pages), pg, kv, dh).astype(pool.dtype)
            return pool.at[:, idx].set(blocks)

        caches["attn"] = {
            "k_pages": put(caches["attn"]["k_pages"], raw["k"]),
            "v_pages": put(caches["attn"]["v_pages"], raw["v"]),
        }
        return caches

    # ------------------------------------------------------------------
    # request-shaping helpers
    # ------------------------------------------------------------------
    def _model_batch(self, tokens):
        cfg = self.cfg
        b, s = tokens.shape
        if cfg.family == "vlm":
            key = jax.random.PRNGKey(0)
            prefix = jax.random.normal(key, (b, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
            return {"tokens": jnp.asarray(tokens), "prefix_embeds": prefix.astype(jnp.dtype(cfg.dtype))}
        if cfg.family == "encdec":
            key = jax.random.PRNGKey(0)
            frames = jax.random.normal(key, (b, max(s // 4, 1), cfg.d_model), jnp.float32)
            return {"tokens": jnp.asarray(tokens), "frames": frames.astype(jnp.dtype(cfg.dtype))}
        return {"tokens": jnp.asarray(tokens)}

    @property
    def _prompt_offset(self) -> int:
        return self.cfg.num_prefix_embeds if self.cfg.family == "vlm" else 0

    def _bucket_len(self, s0: int, fixed: int | None) -> int:
        lb = fixed if fixed is not None else bucket_length(s0, self.bucket)
        w = self.cfg.sliding_window
        if w is not None and lb > w:
            # The prefill ring keeps only the last `w` *sequence* positions,
            # so pad tokens past the window would evict real prompt K/V
            # before _insert_slot can mask them — pad only while the whole
            # padded prompt still fits in the ring, else prefill exact.
            return s0
        return lb

    # ------------------------------------------------------------------
    # continuous-batching serve loop
    # ------------------------------------------------------------------
    def serve(
        self, requests, *, slots: int | None = None, equalize: bool = True,
    ) -> list[np.ndarray]:
        """Serve ``requests`` (GenRequests) to completion; returns, per
        request (input order), the (S0_i + max_new_i,) int32 token array."""
        reqs = list(requests)
        if not reqs:
            return []
        nslots = min(slots or self.slots, len(reqs))
        offset = self._prompt_offset
        # encdec cross-attention caches are sized by the encoder length,
        # which tracks the padded prompt length — pin ONE bucket for the
        # whole call so every slot's cross cache rows agree.
        fixed_bucket = None
        if self.cfg.family == "encdec":
            fixed_bucket = max(bucket_length(len(r.tokens), self.bucket) for r in reqs)
        for r in reqs:
            if r.max_new_tokens < 1:
                raise ValueError(
                    f"max_new_tokens must be >= 1, got {r.max_new_tokens} "
                    "(the first token comes from prefill; a slot holding a "
                    "zero-budget request would never retire)"
                )
            lb = self._bucket_len(len(r.tokens), fixed_bucket)
            assert lb + offset + r.max_new_tokens <= self.max_len, "max_len too small"
            if self.paged:
                need = self._request_pages(len(r.tokens), lb, r.max_new_tokens)
                cap = getattr(self.pool, "shard_capacity", self.pool.capacity)
                if need > cap:
                    raise ValueError(
                        f"request needs {need} pages of {self.page_size} but "
                        f"{'each shard' if self.shards > 1 else 'the pool'} "
                        f"only holds {cap}; raise pool_pages to at least "
                        f"{(need + 1) * self.shards} (one page per "
                        f"{'shard' if self.shards > 1 else 'pool'} is reserved scrap)"
                    )

        sched = Scheduler()
        prefix_reuse = self.paged and any(c is not None for c in self.prefix_caches)
        for i, r in enumerate(reqs):
            s0 = len(r.tokens)
            lb = self._bucket_len(s0, fixed_bucket)
            chain = None
            if prefix_reuse:
                # salt = the bucket length: prefix K/V is bitwise-exact only
                # between prompts prefilled at the same padded length, so
                # hits must never cross buckets (see paged.prefix_chain)
                chain = prefix_chain(r.tokens, self.page_size, salt=f"lb={lb}")
            sched.submit(
                (i, r), bucket=lb, cost=lb + r.max_new_tokens,
                deadline=r.deadline, real=s0, padded=lb - s0, prefix=chain,
            )

        self.stats = stats = EngineStats()
        enc_len = max((fixed_bucket or 0) // 4, 1) if self.cfg.family == "encdec" else 0
        if self.paged:
            caches = self._paged_caches(nslots, enc_len)
            # idle rows sink writes into their own shard's scrap page
            # (all-zeros — the historical layout — when single-shard)
            page_table = jnp.asarray(
                np.array(
                    [[self._scrap_id(s, nslots)] * self.pages_per_slot
                     for s in range(nslots)],
                    np.int32,
                )
            )
        else:
            caches = lm.init_caches(self.cfg, nslots, self.max_len, enc_len=enc_len)
            page_table = None
        out_cap = max(r.max_new_tokens for r in reqs)
        tok = jnp.zeros((nslots, 1), jnp.int32)
        pos = jnp.zeros((nslots,), jnp.int32)
        keys = jnp.zeros((nslots, 2), jnp.uint32)
        temps = jnp.zeros((nslots,), jnp.float32)
        out_buf = jnp.zeros((nslots, out_cap), jnp.int32)
        out_idx = jnp.zeros((nslots,), jnp.int32)
        # device-side EOS state: compared/updated inside the decode loop,
        # polled (one tiny transfer) every eos_poll steps
        any_eos = any(r.eos_token is not None for r in reqs)
        eos_vec = jnp.full((nslots,), -1, jnp.int32)
        done = jnp.zeros((nslots,), bool)
        done_idx = jnp.full((nslots,), out_cap, jnp.int32)
        eos_countdown = self.eos_poll
        active: list[dict | None] = [None] * nslots
        results: list[np.ndarray | None] = [None] * len(reqs)
        # live admitted cost per shard — the scheduler's occupancy signal
        shard_cost = [0.0] * self.shards

        def finish(slot):
            nonlocal page_table
            st = active[slot]
            r = reqs[st["rid"]]
            shard_cost[st["shard"]] -= st["cost"]
            if r.eos_token is not None:
                # output row ++ truncation index, fetched together — still
                # ONE transfer per request
                packed = np.asarray(
                    jnp.concatenate([out_buf[slot], done_idx[slot][None]])
                )
                n = min(int(packed[-1]), r.max_new_tokens)
                new = packed[:n]
            else:
                n = r.max_new_tokens
                new = np.asarray(out_buf[slot, :n])  # ONE transfer
            results[st["rid"]] = np.concatenate([np.asarray(r.tokens, np.int32), new])
            stats.generated_tokens += n
            if self.paged:
                self.pool.release(st["pages"])
                page_table = page_table.at[slot].set(  # → shard-local scrap
                    jnp.full(
                        (self.pages_per_slot,), self._scrap_id(slot, nslots),
                        jnp.int32,
                    )
                )
                sched.stats.live_tokens += st["valid"] + n
                sched.stats.page_tokens += len(st["pages"]) * self.page_size
            active[slot] = None

        while len(sched) or any(active):
            free = [s for s in range(nslots) if active[s] is None]
            if free and len(sched):
                taken = sched.take(
                    len(free), equalize=equalize,
                    shards=(
                        [self._slot_shard(s, nslots) for s in free]
                        if self.shards > 1 else None
                    ),
                    shard_load=shard_cost if self.shards > 1 else None,
                )
                while taken:
                    sr = taken.pop(0)
                    slot = free.pop(0)
                    shard = self._slot_shard(slot, nslots)
                    pcache = self.prefix_caches[shard] if self.paged else None
                    rid, r = sr.payload
                    s0 = len(r.tokens)
                    lb = self._bucket_len(s0, fixed_bucket)
                    hit_pages: list[int] = []
                    new_pages: list[int] = []
                    prior = None
                    if self.paged:
                        if pcache is not None and sr.prefix:
                            # strictly-before-the-last-token limit keeps at
                            # least one suffix token to prefill (the logits
                            # source) — and, with the s0 // page insert limit
                            # below, guarantees shared pages are never
                            # decode-written (structural copy-on-write).
                            # Sharded: only this shard's index is consulted,
                            # so hit pages are always slot-local.
                            hit_pages = pcache.lookup(
                                sr.prefix[: (s0 - 1) // self.page_size]
                            )
                        need = self._request_pages(s0, lb, r.max_new_tokens)
                        need_new = need - len(hit_pages)
                        new_pages = self._alloc_pages(need_new, shard)
                        if new_pages is None and pcache is not None:
                            pcache.evict(need_new)
                            new_pages = self._alloc_pages(need_new, shard)
                        if new_pages is None:
                            # pool exhausted: queue the rest of the batch
                            # rather than corrupting live pages
                            if hit_pages:
                                self.pool.release(hit_pages)
                            if not any(a is not None for a in active):
                                raise RuntimeError(
                                    "page pool exhausted with no slot in "
                                    "flight — per-request capacity was "
                                    "checked upfront, so only the prefix "
                                    "index can be pinning pages and evict() "
                                    "should have freed it"
                                )
                            sched.restore([sr] + taken)
                            free.insert(0, slot)
                            break
                    shared = len(hit_pages) * (self.page_size if self.paged else 0)
                    if hit_pages:
                        prior = self._gather_prior(caches, hit_pages)
                        stats.prefix_hits += 1
                        stats.prefix_hit_tokens += shared
                    tail, tail_lb = s0 - shared, lb - shared
                    prompt = np.zeros((1, tail_lb), np.int32)
                    prompt[0, :tail] = np.asarray(r.tokens[shared:], np.int32)
                    last = jnp.asarray([tail + offset - 1], jnp.int32)
                    if self.paged:
                        new_caches, logits = self._prefill(
                            self.params, self._model_batch(prompt), last, prior
                        )
                    else:
                        new_caches, logits = self._prefill(
                            self.params, self._model_batch(prompt), last
                        )
                    stats.prefill_dispatches += 1
                    stats.events.append(("prefill", rid))
                    valid = s0 + offset
                    if self.paged:
                        rest = dict(new_caches)
                        attn_raw = rest.pop("attn")
                        if rest:  # per-slot parts: SSM state, cross K/V
                            live = {k2: caches[k2] for k2 in rest}
                            caches.update(_insert_slot(live, rest, slot, valid))
                        npg = -(-attn_raw["k"].shape[2] // self.page_size)
                        caches = self._scatter_pages(caches, attn_raw, new_pages[:npg])
                        row = hit_pages + new_pages
                        row_np = np.zeros((self.pages_per_slot,), np.int32)
                        row_np[: len(row)] = row
                        page_table = page_table.at[slot].set(jnp.asarray(row_np))
                        if pcache is not None and sr.prefix:
                            # full prompt pages only: decode writes start at
                            # position s0, i.e. page >= s0 // page_size
                            ins = s0 // self.page_size
                            pcache.insert(sr.prefix[:ins], row[:ins])
                    else:
                        caches = _insert_slot(caches, new_caches, slot, valid)
                    # split before first use (same key discipline the
                    # lockstep engine regression-tested): the root key is
                    # never consumed directly
                    key, sub = jax.random.split(jax.random.PRNGKey(r.seed))
                    t0 = self._sample(
                        logits[:, -1], jnp.asarray([r.temperature], jnp.float32), sub[None]
                    )
                    tok = tok.at[slot].set(t0[0])
                    pos = pos.at[slot].set(valid)
                    keys = keys.at[slot].set(key)
                    temps = temps.at[slot].set(r.temperature)
                    out_buf = out_buf.at[slot].set(
                        jnp.zeros((out_cap,), jnp.int32).at[0].set(t0[0, 0])
                    )
                    out_idx = out_idx.at[slot].set(1)
                    if any_eos:
                        e = r.eos_token if r.eos_token is not None else -1
                        eos_vec = eos_vec.at[slot].set(e)
                        d0 = (t0[0, 0] == e) if e >= 0 else jnp.asarray(False)
                        done = done.at[slot].set(d0)
                        done_idx = done_idx.at[slot].set(jnp.where(d0, 1, out_cap))
                    active[slot] = {
                        "rid": rid, "left": r.max_new_tokens - 1,
                        "shard": shard, "cost": sr.cost,
                    }
                    shard_cost[shard] += sr.cost
                    stats.shard_peak_cost = [
                        max(a, b) for a, b in zip(
                            stats.shard_peak_cost or [0.0] * self.shards,
                            shard_cost,
                        )
                    ]
                    if self.paged:
                        active[slot]["pages"] = row
                        active[slot]["valid"] = valid
                    if active[slot]["left"] == 0:
                        finish(slot)
                        free.insert(0, slot)
            stats.peak_active = max(
                stats.peak_active, sum(a is not None for a in active)
            )
            if not any(active):
                continue
            split2 = jax.vmap(lambda k: jax.random.split(k))(keys)  # (S, 2, 2)
            keys, subs = split2[:, 0], split2[:, 1]
            if self.paged:
                caches, logits = self._decode(self.params, caches, tok, pos, page_table)
            else:
                caches, logits = self._decode(self.params, caches, tok, pos)
            stats.decode_dispatches += 1
            stats.events.append(("decode", sum(a is not None for a in active)))
            tok = self._sample(logits[:, -1], temps, subs)
            out_buf = jax.vmap(
                lambda row, t, i: jax.lax.dynamic_update_slice(row, t, (i,))
            )(out_buf, tok[:, 0:1], out_idx)
            out_idx = out_idx + 1
            pos = pos + 1
            if any_eos:
                hit = (tok[:, 0] == eos_vec) & (eos_vec >= 0) & (~done)
                done_idx = jnp.where(hit, out_idx, done_idx)
                done = done | hit
            for slot in range(nslots):
                if active[slot] is not None:
                    active[slot]["left"] -= 1
                    if active[slot]["left"] == 0:
                        finish(slot)
            eos_countdown -= 1
            if any_eos and eos_countdown <= 0:
                eos_countdown = self.eos_poll
                flags = np.asarray(done)  # one (slots,) bool transfer
                for slot in range(nslots):
                    if (
                        active[slot] is not None
                        and reqs[active[slot]["rid"]].eos_token is not None
                        and flags[slot]
                    ):
                        stats.early_exits += 1
                        finish(slot)
        stats.padding_frac = sched.stats.padding_frac
        stats.sched = sched.stats
        if self.paged:
            stats.page_frac = sched.stats.page_frac
            stats.pool_peak_pages = self.pool.peak_used
            # pool K/V persists across serve() calls: pages pinned by the
            # prefix index stay readable for the next call's warm prefills
            self._pages = {
                "k_pages": caches["attn"]["k_pages"],
                "v_pages": caches["attn"]["v_pages"],
            }
            if self.mesh is not None:
                # park the persistent pool over the mesh: shard k's page
                # range [k·P, (k+1)·P) lands on device k of the axis —
                # exactly the blocks its slots allocate from, so the
                # resident KV footprint per device is 1/shards of the pool.
                # _paged_caches canonicalizes back before the next jitted
                # dispatch (bitwise-per-request contract).
                from jax.sharding import NamedSharding, PartitionSpec

                self._pages = jax.device_put(
                    self._pages,
                    NamedSharding(
                        self.mesh,
                        PartitionSpec(None, self.mesh_axis, None, None, None),
                    ),
                )
        return results  # type: ignore[return-value]

    # ------------------------------------------------------------------
    # lockstep-compatible wrapper
    # ------------------------------------------------------------------
    def generate(
        self, prompts: np.ndarray, *, max_new_tokens: int = 32,
        temperature: float = 0.0, seed: int = 0,
    ) -> np.ndarray:
        """prompts: (B, S0) int32 → (B, S0 + max_new_tokens) int32.

        Runs the serve loop with one slot per row and exact-length buckets
        (no padding).  Row ``i`` samples from seed ``seed + i`` so rows
        draw independently; tokens accumulate in the device-side buffer and
        transfer once per row (the old loop synced the host every token)."""
        prompts = np.asarray(prompts, np.int32)
        b, s0 = prompts.shape
        reqs = [
            GenRequest(
                tokens=prompts[i], max_new_tokens=max_new_tokens,
                temperature=temperature, seed=seed + i,
            )
            for i in range(b)
        ]
        out = self.serve(reqs, slots=b)
        return np.stack(out)

    def _sample(self, logits, temperature, key):
        logits = logits[:, : self.cfg.vocab_size]  # drop padded vocab tail
        t = jnp.asarray(temperature, jnp.float32)
        key = jnp.asarray(key)
        if t.ndim == 0 and key.ndim == 1:
            # legacy lockstep signature: one stream for the whole batch
            if float(t) <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return jax.random.categorical(key, logits / t, axis=-1).astype(jnp.int32)[:, None]
        t = jnp.broadcast_to(t, (logits.shape[0],))
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        safe = jnp.where(t > 0.0, t, 1.0)
        sampled = jax.vmap(jax.random.categorical)(key, logits / safe[:, None]).astype(jnp.int32)
        return jnp.where(t > 0.0, sampled, greedy)[:, None]


def _insert_slot(live, new, slot: int, valid_len: int):
    """Scatter a prefilled (batch-1) cache pytree into row ``slot`` of the
    live caches.  ``pos`` leaves are masked by *position value* (>=
    ``valid_len`` → −1) so bucket-pad K/V slots can never be attended.
    For sliding-window caches this relies on ``Engine._bucket_len`` keeping
    the padded prompt inside the ring (pads past the window would evict
    real K/V before this mask could catch them)."""

    def fix(path, lv, nw):
        row = nw[:, 0]
        if path and getattr(path[-1], "key", None) == "pos":
            row = jnp.where((row >= 0) & (row < valid_len), row, -1)
        return lv.at[:, slot].set(row)

    return jax.tree_util.tree_map_with_path(fix, live, new)

"""Paged KV cache tests: page-pool bookkeeping, prefix fingerprint chains,
the Pallas gather-attention kernel's bitwise twin, paged-vs-dense serve
identity, shared-prefix warm admission, structural copy-on-write, pool
exhaustion queuing, and EOS early exit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels.paged_attn import paged_decode_attention, paged_decode_attention_ref
from repro.models import lm
from repro.serve.engine import Engine, GenRequest
from repro.serve.paged import SCRAP_PAGE, PagePool, PrefixCache, ShardedPagePool, prefix_chain
from repro.utils.hlo import primitive_count


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3_8b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def dense_engine(setup):
    cfg, params = setup
    return Engine(params, cfg, max_len=64, slots=4, bucket=4)


@pytest.fixture(scope="module")
def paged_engine(setup):
    cfg, params = setup
    return Engine(params, cfg, max_len=64, slots=4, bucket=4,
                  paged=True, page_size=8)


def _ragged_requests(cfg, *, temperature_odd=0.8):
    rng = np.random.default_rng(42)
    lens = [3, 9, 5, 12, 2, 7, 4, 10]
    news = [9, 2, 5, 3, 11, 4, 6, 2]
    return [
        GenRequest(
            tokens=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
            max_new_tokens=n,
            temperature=0.0 if i % 2 else temperature_odd,
            seed=100 + i,
        )
        for i, (s, n) in enumerate(zip(lens, news))
    ]


# ---------------------------------------------------------------------------
# PagePool / prefix_chain / PrefixCache units
# ---------------------------------------------------------------------------
def test_page_pool_alloc_release_refcount():
    pool = PagePool(6, page_size=4)
    assert pool.capacity == 5 and pool.free == 5  # page 0 reserved scrap
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3] and SCRAP_PAGE not in a
    assert pool.used == 3 and pool.peak_used == 3
    pool.retain([a[0]])
    assert pool.refcount(a[0]) == 2 and not pool.writable(a[0])
    pool.release(a)
    assert pool.refcount(a[0]) == 1 and pool.free == 4
    pool.release([a[0]])
    assert pool.free == 5
    # all-or-nothing: a short alloc takes nothing
    assert pool.alloc(6) is None
    assert pool.free == 5 and pool.failed_allocs == 1
    with pytest.raises(ValueError):
        pool.release([a[0]])  # already free
    with pytest.raises(ValueError):
        pool.retain([SCRAP_PAGE])


def test_prefix_chain_determinism_and_salt():
    toks = np.arange(20, dtype=np.int32)
    c1 = prefix_chain(toks, 8)
    c2 = prefix_chain(toks, 8)
    assert c1 == c2 and len(c1) == 2  # only FULL pages are fingerprinted
    # chain property: equal leading blocks -> equal chain prefix, and the
    # first divergent block breaks every later digest
    other = toks.copy()
    other[9] = 99
    c3 = prefix_chain(other, 8)
    assert c3[0] == c1[0] and c3[1] != c1[1]
    # the bucket-length salt separates otherwise-identical prompts: prefix
    # K/V is only bitwise-reproducible within one padded length
    assert prefix_chain(toks, 8, salt="lb=24") != prefix_chain(toks, 8, salt="lb=32")


def test_prefix_cache_lru_evicts_only_unpinned():
    pool = PagePool(5, page_size=4)
    cache = PrefixCache(pool)
    held = pool.alloc(2)
    cache.insert(["a", "b"], held)  # refcount 2 each (caller + index)
    assert len(cache) == 2 and pool.free == 2
    # pinned pages never evict
    assert cache.evict(need_free=4) == 0
    pool.release(held)  # caller drops; index still holds both
    got = cache.lookup(["a", "b", "c"])
    assert got == held  # longest-prefix hit, retained for us
    assert cache.hits == 1 and cache.hit_tokens == 8
    pool.release(got)
    assert cache.evict(need_free=4) == 2 and pool.free == 4
    assert cache.lookup(["a"]) == [] and cache.misses == 1


# ---------------------------------------------------------------------------
# kernel twin: bitwise + one pallas_call
# ---------------------------------------------------------------------------
def test_paged_attention_kernel_bitwise_and_single_call():
    key = jax.random.PRNGKey(0)
    b, h, kvh, dh, pool_pages, page, np_ = 3, 4, 2, 16, 9, 8, 4
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (pool_pages, page, kvh, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (pool_pages, page, kvh, dh), jnp.float32)
    # ragged page tables with -1 holes past each row's allocation
    pt = np.full((b, np_), -1, np.int32)
    pt[0, :2] = [3, 7]
    pt[1, :4] = [1, 2, 5, 8]
    pt[2, :1] = [4]
    lengths = jnp.asarray([13, 32, 5], jnp.int32)
    pt = jnp.asarray(pt)
    out = paged_decode_attention(q, kp, vp, pt, lengths)
    ref = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    jx = jax.make_jaxpr(
        lambda *a: paged_decode_attention(*a, interpret=True)
    )(q, kp, vp, pt, lengths)
    assert primitive_count(jx, "pallas_call") == 1


def test_paged_decode_step_single_pallas_call_per_layer(setup):
    cfg, params = setup
    caches = lm.init_paged_caches(cfg, 2, num_pages=9, page_size=8)
    pt = jnp.zeros((2, 4), jnp.int32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    jx = jax.make_jaxpr(
        lambda p, c, t, s, g: lm.decode_step(p, c, t, s, cfg, page_table=g)
    )(params, caches, tok, pos, pt)
    # the layer stack is one lax.scan: the whole decode traces ONE
    # pallas_call (inside the scan body), not one per layer
    assert primitive_count(jx, "pallas_call") == 1


# ---------------------------------------------------------------------------
# serve-level bitwise identity
# ---------------------------------------------------------------------------
def test_paged_serve_bitwise_identical_to_dense(setup, dense_engine, paged_engine):
    cfg, _ = setup
    reqs = _ragged_requests(cfg)
    outs_d = dense_engine.serve(_ragged_requests(cfg))
    outs_p = paged_engine.serve(reqs)
    for i, (a, b) in enumerate(zip(outs_d, outs_p)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    st = paged_engine.stats
    assert st.peak_active <= 4
    assert st.pool_peak_pages <= paged_engine.pool.capacity
    # every retired page came back (only the prefix index may pin pages)
    pool = paged_engine.pool
    pinned = len(set(paged_engine.prefix_cache.pages.values()))
    assert pool.free == pool.capacity - pinned


def test_page_frac_accounting(setup, paged_engine):
    cfg, _ = setup
    paged_engine.serve(_ragged_requests(cfg))
    st = paged_engine.stats
    sched = st.sched
    assert sched.page_tokens >= sched.live_tokens > 0
    assert 0.0 <= st.page_frac < 1.0
    assert st.page_frac == pytest.approx(
        (sched.page_tokens - sched.live_tokens) / sched.page_tokens
    )


def test_warm_prefix_bitwise_identical_to_cold(setup):
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, (37,)).astype(np.int32)
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=8,
                 paged=True, page_size=8)
    cold = eng.serve([GenRequest(prompt, 6, seed=1)])[0]
    assert eng.stats.prefix_hits == 0
    warm = eng.serve([GenRequest(prompt, 6, seed=1)])[0]
    np.testing.assert_array_equal(cold, warm)
    # lookup stops strictly before the last prompt token: (37-1)//8 = 4
    # pages = 32 tokens reused, 5 suffix tokens re-prefilled
    assert eng.stats.prefix_hits == 1
    assert eng.stats.prefix_hit_tokens == 32
    dense = Engine(params, cfg, max_len=64, slots=2, bucket=8)
    np.testing.assert_array_equal(dense.serve([GenRequest(prompt, 6, seed=1)])[0], warm)


def test_copy_on_write_divergent_sharer_does_not_perturb(setup):
    """A prompt sharing a donor's prefix pages but diverging mid-prompt must
    (a) produce its own correct output and (b) leave the donor's shared
    pages untouched — CoW is structural: shared pages are never written."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    a = rng.integers(0, cfg.vocab_size, (37,)).astype(np.int32)
    b = a.copy()
    b[20] = (b[20] + 1) % cfg.vocab_size  # diverge inside page 2 of 8
    dense = Engine(params, cfg, max_len=64, slots=2, bucket=8)
    want_a = dense.serve([GenRequest(a, 6, seed=1)])[0]
    want_b = dense.serve([GenRequest(b, 6, seed=2)])[0]
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=8,
                 paged=True, page_size=8)
    eng.serve([GenRequest(a, 6, seed=1)])  # donor populates the prefix cache
    outs = eng.serve([GenRequest(a, 6, seed=1), GenRequest(b, 6, seed=2)])
    np.testing.assert_array_equal(outs[0], want_a)
    np.testing.assert_array_equal(outs[1], want_b)
    # after retirement only the index holds references — nothing leaked a
    # write-protecting refcount
    for page in set(eng.prefix_cache.pages.values()):
        assert eng.pool.refcount(page) == 1
    # and the donor still serves warm + bitwise
    np.testing.assert_array_equal(eng.serve([GenRequest(a, 6, seed=1)])[0], want_a)


def test_pool_exhaustion_queues_and_stays_bitwise(setup):
    """5 requests of 3 pages each against a 6-page pool: at most 2 fit at
    once, the rest re-queue (no crash, no corruption), outputs stay
    bitwise-identical to dense."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    reqs = [
        GenRequest(rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32),
                   max_new_tokens=4, seed=10 + i)
        for i in range(5)
    ]
    eng = Engine(params, cfg, max_len=64, slots=8, bucket=4,
                 paged=True, page_size=8, pool_pages=7, prefix_reuse=False)
    outs = eng.serve(reqs)
    assert eng.stats.peak_active <= 2
    assert eng.pool.failed_allocs > 0
    assert eng.pool.free == eng.pool.capacity
    dense = Engine(params, cfg, max_len=64, slots=8, bucket=4)
    for a, b in zip(dense.serve(reqs), outs):
        np.testing.assert_array_equal(a, b)


def test_oversized_request_rejected_upfront(setup):
    cfg, params = setup
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                 paged=True, page_size=8, pool_pages=5)
    big = GenRequest(np.zeros((30,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="pool only holds"):
        eng.serve([big])


def test_unpageable_archs_rejected():
    for arch, err in [("mixtral_8x22b", "sliding-window"), ("mamba2_1_3b", "SSM"),
                      ("hymba_1_5b", "sliding-window")]:
        cfg = get_config(arch).reduced()
        with pytest.raises(ValueError, match=err):
            Engine(None, cfg, max_len=64, paged=True, page_size=8)


@pytest.mark.parametrize("arch", ["qwen2_vl_2b", "whisper_tiny"])
def test_families_paged_identical_to_dense(arch):
    """Every row-independent pageable family (vlm prefix offset, encdec
    cross caches) serves bitwise-identically paged vs dense.  MoE is
    excluded here exactly as in the dense ragged suite: expert capacity
    couples batch rows, and the *idle-slot* garbage rows differ between
    dense (stale cache) and paged (scrap page), so the coupled live rows
    can legitimately diverge.  Hymba/Mixtral are sliding-window (not
    pageable, rejected above); Mamba2 is pure SSM (no KV to page)."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [
        GenRequest(tokens=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
                   max_new_tokens=n, seed=i)
        for i, (s, n) in enumerate([(5, 4), (8, 2), (3, 6)])
    ]
    dense = Engine(params, cfg, max_len=64, slots=2, bucket=4)
    paged = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                   paged=True, page_size=16)
    for a, b in zip(dense.serve(reqs), paged.serve(reqs)):
        np.testing.assert_array_equal(a, b)


def test_moe_paged_smoke_and_deterministic():
    """MoE serves paged (shapes + repeatability); bitwise-vs-dense is not
    asserted because expert capacity couples rows with the idle-slot
    garbage, which differs by cache layout (see the families test)."""
    cfg = get_config("granite_moe_1b_a400m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [
        GenRequest(tokens=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
                   max_new_tokens=n, seed=i)
        for i, (s, n) in enumerate([(5, 4), (8, 2), (3, 6)])
    ]
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                 paged=True, page_size=16)
    outs1 = eng.serve(reqs)
    for r, o in zip(reqs, outs1):
        assert o.shape == (len(r.tokens) + r.max_new_tokens,)
    eng2 = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                  paged=True, page_size=16)
    for a, b in zip(outs1, eng2.serve(reqs)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# EOS early exit
# ---------------------------------------------------------------------------
def test_eos_early_exit_truncates_and_saves_dispatches(setup, dense_engine):
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
    base = dense_engine.serve([GenRequest(prompt, 10, seed=1)])[0]
    n_base = dense_engine.stats.decode_dispatches
    eos_tok = int(base[len(prompt) + 2])  # the third generated token
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                 paged=True, page_size=8, eos_poll=2)
    out = eng.serve([GenRequest(prompt, 10, seed=1, eos_token=eos_tok)])[0]
    # output ends AT the eos token (included), budget unspent
    np.testing.assert_array_equal(out, base[: len(prompt) + 3])
    assert eng.stats.early_exits == 1
    assert eng.stats.decode_dispatches < n_base
    assert eng.stats.generated_tokens == 3
    # early retirement freed the pages
    assert eng.pool.free == eng.pool.capacity - len(
        set(eng.prefix_cache.pages.values())
    )


def test_eos_never_sampled_runs_full_budget(setup):
    cfg, params = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    dense = Engine(params, cfg, max_len=64, slots=2, bucket=4)
    base = dense.serve([GenRequest(prompt, 6, seed=3)])[0]
    gen = base[len(prompt):]
    absent = int(next(t for t in range(cfg.vocab_size) if t not in set(gen.tolist())))
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4)
    out = eng.serve([GenRequest(prompt, 6, seed=3, eos_token=absent)])[0]
    np.testing.assert_array_equal(out, base)
    assert eng.stats.early_exits == 0


def test_eos_works_in_dense_mode_mixed_batch(setup, dense_engine):
    """eos_token composes with the dense engine and with non-eos flight
    mates: the non-eos request's output is untouched."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    p1 = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    base = dense_engine.serve([GenRequest(p1, 8, seed=1), GenRequest(p2, 8, seed=2)])
    # eos = the first generated token of req 1 (past the first) that has not
    # appeared earlier in its output: the output truncates at the FIRST
    # occurrence, so a repeated token would end it sooner
    gen = base[0][len(p1):].tolist()
    k = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
    eos_tok = int(gen[k])
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4, eos_poll=1)
    outs = eng.serve([GenRequest(p1, 8, seed=1, eos_token=eos_tok),
                      GenRequest(p2, 8, seed=2)])
    np.testing.assert_array_equal(outs[0], base[0][: len(p1) + k + 1])
    np.testing.assert_array_equal(outs[1], base[1])
    assert eng.stats.early_exits == 1


# ---------------------------------------------------------------------------
# mesh-sharded serving (ISSUE 10): per-shard pools, bitwise identity
# ---------------------------------------------------------------------------
def test_sharded_page_pool_disjoint_ranges():
    pool = ShardedPagePool(shards=4, pages_per_shard=4, page_size=8)
    assert pool.capacity == 12 and pool.shard_capacity == 3
    assert [pool.scrap(k) for k in range(4)] == [0, 4, 8, 12]
    a = pool.alloc(3, shard=1)
    assert a is not None and all(4 < p < 8 for p in a)
    assert pool.shard_used() == [0, 3, 0, 0]
    # all-or-nothing WITHIN the shard: shard 1 is full, shard 2 has room,
    # but pages are never borrowed across shards
    assert pool.alloc(1, shard=1) is None
    assert pool.failed_allocs == 1
    b = pool.alloc(2, shard=2)
    assert all(8 < p < 12 for p in b)
    # retain/release route by global id range
    pool.retain(a + b)
    assert pool.refcount(a[0]) == 2 and pool.refcount(b[0]) == 2
    pool.release(a + b)
    pool.release(a + b)
    assert pool.free == pool.capacity and pool.used == 0
    # scrap pages are never allocatable or releasable
    with pytest.raises(ValueError):
        pool.release([pool.scrap(2)])


def test_sharded_serve_bitwise_identical_to_single_device(setup):
    """Acceptance: per-request outputs of a shards>1 paged serve are
    bitwise-identical to the single-device paged serve (per-slot rows are
    computed independently, so shard placement must not change a bit)."""
    cfg, params = setup
    reqs = _ragged_requests(cfg)
    base = Engine(params, cfg, max_len=64, slots=4, bucket=4,
                  paged=True, page_size=8)
    want = base.serve(_ragged_requests(cfg))
    eng = Engine(params, cfg, max_len=64, slots=4, bucket=4,
                 paged=True, page_size=8, shards=4)
    got = eng.serve(reqs)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    # per-shard accounting is live: peak cost was tracked for every shard
    assert len(eng.stats.shard_peak_cost) == 4
    assert all(c > 0 for c in eng.stats.shard_peak_cost)


def test_mesh_sharded_serve_bitwise_and_parked_pool(setup):
    """mesh= derives the shard count from the mesh axis; outputs stay
    bitwise-identical, and a SECOND serve() (which reuses the mesh-parked
    KV pool) is bitwise-identical too."""
    from repro.launch.mesh import make_mesh

    cfg, params = setup
    mesh = make_mesh((8,), ("model",))
    base = Engine(params, cfg, max_len=64, slots=8, bucket=4,
                  paged=True, page_size=8)
    want = base.serve(_ragged_requests(cfg))
    eng = Engine(params, cfg, max_len=64, slots=8, bucket=4,
                 paged=True, page_size=8, mesh=mesh)
    assert eng.shards == 8
    for w, g in zip(want, eng.serve(_ragged_requests(cfg))):
        np.testing.assert_array_equal(w, g)
    for w, g in zip(want, eng.serve(_ragged_requests(cfg))):
        np.testing.assert_array_equal(w, g)


def test_sharded_serve_balances_shard_cost(setup):
    """The shard-aware take() keeps per-shard peak cost closer together
    than the worst case (all heavy requests on one shard)."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    # two heavy + two light requests, admitted into 4 slots over 2 shards
    reqs = [
        GenRequest(tokens=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
                   max_new_tokens=n, temperature=0.0, seed=i)
        for i, (s, n) in enumerate([(12, 12), (12, 12), (2, 2), (2, 2)])
    ]
    eng = Engine(params, cfg, max_len=64, slots=4, bucket=4,
                 paged=True, page_size=8, shards=2)
    eng.serve(reqs)
    peak = eng.stats.shard_peak_cost
    assert len(peak) == 2
    # each shard got one heavy + one light request, not heavy+heavy
    assert max(peak) < 2 * 24 and min(peak) > 0
    assert abs(peak[0] - peak[1]) < 24


def test_sharded_prefix_cache_is_shard_local(setup):
    """Prefix reuse still works sharded — but an entry only hits for slots
    on its own shard (pages are never borrowed across shards)."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    reqs = [GenRequest(np.concatenate([shared, rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)]),
                       max_new_tokens=3, temperature=0.0, seed=50 + i)
            for i in range(4)]
    base = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                  paged=True, page_size=8)
    want = base.serve(reqs)
    eng = Engine(params, cfg, max_len=64, slots=2, bucket=4,
                 paged=True, page_size=8, shards=2)
    got = eng.serve(reqs)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    hits = sum(c.hits for c in eng.prefix_caches if c is not None)
    assert hits >= 1  # same-shard reuse happened
    # every cached page lives on its cache's own shard
    for k, c in enumerate(eng.prefix_caches):
        for page in c.pages.values():
            assert eng.pool.shard_of(page) == k

"""Cross-request prefix cache (serve/prefixcache.py, the refcounted
kvpool share/release semantics, serving's tail-prefill family, and
the continuous engine's copy-on-write page sharing):

* BlockPool refcounts: share/release lifecycle, double-free errors
  naming the owning lane/trie node, share-of-free-page refusal;
* the trie: page-granular matching (a prompt that is not a kv_block
  multiple never shares its straddling page; a fully-cached prompt
  still keeps a 1-token tail), LRU-by-leaf eviction with pinned-page
  refusal, share-then-evict churn under the lockcheck monitor;
* the artifact: tail-prefill export/load surface, and the
  no-tail-programs fallback (prefix_cache=True raises, auto
  disables);
* the engine: BITWISE cached-vs-cold greedy parity on the native
  rung, int8 scale-plane sharing (quantized pages reused, live
  shared-page refcounts observed mid-decode), pool-integrity reset
  releasing trie refs after an injected step fault, zero pool-page
  leaks at drain;
* the watchdogged smoke (tools/prefix_smoke.py) in-process, the
  scenario_smoke tier-1 pattern.
"""

import threading

import numpy as np
import pytest

from cxxnet_tpu import config, models, serving
from cxxnet_tpu.io import DataBatch
from cxxnet_tpu.serve.continuous import ContinuousDecodeEngine
from cxxnet_tpu.serve.kvpool import BlockPool
from cxxnet_tpu.serve.prefixcache import PrefixCache
from cxxnet_tpu.trainer import Trainer

SEQ, PROMPT, MAX_NEW, VOCAB = 200, 160, 6, 16
KVB = 128


# ----------------------------------------------------------------------
# BlockPool refcounts

def test_pool_share_release_lifecycle():
    p = BlockPool(9, KVB)
    a = p.alloc(2, owner="req-1")
    assert p.refcount(a[0]) == 1 and p.shared_blocks == 0
    p.share([a[0]], owner="req-2")
    p.share([a[0]], owner="trie[d0]")
    assert p.refcount(a[0]) == 3 and p.shared_blocks == 1
    assert p.in_use == 2            # refs don't inflate page counts
    p.release([a[0]], owner="req-1")
    p.release([a[0]], owner="req-2")
    assert p.refcount(a[0]) == 1 and p.in_use == 2
    p.release([a[0]], owner="trie[d0]")
    assert p.refcount(a[0]) == 0 and p.in_use == 1
    b = p.alloc(1)                  # the freed page is reusable
    assert b[0] == a[0]
    p.free(b)
    p.free([a[1]], owner="req-1")
    p.assert_empty()


def test_pool_share_of_free_page_raises():
    p = BlockPool(4, KVB)
    a = p.alloc(1, owner="req-1")
    p.free(a, owner="req-1")
    with pytest.raises(ValueError, match="share of FREE"):
        p.share(a)
    with pytest.raises(ValueError, match="outside the usable"):
        p.share([0])


def test_pool_double_free_names_owner():
    p = BlockPool(4, KVB)
    a = p.alloc(1, owner="lane-7")
    p.free(a, owner="lane-7")
    with pytest.raises(ValueError, match="lane-7"):
        p.free(a)                   # names the LAST releaser
    b = p.alloc(1, owner="trie[d0]")
    p.share(b, owner="req-9")
    # dropping three refs against two held names the current holders
    with pytest.raises(ValueError) as ei:
        p.release(b + b + b)
    assert "trie[d0]" in str(ei.value) or "req-9" in str(ei.value)
    p.release(b, owner="req-9")
    p.release(b, owner="trie[d0]")
    p.assert_empty()


def test_pool_leak_report_names_owners():
    p = BlockPool(4, KVB)
    p.alloc(1, owner="req-leaky")
    with pytest.raises(AssertionError, match="req-leaky"):
        p.assert_empty()


# ----------------------------------------------------------------------
# trie

def _toks(n, seed=0):
    return (np.random.RandomState(seed)
            .randint(0, VOCAB, n).astype(np.int32))


def test_trie_page_granular_match_and_publish():
    pool = BlockPool(16, KVB)
    pc = PrefixCache(pool, KVB, capacity_pages=8)
    t = _toks(130, seed=3)

    # below one full page: nothing to match, nothing to publish
    nodes, pages = pc.match_and_pin(t[:127])
    assert nodes == [] and pages == []
    blocks = pool.alloc(2, owner="r0")
    assert pc.publish(t[:127], blocks) == 0

    # 130 tokens = one full page + a straddling partial page: only
    # the full page publishes (the straddling page never shares)
    assert pc.publish(t, blocks) == 1
    assert pc.pages_held == 1 and pool.refcount(blocks[0]) == 2

    # an EXACTLY page-aligned prompt never matches its last page:
    # the tail must keep >= 1 token for the first sampled token
    nodes, pages = pc.match_and_pin(t[:128])
    assert nodes == [] and pages == []
    nodes, pages = pc.match_and_pin(t, owner="r1")
    assert len(nodes) == 1 and pages == [blocks[0]]
    assert pool.refcount(blocks[0]) == 3
    pc.unpin(nodes)
    pool.release(pages, owner="r1")
    pool.release(blocks, owner="r0")
    assert pc.reset() == 1
    pool.assert_empty()


def test_trie_eviction_lru_and_pinned_refusal():
    pool = BlockPool(16, KVB)
    pc = PrefixCache(pool, KVB, capacity_pages=2)
    rows = [_toks(128, seed=i) for i in range(3)]
    blocks = {i: pool.alloc(1, owner="r%d" % i)[0]
              for i in range(3)}
    pc.publish(rows[0], [blocks[0]])
    pc.publish(rows[1], [blocks[1]])
    # touch row 0 so row 1 is the LRU leaf
    nodes0, pages0 = pc.match_and_pin(np.concatenate(
        [rows[0], rows[0][:1]]), owner="pin0")
    assert len(nodes0) == 1

    # over capacity: the LRU unpinned leaf (row 1) evicts; the pinned
    # row-0 page is REFUSED even though it is older by insertion
    assert pc.publish(rows[2], [blocks[2]]) == 1
    assert pc.evictions == 1 and pc.pages_held == 2
    assert pool.refcount(blocks[1]) == 1       # trie ref released
    assert pool.refcount(blocks[0]) == 3       # pinned + trie + owner

    # with every leaf pinned, a further insert is SKIPPED, not forced
    nodes2, pages2 = pc.match_and_pin(np.concatenate(
        [rows[2], rows[2][:1]]), owner="pin2")
    extra = pool.alloc(1, owner="r3")[0]
    assert pc.publish(_toks(128, seed=9), [extra]) == 0
    assert pc.pages_held == 2

    pc.unpin(nodes0)
    pc.unpin(nodes2)
    pool.release(pages0, owner="pin0")
    pool.release(pages2, owner="pin2")
    pool.release([extra], owner="r3")
    for i in range(3):
        pool.release([blocks[i]], owner="r%d" % i)
    pc.reset()
    pool.assert_empty()


def test_trie_pool_pressure_reclaim_and_capacity_clamp():
    # a user-set capacity near the pool size is clamped so one
    # sequence stays allocatable, and pool pressure reclaims
    # EXCLUSIVELY trie-held pages so cache growth can never wedge
    # admission (the second eviction trigger beside publish overflow)
    pool = BlockPool(9, KVB)                  # 8 usable
    pc = PrefixCache(pool, KVB, capacity_pages=8, reserve_pages=2)
    assert pc.capacity_pages == 6
    pages = []
    for i in range(6):
        b = pool.alloc(1, owner="r%d" % i)
        pc.publish(_toks(128, seed=40 + i), b)
        pool.release(b, owner="r%d" % i)
        pages.append(b[0])
    assert pc.pages_held == 6 and pool.free_blocks == 2
    # a shared (still-referenced) page must not count as reclaimed
    nodes, shared = pc.match_and_pin(
        np.concatenate([_toks(128, seed=40), [1]]), owner="live")
    assert len(shared) == 1
    freed = pc.reclaim(4)
    assert freed == 4 and pool.free_blocks == 6
    assert pc.evictions >= 4
    # the pinned+shared page survived
    assert pool.refcount(shared[0]) == 2
    pc.unpin(nodes)
    pool.release(shared, owner="live")
    pc.reset()
    pool.assert_empty()


def test_trie_share_then_evict_race_lockcheck():
    from cxxnet_tpu.analysis import lockcheck
    m = lockcheck.enable(held_warn_s=5.0)
    try:
        pool = BlockPool(33, KVB)
        pc = PrefixCache(pool, KVB, capacity_pages=4)
        prompts = [_toks(129, seed=i) for i in range(8)]
        errs = []

        def churn(seed):
            rs = np.random.RandomState(seed)
            try:
                for _ in range(120):
                    t = prompts[rs.randint(len(prompts))]
                    nodes, pages = pc.match_and_pin(
                        t, owner="w%d" % seed)
                    if not pages:
                        try:
                            blocks = pool.alloc(1, owner="w%d" % seed)
                        except Exception:
                            continue
                        pc.publish(t, blocks)
                        pool.release(blocks, owner="w%d" % seed)
                    else:
                        pc.unpin(nodes)
                        pool.release(pages, owner="w%d" % seed)
            except Exception as e:       # pragma: no cover
                errs.append(e)
        ts = [threading.Thread(target=churn, args=(i,))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        pc.reset()
        pool.assert_empty()
        m.assert_clean()
    finally:
        lockcheck.disable()


# ----------------------------------------------------------------------
# trained fixture (prompt region holds one shareable page)

@pytest.fixture(scope="module")
def plm(tmp_path_factory):
    tr = Trainer()
    for k, v in config.parse_string(models.tiny_lm(
            seq_len=SEQ, vocab=VOCAB, embed=32, nlayer=1, nhead=2)):
        tr.set_param(k, v)
    for k, v in (("batch_size", "2"), ("dev", "cpu:0"), ("eta", "0.3"),
                 ("seed", "0"), ("metric", "token_error")):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for _ in range(5):
        start = rs.randint(0, VOCAB, size=(2, 1))
        seq = (start + np.arange(SEQ + 1)) % VOCAB
        tr.update(DataBatch(
            data=seq[:, :SEQ].astype(np.float32).reshape(2, 1, SEQ, 1),
            label=seq[:, 1:].astype(np.float32)))
    td = tmp_path_factory.mktemp("prefix")
    step_p = str(td / "step.export")
    serving.export_decode_step(
        tr, step_p, max_new=MAX_NEW, temperature=0.0,
        prompt_len=PROMPT, prefill_rows=[1, 2],
        prefill_widths=[64, 192], kv_dtypes=["native", "int8"],
        platforms=["cpu"])
    tmpl = ((np.arange(144) * 5 + 3) % VOCAB).astype(np.int32)
    return {"tr": tr, "step_path": step_p, "template": tmpl}


def _prompts(n, seed, tmpl):
    g = np.random.RandomState(seed)
    toks = np.zeros((n, SEQ), np.int32)
    lens = np.zeros((n,), np.int32)
    for r in range(n):
        plen = 150 + r
        toks[r, :144] = tmpl
        toks[r, 144:plen] = g.randint(0, VOCAB, plen - 144)
        lens[r] = plen
    return toks, lens


def _run(eng, toks, lens):
    outs = []
    for r in range(toks.shape[0]):
        req = eng.submit_tokens(toks[r:r + 1], [int(lens[r])])
        outs.append(np.asarray(req.result(60.0)))
    return np.concatenate(outs, 0)


# ----------------------------------------------------------------------
# artifact surface

def test_tail_prefill_export_surface(plm):
    dec = serving.load_exported(plm["step_path"])
    assert dec.has_tail_prefill("native")
    assert dec.has_tail_prefill("int8")
    assert dec.tail_widths("native") == [64]
    assert dec.pick_tail_width(30) == 64
    with pytest.raises(ValueError, match="widest exported"):
        dec.pick_tail_width(100)
    assert dec.ctx_blocks == 2       # P = 192, kv_block = 128
    with pytest.raises(ValueError, match="tail-prefill"):
        dec.tail_call("native", 7, 64)
    kinds = {p["kind"] for p in dec.meta["programs"]}
    assert "tail_prefill" in kinds


def test_no_tail_programs_disables_cache(plm, tmp_path):
    # a narrow prompt region (P <= kv_block) has no shareable page:
    # the tail family is skipped and the cache degrades to off
    p = str(tmp_path / "narrow.export")
    serving.export_decode_step(plm["tr"], p, max_new=4, temperature=0.0,
                               prompt_len=8, platforms=["cpu"])
    dec = serving.load_exported(p)
    assert not dec.has_tail_prefill("native")
    assert dec.meta["tail_prefill_widths"] == []
    with pytest.raises(ValueError, match="prefix_cache=True"):
        ContinuousDecodeEngine(dec, prefix_cache=True, start=False)
    eng = ContinuousDecodeEngine(dec, prefix_cache="auto",
                                 start=False)
    assert eng.prefix is None
    eng.close()


# ----------------------------------------------------------------------
# engine: parity, sharing, reset, leaks

def test_engine_cached_vs_cold_bitwise_parity(plm):
    dec_cold = serving.load_exported(plm["step_path"])
    eng0 = ContinuousDecodeEngine(dec_cold, warmup=False,
                                  prefix_cache=False)
    toks, lens = _prompts(2, 11, plm["template"])
    cold = _run(eng0, toks, lens)
    eng0.close()
    eng0.pool.assert_empty()

    eng1 = ContinuousDecodeEngine(serving.load_exported(
        plm["step_path"]), warmup=False, prefix_cache=True)
    warm1 = _run(eng1, toks, lens)       # row 0 publishes, row 1 hits
    warm2 = _run(eng1, toks, lens)       # all hits
    m = eng1.metrics()
    assert m["prefix_cache"]["hits"] >= 3
    assert m["prefix_cache"]["misses"] == 1
    assert m["tail_prefills"] >= 3
    assert np.array_equal(warm1, cold)
    assert np.array_equal(warm2, cold)
    eng1.close()
    eng1.pool.assert_empty()             # zero leaks at drain


def test_engine_partial_block_never_shares_straddling_page(plm):
    tmpl = plm["template"]
    eng = ContinuousDecodeEngine(serving.load_exported(
        plm["step_path"]), warmup=False, prefix_cache=True)
    t = np.zeros((1, SEQ), np.int32)
    t[0, :130] = np.concatenate([tmpl[:128], [1, 2]])
    _run(eng, t, np.array([130]))        # publishes ONLY page 0
    assert eng.metrics()["prefix_cache"]["pages_held"] == 1
    t2 = np.zeros((1, SEQ), np.int32)
    t2[0, :127] = tmpl[:127]             # same leading tokens, < 1 page
    _run(eng, t2, np.array([127]))
    m = eng.metrics()["prefix_cache"]
    assert m["hits"] == 0 and m["misses"] == 2
    _run(eng, t, np.array([130]))        # full page + tail: hits
    m = eng.metrics()["prefix_cache"]
    assert m["hits"] == 1
    eng.close()
    eng.pool.assert_empty()


def test_engine_int8_scale_plane_sharing(plm):
    # the int8 rung shares QUANTIZED pages + scale planes (one page id
    # covers K, V and both planes); cached-vs-cold is approximate (the
    # tail attends over dequantized prefix), gated like the rung
    toks, lens = _prompts(2, 23, plm["template"])
    eng0 = ContinuousDecodeEngine(serving.load_exported(
        plm["step_path"]), warmup=False, kv_dtype="int8",
        prefix_cache=False)
    cold = _run(eng0, toks, lens)
    eng0.close()

    shared_seen = []

    def hook():
        shared_seen.append(eng1.pool.snapshot()["shared"])

    eng1 = ContinuousDecodeEngine(serving.load_exported(
        plm["step_path"]), warmup=False, kv_dtype="int8",
        prefix_cache=True, step_hook=hook)
    _run(eng1, toks, lens)
    cached = _run(eng1, toks, lens)
    m = eng1.metrics()
    assert m["prefix_cache"]["hits"] >= 3
    # a decoding hit really holds the page at refcount > 1 (trie +
    # request) — observed live from the step hook
    assert max(shared_seen) >= 1
    gen = np.asarray(
        [cold[r, int(lens[r]):int(lens[r]) + MAX_NEW]
         for r in range(2)])
    gen_c = np.asarray(
        [cached[r, int(lens[r]):int(lens[r]) + MAX_NEW]
         for r in range(2)])
    assert (gen == gen_c).mean() >= 0.95
    eng1.close()
    eng1.pool.assert_empty()


def test_engine_failed_step_resets_trie_without_leaking(plm):
    fault = {"arm": False}

    def hook():
        if fault["arm"]:
            fault["arm"] = False
            raise RuntimeError("injected step fault")

    eng = ContinuousDecodeEngine(serving.load_exported(
        plm["step_path"]), warmup=False, prefix_cache=True,
        step_hook=hook)
    toks, lens = _prompts(2, 31, plm["template"])
    _run(eng, toks, lens)                # warm: trie holds a page
    assert eng.metrics()["prefix_cache"]["pages_held"] == 1
    fault["arm"] = True
    with pytest.raises(Exception):
        req = eng.submit_tokens(toks[:1], [int(lens[0])])
        req.result(30.0)
    # pool-integrity reset released the trie's refs instead of
    # leaking them, and no request holds anything
    assert eng.metrics()["prefix_cache"]["pages_held"] == 0
    assert eng.pool.in_use == 0
    # readmission works and re-warms the cache
    out = _run(eng, toks, lens)
    assert out.shape == (2, SEQ)
    assert eng.metrics()["prefix_cache"]["pages_held"] == 1
    eng.close()
    eng.pool.assert_empty()


# ----------------------------------------------------------------------
# smoke (the tier-1 wiring, scenario_smoke pattern)

def test_prefix_smoke_inprocess():
    from tools import prefix_smoke
    assert prefix_smoke.run() == 0

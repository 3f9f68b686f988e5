"""Decode economics (ISSUE 16): block-paged KV cache with
copy-on-write prefix sharing, speculative decoding, and the int8
decode path.

The acceptance contracts pinned here:

* paged decode is BIT-identical to the dense slot cache (greedy and
  sampled), over one compiled decode signature (page faults, ragged
  arrivals, and speculative steps never retrace);
* prefix-shared prompts store their prefill pages once, cohabitants
  stay bit-identical through wedges/cancels/releases, and refcounts
  prove who holds what;
* cancel and mid-stream deadline release KV pages in the SAME
  scheduler tick (drain reports ``kv_pages_owed == 0`` under load);
* speculation is pure upside: greedy AND sampled output bit-identical
  to non-speculative decode whatever the drafts, with the acceptance
  ratio/counters exposed;
* the int8 artifact pass quantizes decode matmul weights per channel
  with bounded reconstruction error, inside the same single decode
  executable.
"""

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core import chaos, health
from paddle1_tpu.core.errors import InvalidArgumentError
from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.serving import (PARKING_PAGE, CausalLM, GenerationEngine,
                                 GenerationServer, KVPageAccountingError,
                                 KVPoolExhausted, NGramSpeculator,
                                 PagePool, SlotWedged)
from paddle1_tpu.serving.speculate import DraftModelSpeculator

VOCAB, MAX_SEQ, SLOTS, PS = 32, 64, 4, 8


@pytest.fixture(autouse=True)
def _isolate():
    health.reset()
    chaos.reset()
    yield
    health.reset()
    chaos.reset()


@pytest.fixture(scope="module")
def lm():
    paddle.seed(7)
    return CausalLM(vocab_size=VOCAB, d_model=16, nhead=2,
                    dim_feedforward=32, num_layers=2, max_seq=MAX_SEQ)


@pytest.fixture(scope="module")
def dense(lm):
    return GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                            prefill_buckets=(8, 24))


@pytest.fixture(scope="module")
def paged(lm):
    return GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                            prefill_buckets=(8, 24), paged=True,
                            page_size=PS, prefix_cache=8)


def _run(eng, slot, prompt, steps, temperature=0.0, top_k=0, seed=1):
    """prefill + ``steps`` single-slot decode steps -> token list."""
    out = [eng.prefill(slot, np.asarray(prompt, np.int32),
                       temperature, top_k, seed)]
    active = np.zeros([eng.slots], bool)
    active[slot] = True
    for _ in range(steps):
        toks, flags = eng.decode(active)
        out.append(int(toks[slot, 0]))
    eng.release(slot)
    return out


# ---------------------------------------------------------------------------
# page pool (host accounting unit)


class TestPagePool:
    def test_parking_page_reserved(self):
        pool = PagePool(4, PS)
        assert PARKING_PAGE not in pool.alloc(3)
        with pytest.raises(KVPoolExhausted, match="exhausted"):
            pool.alloc(1)

    def test_refcount_release_roundtrip(self):
        pool = PagePool(5, PS)
        pages = pool.alloc(2)
        pool.retain(pages)
        pool.release(pages)
        assert pool.pages_in_use == 2      # still held once
        pool.release(pages)
        assert pool.pages_in_use == 0 and pool.free_pages == 4

    def test_over_release_is_an_accounting_bug(self):
        # the double-release guard raises TYPED, and BEFORE mutating:
        # a page appended to the free list twice would be handed to two
        # holders and silently cross-write their KV
        pool = PagePool(3, PS)
        [p] = pool.alloc(1)
        pool.release([p])
        with pytest.raises(KVPageAccountingError, match="over-released"):
            pool.release([p])
        # the failed release corrupted nothing: the free list still
        # holds the page exactly once and the invariants all pass
        assert pool.free_pages == 2 and pool.pages_in_use == 0
        pool.check_invariants()

    def test_prefix_registry_hit_and_refs(self):
        pool = PagePool(8, 4, prefix_entries=4)
        prompt = np.arange(9, dtype=np.int32)     # 2 full pages + 1
        chain = pool.alloc(3)
        pool.register_prefix(prompt, chain)
        hit = pool.lookup_prefix(np.concatenate(
            [prompt[:8], [30, 31]]).astype(np.int32))
        assert hit == chain[:2]                    # full pages only
        # holders now: slot(1) + registry(len-1 and len-2 chains) + hit
        assert pool.refcount(chain[0]) == 4
        assert pool.refcount(chain[2]) == 1        # tail never shared

    def test_lru_eviction_under_pressure(self):
        pool = PagePool(4, 2, prefix_entries=8)
        a = pool.alloc(2)
        pool.register_prefix(np.array([1, 2], np.int32), a[:1])
        pool.register_prefix(np.array([3, 4], np.int32), a[1:])
        pool.release(a)                            # only registry holds
        got = pool.alloc(3)                        # forces both evicted
        assert len(got) == 3 and pool.stats()["evictions"] == 2

    def test_needs_room_for_parking(self):
        with pytest.raises(ValueError, match="parking"):
            PagePool(1, PS)


class TestInvariantChecker:
    """``check_invariants`` (FLAGS_debug_kv_refcount's engine): the
    refcount ledger must equal registry + holder chains exactly, and
    every way it can lie raises typed."""

    def test_clean_pool_passes(self):
        pool = PagePool(8, 4, prefix_entries=4)
        pool.check_invariants()
        chain = pool.alloc(3)
        prompt = np.arange(9, dtype=np.int32)
        pool.register_prefix(prompt, chain)
        pool.check_invariants(holders=[chain])
        pool.release(chain)                 # slot's refs gone
        pool.check_invariants()             # registry still holds 1..2

    def test_unreported_holder_raises(self):
        # pages held by a slot the caller didn't report = the ledger
        # and reality disagree — typed, with the page named
        pool = PagePool(6, 4)
        chain = pool.alloc(2)
        with pytest.raises(KVPageAccountingError, match="refcount"):
            pool.check_invariants()         # holders omitted
        pool.check_invariants(holders=[chain])

    def test_corrupt_free_list_raises(self):
        pool = PagePool(6, 4)
        pool.alloc(2)
        pool._free.append(pool._free[0])    # simulate a double-free
        with pytest.raises(KVPageAccountingError, match="duplicate"):
            pool.check_invariants()

    def test_parking_page_leak_raises(self):
        pool = PagePool(6, 4)
        pool._free.append(PARKING_PAGE)
        with pytest.raises(KVPageAccountingError, match="parking"):
            pool.check_invariants()


class TestCOWRegistryLifecycle:
    """Eviction vs live holders — the copy-on-write registry's whole
    lifecycle matrix: an entry evicted while its pages are SHARED must
    keep them alive for the current holders, and only the LAST release
    returns them to the free list."""

    def test_evicted_while_shared_keeps_pages_for_holders(self):
        pool = PagePool(8, 4, prefix_entries=2)
        prompt = np.arange(8, dtype=np.int32)      # 2 full pages
        chain = pool.alloc(2)
        pool.register_prefix(prompt, chain)
        # a second "request" comes in over the same prefix
        held = pool.lookup_prefix(prompt)
        assert held == chain
        # evict everything the registry holds (pressure simulation)
        while pool._evict_one():
            pass
        assert pool.stats()["prefix_entries"] == 0
        # the holder's pages survived the eviction: refcounts are the
        # holder chains only (original alloc + lookup retain)
        for p in chain:
            assert pool.refcount(p) == 2
        pool.check_invariants(holders=[chain, held])
        # a NEW lookup misses (the registry forgot the prefix)...
        assert pool.lookup_prefix(prompt) == []
        # ...but the live streams keep decoding on their pages
        pool.release(held)
        for p in chain:
            assert pool.refcount(p) == 1           # still alive
        assert pool.free_pages == 5
        pool.release(chain)                        # LAST holder out
        assert pool.free_pages == 7                # only now reaped
        pool.check_invariants()

    def test_release_order_is_irrelevant(self):
        # same matrix, releases interleaved the other way round:
        # registry evicts LAST, after both holders released
        pool = PagePool(8, 4, prefix_entries=2)
        prompt = np.arange(8, dtype=np.int32)
        chain = pool.alloc(2)
        pool.register_prefix(prompt, chain)
        held = pool.lookup_prefix(prompt)
        pool.release(chain)
        pool.release(held)
        # only the registry holds the pages now — they are CACHED, not
        # free, and a hit revives them without allocation
        assert pool.free_pages == 5
        assert pool.stats()["pages_cached"] == 2
        revived = pool.lookup_prefix(prompt)
        assert revived == chain
        pool.release(revived)
        while pool._evict_one():
            pass
        assert pool.free_pages == 7                # reaped on last ref
        pool.check_invariants()


# ---------------------------------------------------------------------------
# paged <-> dense parity (the tentpole gate)


class TestPagedParity:
    # prompt lengths straddle the page boundary: P % page_size == 0 is
    # the all-pages-full edge where the first decode write must land in
    # a freshly faulted page
    @pytest.mark.parametrize("plen", [3, PS - 1, PS, PS + 3, 2 * PS])
    def test_greedy_bit_identical(self, dense, paged, plen):
        prompt = (np.arange(plen) % VOCAB).astype(np.int32)
        assert _run(dense, 0, prompt, 12) == _run(paged, 0, prompt, 12)

    @pytest.mark.parametrize("temp,top_k", [(0.8, 5), (1.3, 0)])
    def test_sampled_bit_identical(self, dense, paged, temp, top_k):
        prompt = np.array([5, 1, 9, 2, 7], np.int32)
        a = _run(dense, 1, prompt, 10, temp, top_k, seed=11)
        b = _run(paged, 1, prompt, 10, temp, top_k, seed=11)
        assert a == b

    def test_one_decode_compile_across_faults_and_ragged(self, paged):
        before = paged.decode_compile_count
        # long decode crosses page boundaries (faults), then a second
        # ragged arrival joins mid-flight — same executable throughout
        p1 = paged.prefill(0, np.array([1, 2, 3], np.int32), 0.0, 0, 1)
        active = np.array([True, False, False, False])
        for _ in range(PS + 2):
            paged.decode(active)
        paged.prefill(2, (np.arange(17) % VOCAB).astype(np.int32),
                      0.7, 4, 5)
        active[2] = True
        for _ in range(4):
            paged.decode(active)
        paged.release(0)
        paged.release(2)
        assert paged.decode_compile_count == max(before, 1) == 1
        assert p1 is not None

    def test_kernel_vs_ref_routing(self, lm):
        # the Pallas gather (interpret mode on CPU) and the XLA take
        # composition agree numerically on the same pools
        import jax
        from paddle1_tpu.ops.pallas import paged_attention as pa
        k = jax.random.split(jax.random.key(0), 4)
        S, W, H, D, NP = 3, 1, 2, 8, 5
        q = jax.random.normal(k[0], (S, W, H, D), "float32")
        kp = jax.random.normal(k[1], (NP, H, PS, D), "float32")
        vp = jax.random.normal(k[2], (NP, H, PS, D), "float32")
        table = np.array([[1, 2], [3, 0], [4, 1]], np.int32)
        base = np.array([9, 5, 12], np.int32)
        ref = pa.paged_attention_ref(q, kp, vp, table, base)
        assert pa.supported(q.shape, kp.shape)
        out = pa.paged_attention(q, kp, vp, table, base)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_engine_decodes_through_the_kernel(self, lm, paged):
        # the whole paged engine — pool writes in the heads-first
        # layout, prefill scatter, decode gather — through the Pallas
        # arm (interpret mode) gives the tokens of the XLA arm
        prompt = (np.arange(11) % VOCAB).astype(np.int32)
        want = _run(paged, 1, prompt, PS + 3, temperature=0.7, top_k=4)
        with flags_guard(pallas_paged_attention="always"):
            kern = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                                    prefill_buckets=(8, 24), paged=True,
                                    page_size=PS, prefix_cache=8)
            got = _run(kern, 1, prompt, PS + 3, temperature=0.7, top_k=4)
        assert got == want

    def test_paged_needs_paged_cache_contract(self):
        class NoPaged:
            def gen_slot_cache(self, *a, **k):
                raise NotImplementedError
        with pytest.raises(InvalidArgumentError, match="gen_paged_cache"):
            GenerationEngine(NoPaged(), slots=2, max_seq=8, paged=True)


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing


class TestPrefixSharing:
    PREFIX = (np.arange(2 * PS) % VOCAB).astype(np.int32)

    def test_shared_prefill_pages_stored_once(self, lm, dense):
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               prefill_buckets=(24,), paged=True,
                               page_size=PS, prefix_cache=8)
        pA = np.concatenate([self.PREFIX, [7, 9]]).astype(np.int32)
        pB = np.concatenate([self.PREFIX, [11, 3]]).astype(np.int32)
        tA = eng.prefill(0, pA, 0.0, 0, 1)
        in_use_after_A = eng.pool.stats()["pages_in_use"]
        tB = eng.prefill(1, pB, 0.0, 0, 2)
        st = eng.pool.stats()
        # B reused both full prefix pages; only its private tail page
        # is new
        assert st["prefix_hit_pages"] == 2
        assert st["pages_in_use"] == in_use_after_A + 1
        shared = eng._slot_pages[0][:2]
        assert eng._slot_pages[1][:2] == shared
        assert eng._slot_pages[1][2] != eng._slot_pages[0][2]
        # both cohabitants bit-identical to the dense oracle
        seq = {0: [tA], 1: [tB]}
        for _ in range(6):
            toks, _ = eng.decode(np.array([True, True, False, False]))
            seq[0].append(int(toks[0, 0]))
            seq[1].append(int(toks[1, 0]))
        assert seq[0] == _run(dense, 0, pA, 6)
        assert seq[1] == _run(dense, 1, pB, 6)
        # releasing A leaves B + the registry holding the prefix
        eng.release(0)
        for p in shared:
            assert eng.pool.refcount(p) >= 2
        before = seq[1][-1]
        toks, _ = eng.decode(np.array([False, True, False, False]))
        assert toks.shape[0] == SLOTS and before is not None
        eng.release(1)

    def test_wedge_during_shared_prefix_decode(self, lm):
        # satellite: chaos wedge while two requests share prefix pages
        # — the survivor stays bit-identical AND the wedged slot's page
        # refs drop the same tick
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               prefill_buckets=(24,), paged=True,
                               page_size=PS, prefix_cache=8)
        prompt = list(self.PREFIX[:12])
        srv = GenerationServer(eng, token_budget=12).start()
        ref = srv.submit(prompt + [7], max_new_tokens=10).result(
            timeout=120)
        srv.drain()
        chaos.configure("gen_slot_wedge@3:1")
        srv = GenerationServer(eng, token_budget=12).start()
        a = srv.submit(prompt + [7], max_new_tokens=10)   # slot 0
        b = srv.submit(prompt + [9], max_new_tokens=10)   # slot 1: wedged
        got_a = a.result(timeout=120)
        with pytest.raises(SlotWedged):
            b.result(timeout=120)
        rep = srv.drain()
        assert got_a == ref                 # cohabitant bit-identical
        assert eng._slot_pages[1] == []     # wedged slot's pages gone
        assert rep["kv_pages_owed"] == 0
        assert rep["unaccounted"] == 0

    def test_warmup_does_not_pollute_prefix_registry(self, lm):
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, prefix_cache=8)
        eng.warm_up()
        st = eng.pool.stats()
        assert st["prefix_entries"] == 0 and st["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# page lifecycle: cancel / deadline / exhaustion / drain


class TestPageLifecycle:
    def test_cancel_releases_pages_same_tick(self, lm):
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, prefix_cache=0)
        srv = GenerationServer(eng, token_budget=60).start()
        st = srv.submit([1, 2, 3], max_new_tokens=60)
        it = iter(st)
        next(it)
        assert eng.pool.stats()["pages_in_use"] > 0
        st.cancel()
        with pytest.raises(Exception):
            st.result(timeout=120)
        rep = srv.drain()
        # release happened in the tick that retired the stream — by
        # drain time nothing is owed and the slot chain is empty
        assert eng._slot_pages[0] == []
        assert eng.pool.stats()["pages_in_use"] == 0
        assert rep["kv_pages_owed"] == 0

    def test_deadline_midstream_releases_pages(self, lm):
        from paddle1_tpu.serving import DeadlineExceeded
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, prefix_cache=0)
        chaos.configure("gen_slow_step@2")
        with flags_guard(serve_chaos_slow_s=0.4):
            srv = GenerationServer(eng, token_budget=100).start()
            st = srv.submit([1, 2], max_new_tokens=100, deadline_ms=150)
            with pytest.raises(DeadlineExceeded, match="mid-stream"):
                st.result(timeout=120)
            rep = srv.drain()
        assert eng._slot_pages[0] == []
        assert rep["kv_pages_owed"] == 0
        assert rep["deadline_failed"] == 1

    def test_prefill_pool_exhaustion_typed(self, lm):
        # 3 usable pages, prompts need 2 each: the second admit fails
        # typed and the first request is untouched
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(16,), paged=True,
                               page_size=PS, pages=4, prefix_cache=0)
        p = (np.arange(2 * PS - 2) % VOCAB).astype(np.int32)
        eng.prefill(0, p, 0.0, 0, 1)
        with pytest.raises(KVPoolExhausted, match="exhausted"):
            eng.prefill(1, (p + 1) % VOCAB, 0.0, 0, 2)
        assert eng._slot_pages[1] == []    # nothing half-claimed
        eng.release(0)
        assert eng.pool.stats()["pages_in_use"] == 0

    def test_decode_page_fault_exhaustion_fails_only_that_slot(self, lm):
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, pages=4, prefix_cache=0)
        # slot 0: 6 prompt tokens (1 page); slot 1: 7 (1 page); one
        # spare page — the first slot to fault claims it, the next
        # fault finds the pool dry
        t0 = eng.prefill(0, np.arange(6, dtype=np.int32), 0.0, 0, 1)
        t1 = eng.prefill(1, np.arange(7, dtype=np.int32), 0.0, 0, 2)
        active = np.array([True, True])
        faulted = None
        for _ in range(2 * PS):
            toks, flags = eng.decode(active)
            if eng.last_page_faults:
                faulted = dict(eng.last_page_faults)
                break
        assert faulted is not None
        (slot, exc), = faulted.items()
        assert isinstance(exc, KVPoolExhausted)
        # the faulted slot produced nothing that step; the other did
        assert not flags[slot].any()
        other = 1 - slot
        assert flags[other].any()
        assert t0 is not None and t1 is not None
        eng.release(0)
        eng.release(1)

    def test_prefill_failure_releases_shared_prefix_refs(self, lm):
        # exception-path audit: _alloc_prefill_pages retains shared
        # prefix pages BEFORE allocating private ones — when the
        # private alloc raises, the retained refs must be handed back
        # (exactly what was taken), or the prefix pages leak a ref per
        # failed admission forever
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8, 40), paged=True,
                               page_size=PS, pages=4, prefix_cache=4)
        shared = (np.arange(PS) % VOCAB).astype(np.int32)  # 1 full page
        eng.prefill(0, shared, 0.0, 0, 1)
        eng.release(0)                     # page survives in registry
        assert eng.pool.stats()["pages_cached"] == 1
        # same prefix + a long tail: hits the cached page (one ref
        # RETAINED for the slot), then needs 3 private pages from a
        # pool with 2 free — the private alloc raises, and the retained
        # prefix ref must be handed back
        big = np.concatenate([shared,
                              (np.arange(3 * PS) + 3) % VOCAB]
                             ).astype(np.int32)
        with pytest.raises(KVPoolExhausted):
            eng.prefill(1, big, 0.0, 0, 2)
        assert eng._slot_pages[1] == []    # nothing half-claimed
        # every ref the failed admission took was released — a leaked
        # retain would leave pages_in_use > 0 with no holder, which the
        # invariant sweep (refcounts == registry + slot chains) catches
        assert eng.pool.stats()["pages_in_use"] == 0
        eng.check_kv_invariants()

    def test_debug_refcount_asserted_every_scheduler_tick(self, lm):
        # FLAGS_debug_kv_refcount: the scheduler sweeps the invariant
        # checker after EVERY tick — admissions, releases, prefix hits
        # and drains all run under it without tripping
        with flags_guard(debug_kv_refcount=True):
            eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                                   prefill_buckets=(8,), paged=True,
                                   page_size=PS, prefix_cache=4)
            srv = GenerationServer(eng, queue_depth=16, token_budget=6)
            srv.start()
            streams = [srv.submit([1 + i % 3, 2, 3], max_new_tokens=6)
                       for i in range(6)]
            rep = srv.drain(timeout=120)
        assert all(s.done() for s in streams)
        assert rep["fatal"] is None        # a checker trip kills the loop
        assert rep["unaccounted"] == 0 and rep["kv_pages_owed"] == 0

    def test_drain_under_load_owes_no_pages(self, lm):
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, prefix_cache=4)
        srv = GenerationServer(eng, queue_depth=64, token_budget=5)
        srv.start()
        streams = [srv.submit([1 + i % 5, 2], max_new_tokens=5)
                   for i in range(10)]
        rep = srv.drain(timeout=120)
        assert all(s.done() for s in streams)
        assert rep["kv_pages_owed"] == 0
        assert rep["unaccounted"] == 0 and rep["tokens_owed"] == 0

    def test_oversize_prompt_margin_typed(self, lm):
        eng = GenerationEngine(lm, slots=2, max_seq=16, spec_tokens=3)
        with pytest.raises(InvalidArgumentError, match="margin"):
            eng.prefill(0, np.arange(13, dtype=np.int32), 0.0, 0, 1)


# ---------------------------------------------------------------------------
# HBM census coverage (satellite: the page pool is accounted)


class TestCensusCoverage:
    def test_kv_subsystem_covers_page_pool(self, lm):
        from paddle1_tpu.obs import hbm as obs_hbm
        obs_hbm.reset()
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), paged=True,
                               page_size=PS, prefix_cache=0)
        per = obs_hbm.registered_bytes()
        pool_bytes = sum(
            k.size * k.dtype.itemsize + v.size * v.dtype.itemsize
            for k, v in eng._kv)
        assert per["kv_cache"] >= pool_bytes
        assert per["params"] > 0
        obs_hbm.reset()

    def test_census_coverage_with_paged_engine_subprocess(self, tmp_path):
        # a clean process where the ONLY device state is the paged
        # engine: census coverage must be complete (the page pools and
        # table are registered, not leaked into unaccounted bytes)
        import os
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        code = (
            "import sys\n"
            f"sys.path.insert(0, {root!r})\n"
            "import numpy as np\n"
            "import paddle1_tpu as paddle\n"
            "from paddle1_tpu.obs import hbm\n"
            "from paddle1_tpu.serving import CausalLM, GenerationEngine\n"
            "paddle.seed(0)\n"
            "lm = CausalLM(vocab_size=32, d_model=16, nhead=2,\n"
            "              num_layers=2, max_seq=64)\n"
            "eng = GenerationEngine(lm, slots=2, max_seq=64,\n"
            "                       prefill_buckets=(8,), paged=True,\n"
            "                       page_size=8)\n"
            "eng.prefill(0, np.arange(5, dtype=np.int32), 0.0, 0, 1)\n"
            "eng.decode(np.array([True, False]))\n"
            "c = hbm.census()\n"
            "print('COVERAGE', c['coverage_ratio'])\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        cov = float(r.stdout.split("COVERAGE")[1].split()[0])
        assert cov >= 0.95, (cov, r.stdout)


# ---------------------------------------------------------------------------
# speculative decoding


def _spec_run(eng, prompt, steps, temperature=0.0, top_k=0, seed=1):
    """prefill + n-gram speculative decode on slot 0 until ``steps``
    generated tokens -> (token list, dispatch count)."""
    out = [eng.prefill(0, prompt, temperature, top_k, seed)]
    sp = NGramSpeculator(prompt, eng.spec_tokens, n=3)
    sp.observe(out[0])
    active = np.array([True, False])
    dispatches = 0
    while len(out) < steps + 1:
        d = sp.propose()
        drafts = np.zeros([2, eng.spec_tokens], np.int32)
        nd = np.zeros([2], np.int32)
        nd[0] = d.size
        drafts[0, :d.size] = d
        toks, flags = eng.decode(active, drafts, nd)
        dispatches += 1
        for i in range(int(flags[0].sum())):
            sp.observe(int(toks[0, i]))
            out.append(int(toks[0, i]))
    eng.release(0)
    return out[:steps + 1], dispatches


class TestSpeculation:
    PROMPT = np.array([1, 2, 3, 4] * 3, np.int32)

    @pytest.fixture(scope="class")
    def spec(self, lm):
        return GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                                prefill_buckets=(16,), spec_tokens=4)

    def _spec_run(self, eng, prompt, steps, temperature=0.0, top_k=0,
                  seed=1):
        return _spec_run(eng, prompt, steps, temperature, top_k, seed)

    def test_greedy_bit_identical_to_nonspec(self, dense, spec):
        ref = _run(dense, 0, self.PROMPT, 15)
        got, _ = self._spec_run(spec, self.PROMPT, 15)
        assert got == ref

    @pytest.mark.parametrize("temp,top_k", [(0.8, 5), (1.2, 0)])
    def test_sampled_bit_identical_to_nonspec(self, dense, spec, temp,
                                              top_k):
        # stronger than a distribution test: the per-request key
        # schedule advances per ACCEPTED token, so even sampled output
        # is bit-equal whatever the speculator proposed
        ref = _run(dense, 0, self.PROMPT, 12, temp, top_k, seed=9)
        got, _ = self._spec_run(spec, self.PROMPT, 12, temp, top_k,
                                seed=9)
        assert got == ref

    def test_wrong_drafts_cost_nothing_but_width(self, dense, spec):
        # adversarial speculator: propose garbage every step — output
        # must STILL match non-speculative decode exactly
        ref = _run(dense, 0, self.PROMPT, 8)
        out = [spec.prefill(0, self.PROMPT, 0.0, 0, 1)]
        drafts = np.full([2, 4], VOCAB - 1, np.int32)
        nd = np.array([4, 0], np.int32)
        while len(out) < 9:
            toks, flags = spec.decode(np.array([True, False]),
                                      drafts, nd)
            for i in range(int(flags[0].sum())):
                out.append(int(toks[0, i]))
        spec.release(0)
        assert out[:9] == ref

    def test_repetitive_arm_accepts_and_compresses_dispatches(self):
        # the economics arm: on cyclic text the n-gram speculator's
        # acceptance clears 70% and dispatches collapse by > 1.8x
        paddle.seed(7)
        lm = CausalLM(vocab_size=VOCAB, d_model=16, nhead=2,
                      num_layers=2, max_seq=256)
        for _, t in lm.state_dict().items():
            t._data = t.data * 0          # degenerate fixed point:
        eng = GenerationEngine(lm, slots=2, max_seq=256,  # cyclic output
                               prefill_buckets=(16,), spec_tokens=4)
        prompt = np.array([1, 2, 3, 4] * 3, np.int32)
        out, dispatches = self._spec_run(eng, prompt, 60)
        # 60 tokens in far fewer than 60 dispatches
        assert dispatches <= 60 / 1.8
        assert len(set(out[4:])) == 1      # the cycle the drafts rode
        assert eng.decode_compile_count == 1

    def test_spec_metrics_via_server(self, lm):
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(16,), spec_tokens=4)
        srv = GenerationServer(eng, token_budget=12).start()
        got = srv.submit(list(self.PROMPT),
                         max_new_tokens=12).result(timeout=120)
        snap = srv.metrics.snapshot()
        rep = srv.drain()
        assert len(got) == 12
        c = snap["counters"]
        assert c.get("gen_spec_proposed_total", 0) > 0
        assert "gen_spec_accept_ratio" in snap["gauges"]
        assert rep["decode_compiles"] == 1

    def test_draft_model_speculator_protocol(self):
        sp = DraftModelSpeculator([1, 2, 3], 3,
                                  lambda hist, k: hist[-1:] * k)
        sp.observe(9)
        assert list(sp.propose()) == [9, 9, 9]

    def test_ngram_prefers_full_window(self):
        sp = NGramSpeculator([7, 7, 7, 7, 7, 7, 7, 7], 4, n=3)
        assert list(sp.propose()) == [7, 7, 7, 7]
        fresh = NGramSpeculator([1, 2, 3], 4, n=3)
        assert fresh.propose().size == 0

    def test_window_margin_validated(self, lm):
        with pytest.raises(InvalidArgumentError, match="window"):
            GenerationEngine(lm, slots=2, max_seq=4, spec_tokens=4)


@pytest.mark.slow
class TestSpeculationParityMatrix:
    """CI generate-lane matrix (ISSUE 16 satellite): speculation is
    pure upside across every sampling mode x window width — greedy
    EXACT, and sampled exact too (the per-request key schedule advances
    per ACCEPTED token, so even temperature/top-k chains are bit-equal
    to non-speculative decode), all over one compiled signature."""

    PROMPT = np.array([1, 2, 3, 4] * 3, np.int32)
    CASES = [(0.0, 0, 1), (0.0, 0, 7), (0.7, 4, 3), (0.7, 0, 11),
             (1.0, 8, 5), (1.3, 3, 2)]

    @pytest.fixture(scope="class")
    def engines(self, lm):
        return {k: GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                                    prefill_buckets=(16,),
                                    spec_tokens=k) for k in (2, 4)}

    @pytest.mark.parametrize("k", [2, 4])
    @pytest.mark.parametrize("temp,top_k,seed", CASES)
    def test_parity(self, dense, engines, k, temp, top_k, seed):
        ref = _run(dense, 0, self.PROMPT, 14, temp, top_k, seed)
        got, _ = _spec_run(engines[k], self.PROMPT, 14, temp, top_k,
                           seed)
        assert got == ref
        assert engines[k].decode_compile_count == 1

    @pytest.mark.parametrize("temp,top_k,seed", [(0.0, 0, 1),
                                                 (0.9, 6, 4)])
    def test_parity_with_paged_kv(self, lm, dense, temp, top_k, seed):
        # the full economics stack: speculation over the paged cache
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(16,), paged=True,
                               page_size=PS, spec_tokens=4)
        ref = _run(dense, 0, self.PROMPT, 14, temp, top_k, seed)
        got, _ = _spec_run(eng, self.PROMPT, 14, temp, top_k, seed)
        assert got == ref
        assert eng.decode_compile_count == 1
        st = eng.pool.stats()     # owed == 0 (prefix cache stays warm)
        assert st["pages_in_use"] == st["pages_cached"]


# ---------------------------------------------------------------------------
# int8 decode path


class TestInt8Decode:
    def test_quantize_reconstruction_bounded(self):
        from paddle1_tpu.quantization import (dequantize_weights,
                                              quantize_weights_int8)
        rng = np.random.default_rng(0)
        w = rng.standard_normal((32, 16)).astype(np.float32)
        params = {"layers.0.fc.weight": w,
                  "embed.weight": rng.standard_normal(
                      (8, 4)).astype(np.float32),
                  "layers.0.fc.bias": np.zeros(16, np.float32)}
        q = quantize_weights_int8(params)
        from paddle1_tpu.quantization import QuantTensor
        assert isinstance(q["layers.0.fc.weight"], QuantTensor)
        assert not isinstance(q["embed.weight"], QuantTensor)  # skipped
        assert not isinstance(q["layers.0.fc.bias"], QuantTensor)
        deq = dequantize_weights(q)
        scale = np.asarray(q["layers.0.fc.weight"].scale)
        err = np.abs(np.asarray(deq["layers.0.fc.weight"]) - w)
        # per-channel rounding bound: half a quantization step
        assert (err <= 0.5 * scale[None, :] + 1e-7).all()

    def test_int8_engine_greedy_matches_f32(self, lm, dense):
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), int8=True)
        prompt = np.array([3, 1, 4, 1, 5], np.int32)
        assert _run(eng, 0, prompt, 10) == _run(dense, 0, prompt, 10)
        assert eng.decode_compile_count == 1

    def test_int8_halves_weight_bytes(self, lm):
        from paddle1_tpu.quantization import QuantTensor
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(8,), int8=True)
        quant = [v for v in eng._params.values()
                 if isinstance(v, QuantTensor)]
        assert quant, "no decode matmul weights were quantized"
        q_bytes = sum(v.q.size + v.scale.size * 4 for v in quant)
        f_bytes = sum(v.q.size * 4 for v in quant)
        assert q_bytes < 0.5 * f_bytes

    def test_int8_with_paging_and_spec_composes(self, lm, dense):
        # the full decode-economics stack in ONE signature
        eng = GenerationEngine(lm, slots=2, max_seq=MAX_SEQ,
                               prefill_buckets=(16,), paged=True,
                               page_size=PS, spec_tokens=2, int8=True)
        prompt = np.array([1, 2, 3, 4] * 3, np.int32)
        ref = _run(dense, 0, prompt, 10)
        out = [eng.prefill(0, prompt, 0.0, 0, 1)]
        while len(out) < 11:
            toks, flags = eng.decode(np.array([True, False]))
            for i in range(int(flags[0].sum())):
                out.append(int(toks[0, i]))
        assert out[:11] == ref
        assert eng.decode_compile_count == 1

    def test_quant_tensor_is_a_pytree(self):
        import jax
        from paddle1_tpu.quantization import QuantTensor
        import jax.numpy as jnp
        qt = QuantTensor(jnp.zeros((4, 2), jnp.int8),
                         jnp.ones((2,), jnp.float32))
        leaves, treedef = jax.tree_util.tree_flatten(qt)
        assert len(leaves) == 2
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(back, QuantTensor)

    def test_int8_linear_module_pass(self):
        from paddle1_tpu import nn
        from paddle1_tpu.core.tensor import to_tensor
        from paddle1_tpu.quantization import Int8Linear, quantize_decode

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.embed_fc = nn.Linear(4, 8)
                self.head = nn.Linear(8, 4)

            def forward(self, x):
                return self.head(self.embed_fc(x))

        m = M()
        x = to_tensor(np.random.default_rng(1).standard_normal(
            (2, 4)).astype(np.float32))
        ref = m(x).numpy()
        quantize_decode(m, skip=("embed",))
        assert isinstance(m.head, Int8Linear)
        assert not isinstance(m.embed_fc, Int8Linear)
        got = m(x).numpy()
        np.testing.assert_allclose(got, ref, atol=0.1)

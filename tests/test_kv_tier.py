"""Host KV page tier (ISSUE 9 tentpole): HostPageTier residency state
machine, the transfer clock's retry/backoff/timeout model, spill-based
preemption with bitwise-identical resume, prefetch-ahead (zero stalls),
the degradation ladder (resume-in-place / continuation re-queue), the
warm-prefix spill/fetch path, and pcie chaos parity through the gateway."""
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.serve import tier as tier_mod
from repro.serve.engine import Request, ServeEngine
from repro.serve.fault import ServeFaultInjector, TierFaultAdapter
from repro.serve.gateway import Gateway
from repro.serve.tier import (NullFaultHook, TierConfig, TieredServeEngine,
                              TransferClock, pad_pages, trim_pages)


@pytest.fixture(scope="module")
def cfg():
    return smoke_config(get_config("qwen3-14b"))


@pytest.fixture(scope="module")
def shared_params(cfg):
    return ServeEngine(cfg, slots=1, max_len=32, seed=0).params


def _mk(cfg, params, *, pool=16, slots=2, max_len=64, host=48, quantum=4,
        tier_kw=None, **kw):
    """Tiered engine at the bench sizing: a 2-slot device pool that holds
    exactly two full requests, host tier 3x that."""
    tc = TierConfig(quantum=quantum, **(tier_kw or {}))
    return TieredServeEngine(cfg, params=params, slots=slots,
                             max_len=max_len, seed=0, chunk=4, paged=True,
                             page_size=8, pool_pages=pool,
                             page_storage="bf16", prefill_chunk=8,
                             host_tier_pages=host, tier_config=tc, **kw)


def _mk_flat(cfg, params, *, pool=16, slots=2, max_len=64, **kw):
    """Untiered reference engine (PR 8 scheduler) on the same pool."""
    return ServeEngine(cfg, params=params, slots=slots, max_len=max_len,
                       seed=0, chunk=4, paged=True, page_size=8,
                       pool_pages=pool, page_storage="bf16",
                       prefill_chunk=8, **kw)


def _reqs(n=10, max_new=24, seed0=0):
    rng = np.random.default_rng(7)
    return [Request(rid, rng.integers(1, 500, size=9 + rid).astype(np.int32),
                    max_new=max_new, seed=seed0 + rid) for rid in range(n)]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return [r.out for r in reqs]


def _payload(rng, pages=3):
    return {"x": rng.random((2, pages, 4)).astype(np.float32),
            "s": rng.random((1, pages, 4, 2)).astype(np.float32)}


# ---------------------------------------------------------------------------
# HostPageTier unit: state machine, capacity, prefix LRU, CRCs
# ---------------------------------------------------------------------------


class TestHostPageTier:
    def test_residency_cycle(self):
        rng = np.random.default_rng(0)
        tier = tier_mod.HostPageTier(8)
        pay = _payload(rng)
        crcs = tier_mod.payload_page_crcs(pay, 3)
        aux = {"pos": np.arange(4)}
        eid = tier.reserve(3)
        assert eid is not None and tier.state(eid) == tier_mod.TIER_SPILLING
        assert tier.used_pages() == 3 and tier.free_pages() == 5
        tier.commit(eid, pay, aux, crcs, tier_mod.payload_crc(aux))
        assert tier.state(eid) == tier_mod.TIER_HOST
        ent = tier.begin_fetch(eid)
        assert tier.state(eid) == tier_mod.TIER_FETCHING
        assert tier_mod.payload_page_crcs(ent.payload, 3) == crcs
        tier.abort_fetch(eid)              # failed fetch keeps the copy
        assert tier.state(eid) == tier_mod.TIER_HOST
        tier.begin_fetch(eid)
        tier.free(eid)                     # fetch landed -> back to DEVICE
        assert tier.entries() == 0 and tier.used_pages() == 0

    def test_illegal_transitions_raise(self):
        rng = np.random.default_rng(1)
        tier = tier_mod.HostPageTier(8)
        pay = _payload(rng)
        crcs = tier_mod.payload_page_crcs(pay, 3)
        eid = tier.reserve(3)
        with pytest.raises(ValueError, match="expected"):
            tier.begin_fetch(eid)          # SPILLING -> FETCHING illegal
        tier.commit(eid, pay, None, crcs, 0)
        with pytest.raises(ValueError, match="expected"):
            tier.commit(eid, pay, None, crcs, 0)   # double commit
        with pytest.raises(KeyError):
            tier.state(99)
        with pytest.raises(ValueError, match="CRCs"):
            e2 = tier.reserve(3)
            tier.commit(e2, pay, None, crcs[:2], 0)

    def test_reserve_evicts_prefix_lru_first(self):
        rng = np.random.default_rng(2)
        tier = tier_mod.HostPageTier(4)
        for i in range(4):
            pg = _payload(rng, pages=1)
            assert tier.put_prefix(bytes([i]), pg, tier_mod.payload_crc(pg))
        assert tier.free_pages() == 0
        eid = tier.reserve(3)              # squeezes 3 oldest prefix pages
        assert eid is not None
        assert tier.prefix_evictions == 3
        assert tier.prefix_pages() == 1 and tier.prefix_run([b"\x03"]) == 1
        assert tier.reserve(2) is None     # 3 slot + 1 evictable < 2 free
        assert tier.reserve(99) is None    # never fits
        # slot entries are never evicted for prefix pages
        pg = _payload(rng, pages=1)
        assert tier.put_prefix(b"new", pg, tier_mod.payload_crc(pg))
        assert tier.prefix_run([b"\x03"]) == 0   # it paid with the LRU

    def test_prefix_run_take_and_granularity(self):
        rng = np.random.default_rng(3)
        tier = tier_mod.HostPageTier(8)
        keys = [bytes([i]) for i in range(3)]
        for k in keys:
            pg = _payload(rng, pages=1)
            tier.put_prefix(k, pg, tier_mod.payload_crc(pg))
        assert tier.prefix_run(keys) == 3
        assert tier.prefix_run(keys, granularity=2) == 2
        assert tier.prefix_run([b"zz"] + keys) == 0
        got = tier.take_prefix(keys[:2])
        assert len(got) == 2               # (payload, crc) pairs, touched MRU
        tier.drop_prefix(keys[0])
        assert tier.prefix_run(keys) == 0 and tier.prefix_pages() == 2
        with pytest.raises(KeyError):
            tier.take_prefix([keys[0]])

    def test_page_crcs_catch_single_flip(self):
        rng = np.random.default_rng(4)
        pay = _payload(rng)
        crcs = tier_mod.payload_page_crcs(pay, 3)
        pay["x"][1, 2, 0] += 1.0
        crcs2 = tier_mod.payload_page_crcs(pay, 3)
        assert crcs2[2] != crcs[2]
        assert crcs2[:2] == crcs[:2]       # per-page isolation

    def test_trim_pad_roundtrip(self):
        rng = np.random.default_rng(5)
        pay = _payload(rng, pages=5)
        cut = trim_pages(pay, 3)
        assert cut["x"].shape[1] == 3
        back = pad_pages(cut, 5)
        assert back["x"].shape[1] == 5
        np.testing.assert_array_equal(back["x"][:, :3], pay["x"][:, :3])
        assert not back["x"][:, 3:].any()


# ---------------------------------------------------------------------------
# TransferClock: ETA, slow-link stretch, drop/retry/backoff, timeout
# ---------------------------------------------------------------------------


class _Hook(NullFaultHook):
    """Scriptable fault hook: drops while ``dropping`` is set."""

    def __init__(self, slow=1.0):
        self.dropping = False
        self._slow = slow

    def drop(self):
        return self.dropping

    def slow(self):
        return self._slow


class TestTransferClock:
    def test_eta_and_slow_stretch(self):
        clk = TransferClock(TierConfig(xfer_ticks=2))
        clk.submit(tier_mod.SPILL, 1, 0, 100)
        clk.submit(tier_mod.FETCH, 2, 1, 100, slow=3.0)   # eta 6
        hook = NullFaultHook()
        done, fail = clk.advance(hook)
        assert done == [] and fail == []
        done, _ = clk.advance(hook)
        assert [t.rid for t in done] == [1]
        for _ in range(3):
            done, _ = clk.advance(hook)
        assert done == []
        done, _ = clk.advance(hook)       # tick 6 for the slow one
        assert [t.rid for t in done] == [2]
        assert clk.inflight == []

    def test_drop_retries_with_backoff_then_lands(self):
        clk = TransferClock(TierConfig(xfer_ticks=1, max_retries=3))
        hook = _Hook()
        t = clk.submit(tier_mod.FETCH, 7, 0, 100)
        hook.dropping = True
        _, fail = clk.advance(hook)       # attempt dropped, backoff 1
        assert fail == [] and t.retries == 1 and clk.retries == 1
        hook.dropping = False
        done, _ = clk.advance(hook)       # backoff tick (re-arms eta)
        assert done == []
        done, _ = clk.advance(hook)       # retried attempt lands
        assert done == [t] and t.failure is None

    def test_retries_exhaust_to_failure(self):
        clk = TransferClock(TierConfig(xfer_ticks=1, max_retries=2,
                                       timeout_ticks=100))
        hook = _Hook()
        hook.dropping = True
        t = clk.submit(tier_mod.FETCH, 7, 0, 100)
        failed = []
        for _ in range(20):
            _, fail = clk.advance(hook)
            failed += fail
            if failed:
                break
        assert failed == [t] and t.failure == "retries exhausted"
        assert t.retries == 3             # initial + max_retries attempts
        assert clk.inflight == []

    def test_timeout_escalates(self):
        clk = TransferClock(TierConfig(xfer_ticks=1, timeout_ticks=4))
        t = clk.submit(tier_mod.SPILL, 1, 0, 100, slow=100.0)  # eta 100
        hook = NullFaultHook()
        failed = []
        for _ in range(10):
            _, fail = clk.advance(hook)
            failed += fail
        assert failed == [t] and t.failure == "timeout"
        assert clk.timeouts == 1

    def test_cancel_predicate(self):
        clk = TransferClock(TierConfig())
        clk.submit(tier_mod.SPILL, 1, 0, 10)
        clk.submit(tier_mod.FETCH, 2, 1, 10)
        dropped = clk.cancel(lambda t: t.rid == 1)
        assert [t.rid for t in dropped] == [1]
        assert [t.rid for t in clk.inflight] == [2]


# ---------------------------------------------------------------------------
# Tiered engine: oversubscription, bitwise parity, zero stalls
# ---------------------------------------------------------------------------


class TestTieredEngine:
    def test_oversubscribed_bitwise_equal_and_no_stalls(self, cfg,
                                                        shared_params):
        """ISSUE 9 acceptance: a workload needing ~3x the device pool
        completes with no admission failure, zero prefetch stalls, and
        token streams bitwise-equal to the untiered engine."""
        base = _drain(_mk_flat(cfg, shared_params), _reqs())
        eng = _mk(cfg, shared_params)
        outs = _drain(eng, _reqs())
        assert outs == base
        ts = eng.tier_stats()
        assert ts["suspensions"] > 0 and ts["resumes"] == ts["suspensions"]
        assert ts["spilled_pages"] == ts["fetched_pages"] > 0
        assert ts["prefetch_stalls"] == 0
        assert ts["degraded"] == 0 and ts["crc_failures"] == 0
        assert ts["peak_resident_pages"] > eng.pool_pages  # oversubscribed
        # compile-once contract: the tier's jitted hops traced once each
        assert eng.trace_counts["tier_gather"] == 1
        assert eng.trace_counts["tier_scatter"] == 1
        assert eng.trace_counts["tier_resume"] == 1
        # full unwind: device pool recycled, no suspended residue
        assert eng.free_pages() == eng.pool_pages
        assert ts["suspended"] == 0 and ts["transfers_inflight"] == 0
        assert eng.tier.entries() == 0

    def test_sampled_streams_survive_tiering(self, cfg, shared_params):
        """Same parity bar under temperature/top-k sampling: per-request
        seeded streams make suspend/resume invisible to the sampler."""
        kw = dict(temperature=0.8, top_k=8)
        base = _drain(_mk_flat(cfg, shared_params, **kw),
                      _reqs(8, seed0=40))
        outs = _drain(_mk(cfg, shared_params, **kw), _reqs(8, seed0=40))
        assert outs == base

    def test_stats_surfaces(self, cfg, shared_params):
        flat = _mk_flat(cfg, shared_params)
        assert not any(k.startswith("host_") for k in flat.pool_stats())
        eng = _mk(cfg, shared_params)
        ps = eng.pool_stats()
        assert ps["host_pages_total"] == 48
        assert ps["host_pages_free"] == 48 and ps["host_occupancy"] == 0.0
        pf = eng.prefix_stats()
        for k in ("tier_prefix_pages", "tier_prefix_evictions",
                  "tier_prefix_fetched"):
            assert k in pf


# ---------------------------------------------------------------------------
# Degradation ladder: forced transfer failures and CRC corruption
# ---------------------------------------------------------------------------


class TestDegradation:
    def test_forced_fetch_failure_requeues_bitwise(self, cfg,
                                                   shared_params):
        """Kill the link while entries sit in the tier: fetch retries
        exhaust, the request degrades to a continuation re-queue, and the
        finished streams are still bitwise-equal to no-fault."""
        base = _drain(_mk_flat(cfg, shared_params), _reqs())
        hook = _Hook()
        eng = _mk(cfg, shared_params,
                  tier_kw=dict(max_retries=1, timeout_ticks=8),
                  tier_faults=hook)
        reqs = _reqs()
        for r in reqs:
            eng.submit(r)
        # run until something is parked in the tier, then cut the link
        for _ in range(200):
            eng.step()
            if any(e["state"] in ("host", "fetching")
                   for e in eng._suspended.values()):
                break
        hook.dropping = True
        for _ in range(60):
            eng.step()
            if eng.tstats["degraded"] > 0:
                break
        hook.dropping = False
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert eng.tstats["degraded"] > 0
        assert [r.out for r in reqs] == base
        assert eng.free_pages() == eng.pool_pages

    def test_crc_corruption_detected_and_recovered(self, cfg,
                                                   shared_params):
        """Flip a byte in a host-tier copy: the fetch-time CRC catches it
        and the request recomputes via re-queue, bitwise-equal."""
        base = _drain(_mk_flat(cfg, shared_params), _reqs())
        eng = _mk(cfg, shared_params)
        reqs = _reqs()
        for r in reqs:
            eng.submit(r)
        corrupted = False
        for _ in range(300):
            eng.step()
            if not corrupted:
                for e in eng._suspended.values():
                    if e["state"] == "host":
                        import jax
                        ent = eng.tier._entries[e["eid"]]
                        leaf = jax.tree.leaves(ent.payload)[0]
                        leaf.view(np.uint8).reshape(-1)[0] ^= 0xFF
                        corrupted = True
                        break
            if not eng.has_work():
                break
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert corrupted
        assert eng.tstats["crc_failures"] >= 1
        assert eng.tstats["degraded"] >= 1
        assert [r.out for r in reqs] == base

    def test_spill_failure_resumes_in_place(self, cfg, shared_params):
        """A spill whose transfer dies resumes the slot in place — the
        cheapest rung: device pages were never released."""
        base = _drain(_mk_flat(cfg, shared_params), _reqs())
        hook = _Hook()
        eng = _mk(cfg, shared_params,
                  tier_kw=dict(max_retries=1, timeout_ticks=8),
                  tier_faults=hook)
        reqs = _reqs()
        for r in reqs:
            eng.submit(r)
        for _ in range(200):
            eng.step()
            if eng._spilling_slots:
                hook.dropping = True      # kill the in-flight spill
            if eng.tstats["spill_aborts"] > 0:
                hook.dropping = False
                break
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert eng.tstats["spill_aborts"] > 0
        assert [r.out for r in reqs] == base


# ---------------------------------------------------------------------------
# cancel() across the tier state machine
# ---------------------------------------------------------------------------


class TestCancelMatrix:
    def test_cancel_in_every_tier_state(self, cfg, shared_params):
        """Cancel one request in each residency state (SPILLING, HOST,
        FETCHING, ready): device and host pages both free, in-flight
        transfers drop, and the rest of the workload still completes with
        a fully-recycled pool."""
        eng = _mk(cfg, shared_params,
                  tier_kw=dict(xfer_ticks=2))   # keeps transfers in flight
        reqs = _reqs(12, max_new=28)
        for r in reqs:
            eng.submit(r)
        hit = set()
        cancelled = set()
        for _ in range(600):
            eng.step()
            if eng._spilling_slots and "spilling" not in hit:
                rid = next(iter(eng._spilling_slots.values()))
                assert eng.cancel(rid)
                hit.add("spilling")
                cancelled.add(rid)
            for want in ("host", "fetching", "ready"):
                if want in hit:
                    continue
                rid = next((r_ for r_, e in eng._suspended.items()
                            if e["state"] == want), None)
                if rid is not None:
                    assert eng.cancel(rid)
                    hit.add(want)
                    cancelled.add(rid)
            if not eng.has_work():
                break
        eng.run_until_done()
        assert hit == {"spilling", "host", "fetching", "ready"}
        for r in reqs:
            assert r.done or r.rid in cancelled
        assert eng.free_pages() == eng.pool_pages
        assert eng.tier.entries() == 0
        assert len(eng._xfers.inflight) == 0
        assert eng.cancel(999) is False


# ---------------------------------------------------------------------------
# Warm-prefix spill + tier prefix fetch (repeated prompts)
# ---------------------------------------------------------------------------


class TestTierPrefix:
    def test_prefix_pages_spill_and_fetch_back(self, cfg, shared_params):
        """Warm refcount-0 prefix pages harvested to the host tier come
        back through the admission-time tier probe: a repeat of the same
        prefix skips its chunks without recompute, bitwise-equal."""
        rng = np.random.default_rng(11)
        prefix = rng.integers(1, 500, size=16).astype(np.int32)
        tail_a = rng.integers(1, 500, size=5).astype(np.int32)
        tail_b = rng.integers(1, 500, size=7).astype(np.int32)
        prompt_a = np.concatenate([prefix, tail_a])
        prompt_b = np.concatenate([prefix, tail_b])
        fillers = [rng.integers(1, 500, size=17 + i).astype(np.int32)
                   for i in range(4)]

        # reference: prompt_b on a fresh untiered engine
        ref = _mk_flat(cfg, shared_params, pool=12)
        rr = Request(0, prompt_b, max_new=8, seed=3)
        ref.submit(rr)
        ref.run_until_done()

        eng = _mk(cfg, shared_params, pool=12, host=24)
        r0 = Request(0, prompt_a, max_new=8, seed=9)
        eng.submit(r0)
        eng.run_until_done()
        assert r0.done
        assert eng._alloc.cached_free() >= 2   # prefix pages parked warm
        # dry the plain pool so the harvest sweep fires
        fr = [Request(10 + i, p, max_new=8, seed=20 + i)
              for i, p in enumerate(fillers)]
        for r in fr:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in fr)
        assert eng.tstats["prefix_spilled"] >= 2
        assert eng.tier.prefix_pages() >= 2
        # the repeat: device index lost the harvested pages, the tier
        # probe restores them into fresh pages without recompute
        r1 = Request(99, prompt_b, max_new=8, seed=3)
        eng.submit(r1)
        eng.run_until_done()
        assert r1.done
        assert eng.tstats["prefix_fetched"] >= 2
        assert r1.out == rr.out
        assert eng.prefix_stats()["tier_prefix_fetched"] >= 2


# ---------------------------------------------------------------------------
# Gateway integration: chaos parity + heartbeat occupancy
# ---------------------------------------------------------------------------


def mk_gateway(cfg, params, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    kw.setdefault("pool_pages", 10)
    kw.setdefault("page_storage", "bf16")
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("host_tier_pages", 32)
    kw.setdefault("tier_config", TierConfig(quantum=4))
    return Gateway(cfg, params=params, **kw)


def gw_outputs(cfg, params, n=6, max_new=24, **kw):
    """Run a page-oversubscribed batch: three 4-page requests per
    3-slot replica against a 10-page pool, so two decode while the
    third waits — the rotation quantum then forces real spill/fetch
    traffic on every replica."""
    gw = mk_gateway(cfg, params, **kw)
    reqs = [gw.submit(np.arange(4 + i), max_new=max_new, seed=i)
            for i in range(n)]
    gw.run_until_done()
    assert all(r.state == "done" for r in reqs)
    return gw, [list(r.delivered) for r in reqs]


@pytest.fixture(scope="module")
def gw_greedy_base(cfg, shared_params):
    return gw_outputs(cfg, shared_params)


class TestGatewayTier:
    def test_pcie_chaos_bitwise_equal_greedy(self, cfg, shared_params,
                                             gw_greedy_base):
        _, base = gw_greedy_base
        for kind in ("pcie_slow:0", "pcie_drop:0"):
            inj = ServeFaultInjector({4: kind}, pcie_ticks=12)
            gw, outs = gw_outputs(cfg, shared_params, injector=inj)
            assert outs == base, kind

    def test_pcie_chaos_bitwise_equal_sampled(self, cfg, shared_params):
        kw = dict(temperature=0.8, top_k=8)
        _, base = gw_outputs(cfg, shared_params, **kw)
        inj = ServeFaultInjector({4: "pcie_drop:0"}, pcie_ticks=12)
        gw, outs = gw_outputs(cfg, shared_params, injector=inj, **kw)
        assert outs == base

    def test_heartbeat_reports_tier_occupancy(self, gw_greedy_base):
        gw, _ = gw_greedy_base
        assert any(rep.engine.tstats["suspensions"] > 0
                   for rep in gw.registry.replicas.values())
        for rep in gw.registry.replicas.values():
            ts = rep.engine.tier_stats()
            assert rep.host_free_pages == ts["host_pages_free"] <= 32
            assert rep.host_occupancy == ts["host_occupancy"]
            assert rep.tier_suspended == ts["suspended"] == 0
            assert ts["transfers_inflight"] == 0

    def test_tier_full_falls_back_to_evict(self, cfg, shared_params,
                                           gw_greedy_base):
        """tier_full refuses spills for a window; the engine's preemption
        falls back to the PR 8 evict-and-requeue rung and the workload
        still completes bitwise-equal."""
        _, base = gw_greedy_base
        inj = ServeFaultInjector({3: "tier_full"}, pcie_ticks=20)
        gw, outs = gw_outputs(cfg, shared_params, injector=inj)
        assert outs == base


class TestFaultSpecGrammar:
    def test_tier_kinds_parse(self):
        from repro import faultspec
        for spec in ("pcie_slow", "pcie_drop:1", "tier_full"):
            fs = faultspec.parse_spec(spec, faultspec.SERVE_KINDS)
            assert fs.kind in faultspec.SERVE_KINDS
        with pytest.raises(ValueError):
            faultspec.parse_spec("pcie_teleport", faultspec.SERVE_KINDS)

    def test_adapter_self_clocks(self):
        inj = ServeFaultInjector({0: "pcie_drop"})
        ad = TierFaultAdapter(inj, replica=0)
        assert not ad.drop()              # before any tick
        ad.on_tick()
        assert ad.drop() and ad.slow() == 1.0
        for _ in range(inj.pcie_ticks + 1):
            ad.on_tick()
        assert not ad.drop()

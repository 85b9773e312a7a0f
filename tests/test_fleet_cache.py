"""Fleet-wide tiered prefix cache (ISSUE 20): HBM -> host-DRAM ->
peer-replica fetch.

Four layers: (1) the cache/ primitives in isolation — directory
generations/leases/notifications, the DRAM store's pin-disciplined
slots and tenant spill quotas, the planner's batched byte pricing;
(2) the LIVE path — token-for-token parity of streams served off
spilled-then-fetched pages against the ``generate_ring_dense`` oracle,
including kill/respawn of the owning replica between spill and fetch,
peer fetches over the migration-ring frame format, and the
counter-verified prefill-chunk saving; (3) the sim twin —
bit-identical day replays with the priced spill/fetch model, kill and
partition semantics matching the live hub; (4) the
``sweep_spill_capacity`` controller sweep with its refusal contract.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mpistragglers_jl_tpu.cache import (
    FleetPageDirectory,
    FleetPrefixCache,
    PageMove,
    PageStore,
    SpillFetchPlanner,
)
from mpistragglers_jl_tpu.models.decode import generate_ring_dense
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.obs import MetricsRegistry

CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128,
    attn_window=6,
)
PARAMS = init_params(CFG, seed=11)
KCFG = TransformerConfig(
    vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
    d_ff=256, attn_window=128,
)
KPARAMS = init_params(KCFG, seed=31)
RNG = np.random.default_rng(77)

D1 = b"\x01" * 32
D2 = b"\x02" * 32
D3 = b"\x03" * 32


def _prompt(n, vocab=CFG.vocab):
    return RNG.integers(1, vocab, size=n).astype(np.int32)


def _oracle(p, n, *, params=PARAMS, cfg=CFG):
    toks = generate_ring_dense(params, jnp.asarray(p)[None], n, cfg)
    return [int(t) for t in np.asarray(toks)[0]]


def _drained(sched):
    sched.pool.check()
    assert sched.pool.used == 0 and sched.pool.reserved == 0


# --------------------------------------------------------------------------
# FleetPageDirectory
# --------------------------------------------------------------------------


class TestDirectory:
    def test_publish_locate_dram_first(self):
        d = FleetPageDirectory()
        d.register_replica("a")
        d.register_replica("store")
        d.publish(D1, replica="a", tier="hbm")
        d.publish(D1, replica="store", tier="dram")
        assert d.locate(D1) == [("store", "dram"), ("a", "hbm")]
        assert d.locate(D1, exclude="a") == [("store", "dram")]
        assert D1 in d and d.size == 1
        d.check()

    def test_replica_drop_invalidates_by_generation(self):
        """A respawned replica's stale advertisements can never be
        served: drop bumps the generation, locate prunes."""
        d = FleetPageDirectory()
        d.register_replica("a")
        d.publish(D1, replica="a", tier="hbm")
        d.drop_replica("a")
        assert d.locate(D1) == []
        assert D1 not in d
        # respawn is a fresh generation: old entries stay dead, new
        # publishes live
        d.register_replica("a")
        d.publish(D2, replica="a", tier="hbm")
        assert d.locate(D2) == [("a", "hbm")]
        assert d.locate(D1) == []
        d.check()

    def test_publish_refusals(self):
        d = FleetPageDirectory()
        with pytest.raises(ValueError, match="register"):
            d.publish(D1, replica="ghost", tier="hbm")
        d.register_replica("a")
        with pytest.raises(ValueError, match="tier"):
            d.publish(D1, replica="a", tier="tape")

    def test_withdraw_notifies_subscribers(self):
        d = FleetPageDirectory()
        d.register_replica("a")
        seen = []
        d.subscribe(lambda dg, rep, tier: seen.append((dg, rep, tier)))
        d.publish(D1, replica="a", tier="hbm")
        assert d.withdraw(D1, replica="a", tier="hbm")
        assert not d.withdraw(D1, replica="a", tier="hbm")
        assert seen == [(D1, "a", "hbm")]

    def test_lease_lifecycle(self):
        d = FleetPageDirectory()
        d.register_replica("a")
        d.publish(D1, replica="a", tier="hbm")
        with d.lease(D1, "a", "hbm"):
            assert d.leased(D1)
        assert not d.leased(D1)
        lease = d.lease(D1, "a", "hbm")
        lease.release()
        lease.release()  # idempotent
        assert not d.leased(D1)
        d.check()


# --------------------------------------------------------------------------
# PageStore
# --------------------------------------------------------------------------


def _page(fill, nbytes=64):
    return np.full(nbytes, fill, dtype=np.uint8)


class TestPageStore:
    def test_put_get_roundtrip_zero_copy(self):
        st = PageStore(64, 4)
        assert st.put(D1, _page(7))
        got = st.get(D1)
        assert got is not None and got.nbytes == 64
        np.testing.assert_array_equal(np.asarray(got), _page(7))
        assert st.get(D2) is None
        assert st.put(D1, _page(9))  # present: True, bytes unchanged
        np.testing.assert_array_equal(np.asarray(st.get(D1)), _page(7))
        st.check()
        st.close()

    def test_geometry_mismatch_refused_by_name(self):
        st = PageStore(64, 2)
        with pytest.raises(ValueError, match="geometry"):
            st.put(D1, _page(0, nbytes=32))
        st.close()

    def test_capacity_eviction_is_oldest_first(self):
        d = FleetPageDirectory()
        st = PageStore(64, 2, directory=d)
        st.put(D1, _page(1))
        st.put(D2, _page(2))
        st.put(D3, _page(3))
        assert st.get(D1) is None  # oldest went
        assert st.get(D2) is not None and st.get(D3) is not None
        assert d.locate(D1) == []
        assert st.n_evictions == 1
        st.check()
        st.close()

    def test_leased_page_survives_eviction_pressure(self):
        """A fetch in progress must not watch its source evaporate:
        the eviction scan skips leased digests."""
        d = FleetPageDirectory()
        st = PageStore(64, 2, directory=d)
        st.put(D1, _page(1))
        st.put(D2, _page(2))
        with d.lease(D1, st.name, "dram"):
            st.put(D3, _page(3))
            assert st.get(D1) is not None  # leased: kept
            assert st.get(D2) is None      # next-oldest went instead
        st.check()
        st.close()

    def test_evicted_viewed_slot_bytes_survive_readers(self):
        """Zero-copy discipline: while a served view is live its slot
        stays pinned — a full store REFUSES new pages rather than tear
        the reader's bytes, and the slot frees when the view dies."""
        import gc

        st = PageStore(64, 1)
        st.put(D1, _page(5))
        view = st.get(D1)
        assert not st.put(D2, _page(6))  # D1 evicted, slot view-pinned
        assert st.n_refused == 1
        np.testing.assert_array_equal(np.asarray(view), _page(5))
        del view
        gc.collect()
        assert st.put(D2, _page(6))  # last reader gone: slot reusable
        np.testing.assert_array_equal(np.asarray(st.get(D2)), _page(6))
        st.check()
        st.close()

    def test_tenant_spill_quota(self):
        from mpistragglers_jl_tpu.qos import TenantContract, TenantRegistry

        qos = TenantRegistry([
            TenantContract("bulk", spill_pages=1),
            TenantContract("banned", spill_pages=0),
        ])
        st = PageStore(64, 4, qos=qos)
        assert not st.put(D1, _page(1), tenant="banned")
        assert st.n_refused == 1
        assert st.put(D1, _page(1), tenant="bulk")
        assert st.put(D2, _page(2), tenant="bulk")  # evicts own D1
        assert st.tenant_pages("bulk") == 1
        assert st.get(D1) is None and st.get(D2) is not None
        st.check()
        st.close()


# --------------------------------------------------------------------------
# SpillFetchPlanner
# --------------------------------------------------------------------------


class TestPlanner:
    def test_price_is_alpha_plus_bytes_over_rate(self):
        pl = SpillFetchPlanner(spill_gbs=10.0, fetch_gbs=5.0,
                               alpha_s=1e-5)
        assert pl.price(1 << 20, "spill") == pytest.approx(
            1e-5 + (1 << 20) / 10e9
        )
        assert pl.price(1 << 20, "fetch_peer") == pytest.approx(
            1e-5 + (1 << 20) / 5e9
        )
        with pytest.raises(ValueError, match="kind"):
            pl.price(1, "teleport")

    def test_plan_batches_per_link_at_batch_bytes(self):
        pl = SpillFetchPlanner(batch_bytes=128)
        moves = [
            PageMove(D1, src="r0", dst="store", nbytes=96, kind="spill"),
            PageMove(D2, src="r0", dst="store", nbytes=96, kind="spill"),
            PageMove(D3, src="r1", dst="r0", nbytes=96,
                     kind="fetch_peer"),
        ]
        batches = pl.plan(moves)
        # r0->store splits at 128 bytes; r1->r0 is its own link
        assert [(b["src"], b["dst"], len(b["moves"])) for b in batches] \
            == [("r0", "store", 1), ("r0", "store", 1), ("r1", "r0", 1)]
        assert pl.planned_batches == 3
        for b in batches:
            assert b["seconds"] > 0.0


# --------------------------------------------------------------------------
# live path: spill -> fetch parity against the dense oracle
# --------------------------------------------------------------------------


def _small_sched(hub, *, registry=None):
    """CFG geometry where requests do NOT wrap (Tp=4 + max_new=1 +
    n_inner=1 <= W=6), so retired prefix pages are registered
    non-volatile and eligible for fleet spill."""
    return ServingScheduler(
        PARAMS, CFG, slots=2, n_inner=1, prompt_chunk=2,
        max_prompt=16, page_tokens=2, registry=registry, cache=hub,
    )


class TestLiveSpillFetch:
    def test_spilled_then_fetched_stream_matches_oracle(self):
        """Replica A retires a prompt (pages spill to DRAM); replica B
        serves the same prompt off the fetched page — token-for-token
        the dense oracle, with the hit counted under tier="dram" and
        fewer prefill chunks than A paid."""
        hub = FleetPrefixCache(store_pages=8)
        reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
        p = _prompt(4)
        want = _oracle(p, 1)

        a = _small_sched(hub, registry=reg_a)
        ra = a.submit(p, max_new=1)
        a.run()
        assert ra.tokens == want
        _drained(a)
        assert hub.n_spills >= 1
        assert hub.store.pages >= 1

        b = _small_sched(hub, registry=reg_b)
        rb = b.submit(p, max_new=1)
        b.run()
        assert rb.tokens == want
        _drained(b)
        assert hub.n_fetches["dram"] == 1
        assert reg_b.counter(
            "serving_prefix_share_hits_total", tier="dram"
        ).value == 1
        # the fetched page replaced prefill work: B ran fewer chunks
        chunks_a = reg_a.counter("serving_prefill_chunks_total").value
        chunks_b = reg_b.counter("serving_prefill_chunks_total").value
        assert chunks_b < chunks_a
        hub.check()
        hub.close()

    def test_fetch_survives_owner_kill_and_respawn(self):
        """The acceptance crash shape: the replica that SPILLED dies
        between spill and fetch. DRAM is host state — the page
        survives, a respawned fleet member still fetches it, and the
        stream still equals the oracle."""
        hub = FleetPrefixCache(store_pages=8)
        p = _prompt(4)
        want = _oracle(p, 1)

        a = _small_sched(hub)
        name_a = a.cache_name
        ra = a.submit(p, max_new=1)
        a.run()
        assert ra.tokens == want
        assert hub.store.pages >= 1

        hub.kill(name_a)  # owner dies; its hbm entries invalidate
        assert name_a not in hub.members()

        b = _small_sched(hub)  # respawn as a fresh member
        rb = b.submit(p, max_new=1)
        b.run()
        assert rb.tokens == want
        assert hub.n_fetches["dram"] == 1
        assert hub.n_fallbacks == 0
        _drained(b)
        hub.check()
        hub.close()

    def test_peer_fetch_over_migration_ring_matches_oracle(self):
        """T3: with the DRAM tier disabled, a decoding peer's resident
        registered pages are fetched over the r16 frame format — both
        the owner's stream and the fetcher's equal their oracles."""
        hub = FleetPrefixCache(store_pages=0)  # peer-only fleet
        mk = lambda: ServingScheduler(
            KPARAMS, KCFG, slots=2, n_inner=4, prompt_chunk=8,
            max_prompt=64, page_tokens=16, cache=hub,
        )
        a, b = mk(), mk()
        p = RNG.integers(1, KCFG.vocab, size=40).astype(np.int32)
        want_a = _oracle(p, 40, params=KPARAMS, cfg=KCFG)
        want_b = _oracle(p, 8, params=KPARAMS, cfg=KCFG)

        ra = a.submit(p, max_new=40)
        while not ra.tokens:  # hold A mid-decode: pages stay resident
            a.step()
        rb = b.submit(p, max_new=8)
        b.run()
        a.run()
        assert ra.tokens == want_a
        assert rb.tokens == want_b
        assert hub.n_fetches["peer"] >= 1
        assert hub.n_fetches["dram"] == 0
        _drained(a)
        _drained(b)
        hub.check()
        hub.close()

    def test_partitioned_hub_member_falls_back_to_prefill(self):
        """A partition between spill and fetch: the asker sees nothing
        (fail-to-prefill), the stream is still oracle-exact, and after
        heal the same fetch hits."""
        hub = FleetPrefixCache(store_pages=8)
        p = _prompt(4)
        want = _oracle(p, 1)
        a = _small_sched(hub)
        a.submit(p, max_new=1)
        a.run()
        assert hub.store.pages >= 1

        b = _small_sched(hub)
        hub.partition(b.cache_name)
        rb = b.submit(p, max_new=1)
        b.run()
        assert rb.tokens == want  # re-prefilled, not served
        assert hub.n_fetches["dram"] == 0
        _drained(b)

        hub.heal(b.cache_name)
        rc = b.submit(p, max_new=1)
        b.run()
        assert rc.tokens == want
        assert hub.n_fetches["dram"] == 1
        _drained(b)
        hub.close()

    def test_cache_refused_without_a_page_size(self):
        hub = FleetPrefixCache()
        with pytest.raises(TypeError, match="page_tokens"):
            ServingScheduler(PARAMS, CFG, slots=2, cache=hub)

    def test_geometry_drift_refused_at_attach(self):
        hub = FleetPrefixCache(store_pages=4)
        _small_sched(hub)
        with pytest.raises(ValueError, match="geometry"):
            ServingScheduler(
                KPARAMS, KCFG, slots=2, prompt_chunk=8,
                max_prompt=64, page_tokens=16, cache=hub,
            )
        hub.close()


# --------------------------------------------------------------------------
# sim twin: SimFleetCache days
# --------------------------------------------------------------------------


def _sim_day(cache_groups, *, seed=5, n=800, kills=(), partition=None):
    from mpistragglers_jl_tpu.models.router import RequestRouter
    from mpistragglers_jl_tpu.sim import (
        ReplicaPartition,
        SimReplica,
        VirtualClock,
        poisson_arrivals,
        run_router_day,
    )
    from mpistragglers_jl_tpu.sim.workload import SimFleetCache

    clock = VirtualClock()
    cache = (SimFleetCache(store_groups=cache_groups)
             if cache_groups is not None else None)
    reps = [
        SimReplica(clock, slots=4, n_inner=8, tick_s=0.02,
                   prompt_chunk=64, chunk_s=0.004, cache=cache)
        for _ in range(3)
    ]
    router = RequestRouter(reps, policy="least_loaded", clock=clock)
    arrivals = list(poisson_arrivals(
        80.0, n=n, seed=seed, prompt_len=256, max_new=16,
        prefix_share=0.7, prefix_len=128, n_prefix_groups=8,
    ))
    events = []
    if partition is not None:
        events.append(ReplicaPartition(*partition))
    for t, i, until in kills:
        clock.call_at(t, lambda i=i: reps[i].kill())
        clock.call_at(until, lambda i=i: reps[i].revive())
    report = run_router_day(router, arrivals, events=events)
    return report, cache, reps


class TestSimFleetCache:
    def test_day_replays_bit_identically(self):
        r1, c1, f1 = _sim_day(16)
        r2, c2, _ = _sim_day(16)
        assert r1.digest() == r2.digest()
        assert c1.stats() == c2.stats()
        assert sum(r.n_fleet_hits for r in f1) > 0
        assert c1.n_spills > 0
        c1.check()
        # and the cache MOVES the day: priced fetches are not free
        r0, _, _ = _sim_day(None)
        assert r0.digest() != r1.digest()

    def test_counters_stay_outside_digest(self):
        """Same timing, different counter state must digest equal:
        the digest hashes outcomes, not bookkeeping."""
        r1, c1, _ = _sim_day(16)
        c1.n_spills += 100  # bookkeeping-only perturbation
        r2, c2, _ = _sim_day(16)
        assert r1.digest() == r2.digest()

    def test_kill_purges_hbm_but_dram_survives(self):
        from mpistragglers_jl_tpu.sim import SimReplica, VirtualClock
        from mpistragglers_jl_tpu.sim.workload import SimFleetCache

        clock = VirtualClock()
        cache = SimFleetCache(store_groups=8)
        r = SimReplica(clock, slots=2, cache=cache)
        cache.publish_hbm(r.cache_name, "g")
        cache._dram["g2"] = 4096
        r.kill()
        assert cache.stats()["hbm_groups"] == 0
        assert cache.n_replica_drops == 1
        assert cache.fetch("g2", 64) is not None  # dram survived
        assert cache.fetch("g", 64) is None
        # respawn gets a FRESH identity (generation semantics)
        old = r.cache_name
        r.revive()
        assert r.cache_name != old

    def test_partitioned_replica_invisible_and_fallback_counted(self):
        from mpistragglers_jl_tpu.sim.workload import SimFleetCache

        cache = SimFleetCache(store_groups=0)

        class _R:
            pass

        a = cache.register(_R())
        b = cache.register(_R())
        cache.publish_hbm(a, "g")
        assert cache.fetch("g", 64, exclude=b)[0] == "peer"
        cache.partition(a)
        assert cache.fetch("g", 64, exclude=b) is None
        assert cache.n_fallbacks == 1  # known-but-unreachable, named
        cache.heal(a)
        assert cache.fetch("g", 64, exclude=b)[0] == "peer"
        # the owner itself is excluded from its own lookups
        assert cache.fetch("g", 64, exclude=a) is None
        assert cache.n_fallbacks == 1  # a self-only miss is cold, not
        # a fallback: no reachable sibling ever held the group

    def test_fastpath_refuses_cache_days_by_name(self):
        from mpistragglers_jl_tpu.models.router import RequestRouter
        from mpistragglers_jl_tpu.sim import SimReplica, VirtualClock
        from mpistragglers_jl_tpu.sim.fastpath import fastpath_supported
        from mpistragglers_jl_tpu.sim.workload import SimFleetCache

        clock = VirtualClock()
        cache = SimFleetCache()
        reps = [SimReplica(clock, cache=cache) for _ in range(2)]
        router = RequestRouter(reps, policy="least_loaded", clock=clock)
        ok, reason = fastpath_supported(router)
        assert not ok and "fleet cache" in reason


# --------------------------------------------------------------------------
# sweep_spill_capacity
# --------------------------------------------------------------------------


class TestSpillCapacitySweep:
    def test_sweep_prefers_capacity_and_reports_saving(self):
        from mpistragglers_jl_tpu.sim.tune import sweep_spill_capacity

        out = sweep_spill_capacity(
            store_groups_candidates=[0, 64], requests=400, seed=3,
            n_prefix_groups=24,
        )
        assert out["best"] == 64
        assert out["p99_ttft_vs_no_dram"] > 1.0
        by_g = {e["store_groups"]: e for e in out["entries"]}
        assert by_g[64]["fetches"]["dram"] > 0
        assert by_g[0]["fetches"]["dram"] == 0  # no tier, no hits
        assert by_g[64]["prefill_chip_s_saved"] > \
            by_g[0]["prefill_chip_s_saved"]

    def test_sweep_refusals_by_name(self):
        from mpistragglers_jl_tpu.sim.tune import sweep_spill_capacity

        with pytest.raises(ValueError, match="empty"):
            sweep_spill_capacity(store_groups_candidates=[])
        with pytest.raises(ValueError, match="negative"):
            sweep_spill_capacity(store_groups_candidates=[-1])
        with pytest.raises(ValueError, match="shareless"):
            sweep_spill_capacity(store_groups_candidates=[4],
                                 prefix_share=0.0)
        with pytest.raises(ValueError, match="load"):
            sweep_spill_capacity(store_groups_candidates=[4], load=1.0)
        with pytest.raises(ValueError, match="replicas"):
            sweep_spill_capacity(store_groups_candidates=[4],
                                 replicas=1)

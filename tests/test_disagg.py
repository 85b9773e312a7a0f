"""Disaggregated prefill/decode serving (models/disagg.py, round 16).

Four layers of contract:

* **token-for-token parity** — a migrated stream equals the
  never-migrated ``generate_ring_dense`` oracle across fp/int8,
  COW-shared prefixes, sampled streams, and migration at EVERY decode
  step offset (the round-16 acceptance criterion);
* **the handoff edge** — ``cancel()`` arriving mid-migration releases
  pages on BOTH sides (planner-held frames and destination adoptions)
  and never double-frees, pinned by pool-drains-to-baseline in both
  pools (the same contract test_router.py pins for mid-admission
  cancel);
* **the two-tier router** — ``policy="two_tier"`` routes fresh
  requests to the prefill tier, migrates streams at their first token,
  honors the migration-size threshold, and exports the ``disagg_*``
  series + the per-handoff flight event;
* **the sim twin** — two-tier :class:`SimReplica` fleets reproduce the
  decode-p99 collapse/recovery on virtual time bit-identically, and
  ``sweep_tier_split`` refuses its three named floors.

The migration-ring PIN-LIFETIME legs live with their family in
tests/test_transport_rings.py.
"""

import gc

import numpy as np
import pytest

import jax.numpy as jnp

from mpistragglers_jl_tpu.models.decode import generate_ring_dense
from mpistragglers_jl_tpu.models.disagg import (
    DecodeReplica,
    MigrationPlanner,
    MigrationRing,
    MigrationRingReader,
    PrefillWorker,
    ticket_from_frames,
    ticket_to_frames,
)
from mpistragglers_jl_tpu.models.router import RequestRouter
from mpistragglers_jl_tpu.models.serving import (
    PagePoolExhausted,
    ServingScheduler,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.obs import FlightRecorder, MetricsRegistry
from mpistragglers_jl_tpu.sim import (
    SimReplica,
    VirtualClock,
    poisson_arrivals,
    run_router_day,
    sweep_tier_split,
)

# W=24 gives handoffs room before the ring wraps (prefix digests stay
# clean at migration time — the realistic regime); W=6 (CFG6) exercises
# the wrapped/volatile edge
CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2,
    d_ff=128, attn_window=24,
)
CFG6 = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2,
    d_ff=128, attn_window=6,
)
PARAMS = init_params(CFG, seed=11)
PARAMS6 = init_params(CFG6, seed=11)
RNG = np.random.default_rng(61)


def _prompt(n):
    return RNG.integers(1, CFG.vocab, size=n).astype(np.int32)


def _oracle(p, n, *, cfg=CFG, params=None, **kw):
    params = PARAMS if params is None else params
    toks = generate_ring_dense(
        params, jnp.asarray(p)[None], n, cfg, **kw
    )
    return [int(t) for t in np.asarray(toks)[0]]


def _sched(*, cfg=CFG, params=None, **kw):
    params = PARAMS if params is None else params
    kw.setdefault("slots", 2)
    kw.setdefault("n_inner", 2)
    kw.setdefault("prompt_chunk", 8)
    kw.setdefault("max_prompt", 64)
    kw.setdefault("page_tokens", 4)
    return ServingScheduler(params, cfg, **kw)


def _drained(*pools):
    for pool in pools:
        pool.check()
        assert pool.used == 0 and pool.reserved == 0


# --------------------------------------------------------------------------
# token-for-token parity (the acceptance criterion)
# --------------------------------------------------------------------------


class TestMigrationParity:
    @pytest.mark.parametrize("quantize_kv", [False, True],
                             ids=["fp", "int8"])
    def test_migrated_equals_oracle_at_every_offset(self, quantize_kv):
        """n_inner=1 so migration can land at EVERY decode step
        offset: for each k, the stream decodes k tokens on the source,
        migrates, finishes on the destination, and equals the
        never-migrated oracle exactly."""
        p = _prompt(7)
        max_new = 10
        want = _oracle(p, max_new, quantize_kv=quantize_kv)
        for off in range(max_new - 1):
            src = _sched(n_inner=1, quantize_kv=quantize_kv)
            dst = _sched(n_inner=1, quantize_kv=quantize_kv)
            r = src.submit(p, max_new=max_new)
            while len(r.tokens) < 1 + off:
                src.step()
            assert not r.finished
            st = src.export_page_state(r)
            assert dst.can_adopt_state(st)
            dst.adopt_page_state(st)
            dst.run()
            assert r.tokens == want, f"offset {off}"
            _drained(src.pool, dst.pool)

    def test_migrated_equals_oracle_mid_decode_batched(self):
        """n_inner=4 migration at a mid-decode tick boundary, source
        and destination at DIFFERENT n_inner (tick batching is not
        part of the stream's math)."""
        p = _prompt(11)
        src = _sched(n_inner=4)
        dst = _sched(n_inner=3)
        r = src.submit(p, max_new=17)
        src.step(); src.step()  # admit + one decode tick
        assert len(r.tokens) > 1 and not r.finished
        dst.adopt_page_state(src.export_page_state(r))
        dst.run()
        assert r.tokens == _oracle(p, 17)
        _drained(src.pool, dst.pool)

    def test_sampled_stream_survives_migration(self):
        """temperature > 0 with an explicit request key: the PRNG-key
        row travels in the ticket, so the sampled continuation equals
        the single-scheduler sampled stream exactly."""
        import jax

        p = _prompt(6)
        key = jax.random.key(123)
        want = _oracle(p, 12, temperature=0.8, top_k=5, key=key)
        src = _sched(temperature=0.8, top_k=5)
        dst = _sched(temperature=0.8, top_k=5)
        r = src.submit(p, max_new=12, key=key)
        src.step(); src.step()
        assert not r.finished
        dst.adopt_page_state(src.export_page_state(r))
        dst.run()
        assert r.tokens == want
        _drained(src.pool, dst.pool)

    def test_cow_shared_prefix_survives_migration_int8(self):
        """Two int8 streams sharing a page-aligned system prefix, both
        migrated: adoption re-registers the prefix-digest chain, so
        the SECOND migration shares the first's landed pages (COW
        reservations included — both wrap the window later), and both
        streams still equal their independent oracles."""
        planner = MigrationPlanner()
        pw = PrefillWorker(_sched(quantize_kv=True), planner=planner)
        dr = DecodeReplica(_sched(quantize_kv=True), planner=planner)
        sysp = _prompt(8)
        pa = np.concatenate([sysp, _prompt(3)])
        pb = np.concatenate([sysp, _prompt(3)])
        ra = pw.submit(pa, max_new=20)
        rb = pw.submit(pb, max_new=20)
        moved = set()
        while not (ra.finished and rb.finished):
            pw.step()
            for r in list(pw.ready()):
                if r.id not in moved:
                    moved.add(r.id)
                    t = pw.migrate_out(r)
                    assert dr.can_adopt(t)
                    dr.adopt(t)
            dr.step()
        assert ra.tokens == _oracle(pa, 20, quantize_kv=True)
        assert rb.tokens == _oracle(pb, 20, quantize_kv=True)
        assert dr.pool.share_hits > 0, "chain re-registration lost"
        assert dr.pool.cow_copies > 0, "COW never fired on decode tier"
        _drained(pw.pool, dr.pool)

    def test_wrapped_stream_migrates_without_registration(self):
        """A stream past its window wrap (W=6) migrates correctly —
        the pages hold late positions, so nothing is shareable and the
        export publishes no digests — and still equals its oracle."""
        src = _sched(cfg=CFG6, params=PARAMS6, page_tokens=2)
        dst = _sched(cfg=CFG6, params=PARAMS6, page_tokens=2)
        p = _prompt(5)
        r = src.submit(p, max_new=16)
        for _ in range(4):
            src.step()
        assert not r.finished
        st = src.export_page_state(r)
        assert st["n_cover"] == 0  # wrapped: nothing registerable
        dst.adopt_page_state(st)
        dst.run()
        assert r.tokens == _oracle(p, 16, cfg=CFG6, params=PARAMS6)
        _drained(src.pool, dst.pool)

    def test_frames_roundtrip_parity_and_pins_drain(self):
        """The cross-process shape: ticket -> ring-sized frames ->
        rebuilt ticket through the consumer's own mapping -> adoption.
        The rebuilt stream continues token-for-token and every ring
        pin drains once adoption consumed the views."""
        pw, dr = PrefillWorker(_sched()), DecodeReplica(_sched())
        p = _prompt(9)
        r = pw.submit(p, max_new=13)
        while not pw.ready():
            pw.step()
        ring = MigrationRing(slot_bytes=1 << 12, slots=8)
        if ring.region is None:  # pragma: no cover - no memfd
            pytest.skip("memfd_create unavailable")
        ticket = pw.migrate_out(r)
        n_moved = ticket.nbytes
        meta = ticket_to_frames(ticket, ring)
        reader = MigrationRingReader(ring)
        rebuilt = ticket_from_frames(meta, ticket.frames, reader)
        assert rebuilt.nbytes == n_moved
        leg = dr.adopt(rebuilt)
        assert leg is not r  # a fresh request object crossed
        assert list(leg.tokens) == list(r.tokens)
        dr.run()
        assert leg.tokens == _oracle(p, 13)
        ticket.release()
        ticket.release()  # idempotent
        del rebuilt
        gc.collect()
        assert ring.pinned == 0
        _drained(pw.pool, dr.pool)
        ring.close()


# --------------------------------------------------------------------------
# export/adopt contract edges
# --------------------------------------------------------------------------


class TestMigrationContract:
    def test_export_refuses_nonmigratable(self):
        s = _sched(prompt_chunk=4)
        q = s.submit(_prompt(5), max_new=8)
        with pytest.raises(ValueError, match="must be decoding"):
            s.export_page_state(q)  # still queued
        a = s.submit(_prompt(16), max_new=8)  # 4 chunks
        s.step()
        with pytest.raises(ValueError, match="must be decoding"):
            s.export_page_state(a)  # mid-admission
        s.run()
        with pytest.raises(ValueError, match="must be decoding"):
            s.export_page_state(a)  # finished

    def test_adopt_refuses_geometry_and_config_mismatch(self):
        src = _sched()
        r = src.submit(_prompt(6), max_new=8)
        src.step()
        st = src.export_page_state(r)
        with pytest.raises(ValueError, match="P mismatch"):
            _sched(page_tokens=2).adopt_page_state(dict(st))
        with pytest.raises(ValueError, match="quantize_kv mismatch"):
            _sched(quantize_kv=True).adopt_page_state(dict(st))
        with pytest.raises(ValueError, match="temperature mismatch"):
            _sched(temperature=0.5).adopt_page_state(dict(st))

    def test_can_adopt_state_is_boolean_on_config_mismatch(self):
        """can_adopt_state answers False — never raises — for a
        config-mismatched state: the router's adoption gate scans a
        HETEROGENEOUS decode tier replica-by-replica, and one
        sampling replica in a greedy fleet must be skipped, not crash
        the serving step loop."""
        src = _sched()
        r = src.submit(_prompt(6), max_new=8)
        src.step()
        st = src.export_page_state(r)
        for dst in (_sched(page_tokens=2), _sched(quantize_kv=True),
                    _sched(temperature=0.5)):
            assert dst.can_adopt_state(dict(st)) is False
            assert dst.could_adopt_state(dict(st)) is False
        # a compatible destination still answers True both ways
        ok = _sched()
        assert ok.can_adopt_state(dict(st)) is True
        assert ok.could_adopt_state(dict(st)) is True

    def test_adopt_refused_when_no_slot_or_pages(self):
        src = _sched()
        r = src.submit(_prompt(6), max_new=8)
        src.step()
        st = src.export_page_state(r)
        # no free slot: both destination slots busy
        dst = _sched()
        b1 = dst.submit(_prompt(5), max_new=30)
        b2 = dst.submit(_prompt(5), max_new=30)
        dst.step()
        assert dst.can_adopt_state(st) is False
        with pytest.raises(PagePoolExhausted):
            dst.adopt_page_state(st)
        # free slot but no page capacity: a pool too small to cover
        # the adopted request's whole-lifetime budget
        tiny = _sched(slots=2, cache_pages=7)  # 6 usable pages
        t1 = tiny.submit(_prompt(5), max_new=30)  # holds all 6
        tiny.step()
        assert tiny.pool.free == 0
        assert tiny.can_adopt_state(st) is False
        with pytest.raises(PagePoolExhausted):
            tiny.adopt_page_state(st)
        for sched, reqs in ((dst, (b1, b2)), (tiny, (t1,))):
            for q in reqs:
                sched.cancel(q)
            _drained(sched.pool)


# --------------------------------------------------------------------------
# the handoff edge: cancel mid-migration (the satellite bugfix pin)
# --------------------------------------------------------------------------


class TestCancelMidMigration:
    def test_cancel_mid_migration_drains_both_pools(self):
        """cancel() between capture and adoption: the planner releases
        its held frames, the request retires cancelled, BOTH pools sit
        at baseline, and a second cancel is a no-op — never a double
        free (test_router.py's mid-admission contract, extended to the
        migration window)."""
        planner = MigrationPlanner()
        pw = PrefillWorker(_sched(), planner=planner)
        dr = DecodeReplica(_sched(), planner=planner)
        base_pw, base_dr = pw.pool.free, dr.pool.free
        r = pw.submit(_prompt(5), max_new=10)
        while not pw.ready():
            pw.step()
        ticket = pw.migrate_out(r)
        assert planner.in_flight == 1
        assert pw.cancel(r) is True
        assert r.finished and r.reason == "cancelled"
        assert ticket._released and planner.in_flight == 0
        assert pw.cancel(r) is False  # idempotent
        assert pw.pool.free == base_pw and dr.pool.free == base_dr
        _drained(pw.pool, dr.pool)
        # the released ticket can never be adopted (no half-landing)
        with pytest.raises(ValueError, match="released"):
            dr.adopt(ticket)

    def test_cancel_after_adoption_releases_destination_pages(self):
        """cancel() landing AFTER adoption: the destination scheduler
        owns the request again, its cancel frees the adopted pages,
        and neither pool leaks — the 'both sides' half of the
        contract."""
        planner = MigrationPlanner()
        pw = PrefillWorker(_sched(), planner=planner)
        dr = DecodeReplica(_sched(), planner=planner)
        base_pw, base_dr = pw.pool.free, dr.pool.free
        r = pw.submit(_prompt(5), max_new=10)
        while not pw.ready():
            pw.step()
        ticket = pw.migrate_out(r)
        leg = dr.adopt(ticket)
        assert planner.in_flight == 0
        assert dr.cancel(leg) is True and leg.reason == "cancelled"
        assert dr.cancel(leg) is False
        ticket.release()  # idempotent post-adoption
        assert pw.pool.free == base_pw and dr.pool.free == base_dr
        _drained(pw.pool, dr.pool)

    def test_per_replica_planners_drain_the_capturing_book(self):
        """Tiers built with SEPARATE planners: adoption pops the
        in-flight entry from the planner that CAPTURED the ticket, not
        the destination's (whose book never had it) — otherwise every
        completed migration leaked a book entry on the source side and
        in_flight grew without bound."""
        src_p, dst_p = MigrationPlanner(), MigrationPlanner()
        pw = PrefillWorker(_sched(), planner=src_p)
        dr = DecodeReplica(_sched(), planner=dst_p)
        p = _prompt(5)
        r = pw.submit(p, max_new=10)
        while not pw.ready():
            pw.step()
        ticket = pw.migrate_out(r)
        assert src_p.in_flight == 1 and dst_p.in_flight == 0
        leg = dr.adopt(ticket)
        assert src_p.in_flight == 0 and dst_p.in_flight == 0
        dr.run()
        assert list(leg.tokens) == _oracle(p, 10)
        _drained(pw.pool, dr.pool)

    def test_cancel_mid_migration_with_frames_releases_ring(self):
        """The cross-process cancel: frames staged in the migration
        ring are released with the ticket — the ring's slots drain
        even though nothing was ever adopted."""
        planner = MigrationPlanner()
        pw = PrefillWorker(_sched(), planner=planner)
        r = pw.submit(_prompt(5), max_new=10)
        while not pw.ready():
            pw.step()
        ring = MigrationRing(slot_bytes=1 << 12, slots=8)
        if ring.region is None:  # pragma: no cover - no memfd
            pytest.skip("memfd_create unavailable")
        ticket = pw.migrate_out(r)
        ticket_to_frames(ticket, ring)
        assert ring.pinned > 0
        assert pw.cancel(r) is True
        gc.collect()
        assert ring.pinned == 0
        _drained(pw.pool)
        ring.close()


# --------------------------------------------------------------------------
# the two-tier router (live wrappers)
# --------------------------------------------------------------------------


class TestTwoTierRouter:
    def test_streams_equal_oracle_and_metrics_export(self):
        reg, fl = MetricsRegistry(), FlightRecorder(256)
        planner = MigrationPlanner()
        fleet = [
            PrefillWorker(_sched(), planner=planner),
            PrefillWorker(_sched(), planner=planner),
            DecodeReplica(_sched(), planner=planner),
            DecodeReplica(_sched(), planner=planner),
        ]
        router = RequestRouter(fleet, policy="two_tier",
                               registry=reg, flight=fl)
        reqs = [
            (router.submit(p, max_new=n), p, n)
            for p, n in [(_prompt(9), 12), (_prompt(5), 8),
                         (_prompt(12), 15), (_prompt(9), 6),
                         (_prompt(3), 10)]
        ]
        router.drain()
        for rr, p, n in reqs:
            assert rr.finished
            assert list(rr.tokens) == _oracle(p, n), rr.id
        assert router.n_migrated > 0
        migrated = [rr for rr, _, _ in reqs if rr.migrated]
        assert migrated
        assert all(rr.outcome == "migrated" for rr in migrated)
        snap = reg.snapshot()
        for name in ("disagg_migrations_total",
                     "disagg_migrated_pages_total",
                     "disagg_migrated_bytes_total",
                     "disagg_migration_seconds",
                     "disagg_tier_depth"):
            assert name in snap, name
        total = sum(s["value"] for s in
                    snap["disagg_migrations_total"]["series"])
        assert total == router.n_migrated
        assert any(
            e.get("name") == "kv migrated"
            for e in fl.dump()["traceEvents"]
        )
        for rep in fleet:
            _drained(rep.pool)

    def test_mismatched_decode_tier_bounces_stream_not_crashes(self):
        """A HETEROGENEOUS decode tier (here: a sampling replica in a
        greedy fleet) can never adopt the stream — its config-checked
        can_adopt/could_adopt answer False, never raise, so the router
        step survives the scan, and the bounce path lands the captured
        stream back on the prefill tier instead of parking it forever
        (the source slot freed, the request resident nowhere). The
        stream completes equal to its oracle and both pools drain."""
        planner = MigrationPlanner()
        pw = PrefillWorker(_sched(), planner=planner)
        dr = DecodeReplica(_sched(temperature=0.5), planner=planner)
        router = RequestRouter([pw, dr], policy="two_tier")
        p = _prompt(9)
        rr = router.submit(p, max_new=12)
        router.drain()
        assert rr.finished
        assert list(rr.tokens) == _oracle(p, 12)
        assert router.n_bounced == 1
        assert router.n_migrated == 1 and rr.migrated
        assert rr.replica == 0  # landed back on the prefill worker
        _drained(pw.pool, dr.pool)

    def test_threshold_keeps_streams_local(self):
        """A migration-size threshold below every payload: nothing
        migrates, streams decode where they prefilled, and they still
        equal their oracles (the graceful keep-local path)."""
        fleet = [PrefillWorker(_sched()), DecodeReplica(_sched())]
        router = RequestRouter(fleet, policy="two_tier",
                               migrate_threshold_bytes=1)
        p = _prompt(9)
        rr = router.submit(p, max_new=8)
        router.drain()
        assert list(rr.tokens) == _oracle(p, 8)
        assert router.n_migrated == 0
        assert router.n_kept_local == 1
        assert not rr.migrated and rr.outcome == "ok"

    def test_fresh_submits_land_on_prefill_tier(self):
        fleet = [PrefillWorker(_sched()), DecodeReplica(_sched())]
        router = RequestRouter(fleet, policy="two_tier")
        rr = router.submit(_prompt(5), max_new=4)
        assert rr.replica == 0  # the prefill replica
        router.drain()
        assert rr.finished


# --------------------------------------------------------------------------
# the sim twin (virtual time, numpy-only fast paths)
# --------------------------------------------------------------------------


def _sim_day(two_tier, *, chunk_s=0.01, n=2000, seed=3, thr=None):
    clock = VirtualClock()
    if two_tier:
        fleet = [
            SimReplica(clock, slots=4, n_inner=8, prompt_chunk=64,
                       tier=("prefill" if i < 2 else "decode"),
                       chunk_s=chunk_s)
            for i in range(6)
        ]
        router = RequestRouter(fleet, policy="two_tier", clock=clock,
                               migrate_gbs=5.2,
                               migrate_threshold_bytes=thr)
    else:
        fleet = [
            SimReplica(clock, slots=4, n_inner=8, prompt_chunk=64,
                       chunk_s=chunk_s)
            for i in range(6)
        ]
        router = RequestRouter(fleet, policy="least_loaded",
                               clock=clock)
    rate = 0.315 * 6 * 4 / (5 * 0.02)
    report = run_router_day(router, poisson_arrivals(
        rate, n=n, seed=seed, prompt_len=64, max_new=32,
        long_share=0.12, long_prompt_len=2048, long_max_new=32,
    ))
    return report, router


class TestSimTwoTier:
    def test_disagg_beats_unified_decode_p99_at_equal_chips(self):
        """The ROADMAP acceptance shape on virtual time: under the
        mixed long-prompt/short-chat day at EQUAL chip count, the
        two-tier fleet's decode p99 (per-request mean inter-token gap)
        beats the unified fleet by >= 1.5x — the long-prompt bursts'
        prefill chunks no longer stretch decode ticks."""
        unified, _ = _sim_day(False)
        disagg, router = _sim_day(True)
        assert unified.dropped == 0 and disagg.dropped == 0
        assert router.n_migrated > 0
        ratio = unified.p99_decode_itl() / disagg.p99_decode_itl()
        assert ratio >= 1.5, ratio

    def test_two_tier_day_bit_identical(self):
        """The run_router_day digest contract holds for two-tier days:
        migrations, transfer pricing and adoption are all virtual-time
        deterministic."""
        a, ra = _sim_day(True, n=4000, seed=9)
        b, rb = _sim_day(True, n=4000, seed=9)
        assert a.digest() == b.digest()
        assert ra.n_migrated == rb.n_migrated > 0
        assert ra.migrated_bytes == rb.migrated_bytes > 0

    def test_adopted_request_skips_prefill_and_carries_residency(self):
        clock = VirtualClock()
        src = SimReplica(clock, slots=2, n_inner=4, prompt_chunk=32,
                         tier="prefill", chunk_s=0.002)
        dst = SimReplica(clock, slots=2, n_inner=4, prompt_chunk=32,
                         tier="decode")
        from mpistragglers_jl_tpu.sim import SimPrompt

        p = SimPrompt(64, prefix=7, prefix_len=32)
        r = src.submit(p, max_new=16)
        clock.run_until(src.next_tick_at); src.step()
        clock.run_until(src.next_tick_at); src.step()
        assert r.n_emitted >= 1 and not r.finished
        before = r.n_emitted
        ticket = src.migrate_out(r)
        assert ticket.nbytes > 0 and ticket.pages > 0
        assert src.active == 0  # slot and residency left with it
        assert src.prefix_hits(p) == 0
        adopted = dst.adopt(ticket)
        assert adopted is r  # in-process stream continuity
        clock.run_until(dst.next_tick_at); dst.step()  # admit, no chunks
        assert dst.prefix_hits(p) > 0  # residency transferred
        assert r.n_emitted == before  # admission tick decodes nothing
        clock.run_until(dst.next_tick_at); dst.step()
        assert r.n_emitted > before  # decode resumed next tick
        while not r.finished:
            clock.run_until(dst.next_tick_at); dst.step()
        assert r.n_emitted == 16

    def test_dead_decode_tier_bounces_parked_migration(self):
        """The decode tier dies while transfers are in flight: the
        parked tickets may never land there, so the router bounces
        them back onto the prefill tier — zero drops, the _evacuate
        contract extended to the mid-migration window. Before the
        bounce (and its next_event_at wake), this day read as
        'workload stalled' with the captured streams resident
        nowhere."""
        clock = VirtualClock()
        pre = SimReplica(clock, slots=4, n_inner=8, prompt_chunk=64,
                         tier="prefill", chunk_s=0.002)
        dec = SimReplica(clock, slots=4, n_inner=8, prompt_chunk=64,
                         tier="decode")
        router = RequestRouter([pre, dec], policy="two_tier",
                               clock=clock, migrate_gbs=1e-4)
        # ~65 resident tokens * 4096 B/token at 1e-4 GB/s ≈ 2.7 s of
        # virtual transfer — the kill at t=1 lands mid-flight
        clock.call_at(1.0, dec.kill)
        report = run_router_day(router, poisson_arrivals(
            2.0, n=5, seed=7, prompt_len=64, max_new=16,
        ))
        assert report.dropped == 0
        assert router.n_bounced >= 1
        assert len(router._migrating) == 0

    def test_migrate_out_refuses_nonmigratable(self):
        clock = VirtualClock()
        rep = SimReplica(clock, slots=1, n_inner=4, tier="prefill")
        from mpistragglers_jl_tpu.sim import SimPrompt

        r = rep.submit(SimPrompt(512), max_new=8)
        with pytest.raises(ValueError, match="decoding"):
            rep.migrate_out(r)  # no first token yet

    def test_sweep_tier_split_refusals_and_recommendation(self):
        with pytest.raises(ValueError, match="leaves a tier empty"):
            sweep_tier_split(splits=[(0, 4)])
        with pytest.raises(ValueError, match="offered load"):
            sweep_tier_split(splits=[(2, 2)], load=1.0)
        with pytest.raises(ValueError,
                           match="no split meets the decode-p99 SLO"):
            sweep_tier_split(splits=[(2, 2)], requests=300,
                             decode_p99_slo_s=1e-9)
        out = sweep_tier_split(
            splits=[(2, 4), (3, 3)], requests=600, seed=2,
            long_share=0.12, long_prompt_len=1024, load=0.7,
        )
        assert out["best"] in [((2, 4), None), ((3, 3), None)]
        assert all(e["migrated"] > 0 for e in out["entries"])
        assert all(e["dropped"] == 0 for e in out["entries"])

    def test_sweep_router_policy_refuses_two_tier(self):
        from mpistragglers_jl_tpu.sim import sweep_router_policy

        with pytest.raises(ValueError, match="sweep_tier_split"):
            sweep_router_policy(policies=("two_tier",), requests=10)

    def test_long_mix_never_moves_arrival_times(self):
        """The long-prompt mix rides the same coin draw as the prefix
        share: arrival times are bit-identical at every mix rate, so
        mixed days stay comparable event-for-event."""
        plain = list(poisson_arrivals(5.0, n=500, seed=4))
        mixed = list(poisson_arrivals(
            5.0, n=500, seed=4, long_share=0.3, long_prompt_len=2048,
            long_max_new=8,
        ))
        assert [a.t for a in plain] == [a.t for a in mixed]
        longs = [a for a in mixed if a.prompt.length == 2048]
        assert longs and all(a.max_new == 8 for a in longs)
        assert any(a.prompt.length == 128 for a in mixed)

"""Flow-serving tests: concurrency determinism, coalescing, the
netlist-delta fast path, bounded LRU caches and eviction safety.

The server's whole contract is "throughput construct, never a numerics
one": every record a future resolves to must be bit-identical to the
single-request reference ``flow.pack_and_analyze(net, arch,
seeds=(seed,))`` — under concurrency, coalescing, priority reordering,
cache eviction mid-flight, and both delta paths.
"""
from __future__ import annotations

import asyncio
import copy

import numpy as np
import pytest

from repro.core import flow, plan
from repro.core.circuits import kratos_gemm, sha_like, vtr_mixed
from repro.core.eval_jax import eval_netlist_jax
from repro.core.flow import _METRIC_KEYS, pack_and_analyze
from repro.core.netlist import Netlist
from repro.core.repack import cluster_delta, pack_prefix, repack
from repro.core.serve_flow import (FlowRequest, FlowServer, serve_requests)


@pytest.fixture(autouse=True)
def _fresh_caches():
    plan.clear_caches()
    plan.reset_cache_stats()
    yield
    plan.clear_caches()


def _nets():
    return [kratos_gemm(m=4, n=4, width=5, sparsity=0.5),
            sha_like(rounds=1),
            vtr_mixed(logic_nodes=100, adders=2)]


def _assert_record_matches(rec: dict, net: Netlist, arch: str, seed: int):
    ref = pack_and_analyze(net, arch, seeds=(seed,))
    for k in _METRIC_KEYS:
        assert rec[k] == ref[k], (net.name, arch, k, rec[k], ref[k])


# ---------------------------------------------------------------------------
# LRU cache layer (repro.core.plan)
# ---------------------------------------------------------------------------


def test_cache_lru_order_and_counters():
    c = plan.Cache("t", cap=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # refreshes "a" — "b" is now LRU
    c.put("c", 3)                   # evicts "b"
    assert "a" in c and "c" in c and "b" not in c
    assert c.get("b") is None
    st = c.stats()
    assert st == {"size": 2, "cap": 2, "hits": 1, "misses": 1,
                  "evictions": 1, "hit_rate": 0.5}
    # __contains__ is a probe: never counts, never refreshes
    _ = "a" in c
    assert c.stats()["hits"] == 1
    c.clear()                       # entries drop, lifetime counters stay
    assert c.stats() == {"size": 0, "cap": 2, "hits": 1, "misses": 1,
                         "evictions": 1, "hit_rate": 0.5}
    c.reset_stats()
    assert c.stats()["hits"] == 0
    assert c.stats()["hit_rate"] == 0.0    # derived: no lookups yet


def test_cache_resize_and_registry_knobs():
    cache = plan.register_cache("test_resize_knob", cap=8)
    for i in range(8):
        cache.put(i, i)
    plan.set_cache_cap("test_resize_knob", 3)
    assert len(cache) == 3 and cache.cap == 3
    assert cache.stats()["evictions"] == 5
    assert set(cache.keys()) == {5, 6, 7}  # LRU evicted first
    with pytest.raises(KeyError, match="test_resize_knob"):
        plan.set_cache_cap("no_such_cache", 4)
    with pytest.raises(ValueError):
        cache.resize(0)
    assert "test_resize_knob" in plan.cache_stats()


def test_prefix_eviction_forces_clean_repack():
    """Evicting a ClusterPlan prefix (LRU pressure) must force a fresh
    prefix + re-pack that is byte-identical — eviction is a throughput
    event, never a correctness one."""
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    cache = plan.register_cache("pack_prefix")
    old_cap = cache.cap
    try:
        prefix = pack_prefix(net, seed=0)
        cache.put((net.content_digest(), 0), prefix)
        p0 = repack(prefix, flow._arch("baseline"))
        plan.set_cache_cap("pack_prefix", 1)
        # stream unrelated prefixes through to evict the original
        other = sha_like(rounds=1)
        cache.put((other.content_digest(), 0), pack_prefix(other, seed=0))
        assert (net.content_digest(), 0) not in cache
        # a re-pack from a *fresh* prefix must be byte-identical
        p1 = repack(pack_prefix(net, seed=0), flow._arch("baseline"))
        assert cluster_delta(p0, p1)["n_changed"] == 0
        from repro.core.timing import analyze

        assert analyze(p0) == analyze(p1)
    finally:
        plan.set_cache_cap("pack_prefix", old_cap)


# ---------------------------------------------------------------------------
# serving: coalescing, priority, determinism
# ---------------------------------------------------------------------------


def test_serve_matches_serial_flow():
    nets = _nets()
    reqs = [FlowRequest(net, arch, analyses=("area", "timing"), seed=0)
            for net in nets for arch in ("baseline", "dd5")]
    results = serve_requests(reqs)
    assert len(results) == len(reqs)
    for req, res in zip(reqs, results):
        assert res.net == req.net.name
        _assert_record_matches(res.record, req.net, req.arch, req.seed)
        # per-stage wall attribution rides every result
        stages = res.walls["stages"]
        assert {"repack_s", "timing_s", "total_s"} <= set(stages)
        assert res.walls["total_s"] >= res.walls["service_s"] >= 0.0


def test_serve_coalesces_identical_requests():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)

    async def main():
        server = FlowServer(batch_window_s=0.01)
        rs = await asyncio.gather(*(server.submit(
            FlowRequest(net, "baseline")) for _ in range(4)))
        await server.aclose()
        return rs, dict(server.stats)

    rs, stats = asyncio.run(main())
    # all four landed in one batch, one job served them all
    assert all(r.batch["id"] == rs[0].batch["id"] for r in rs)
    assert all(r.batch["n_shared"] == 4 for r in rs)
    assert stats["n_jobs"] == 1 and stats["n_coalesced"] == 3
    assert all(r.record is rs[0].record for r in rs)  # shared, not copied
    _assert_record_matches(rs[0].record, net, "baseline", 0)


def test_serve_priority_order_under_small_batches():
    """With max_batch=1 every batch holds one request; the drain order
    must be (-priority, arrival), so the high-priority latecomer is
    served in an earlier batch than the low-priority head."""
    nets = _nets()

    async def main():
        server = FlowServer(batch_window_s=0.02, max_batch=1)
        futs = [server.submit_nowait(FlowRequest(nets[0], "baseline",
                                                 priority=0)),
                server.submit_nowait(FlowRequest(nets[1], "baseline",
                                                 priority=5)),
                server.submit_nowait(FlowRequest(nets[2], "baseline",
                                                 priority=1))]
        rs = await asyncio.gather(*futs)
        await server.aclose()
        return rs

    r0, r1, r2 = asyncio.run(main())
    assert r1.batch["id"] < r2.batch["id"] < r0.batch["id"]
    for r, net in zip((r0, r1, r2), nets):
        _assert_record_matches(r.record, net, "baseline", 0)


def test_serve_concurrent_clients_with_midflight_eviction():
    """N asyncio clients stream a mixed workload while another task
    repeatedly clears/shrinks the shared caches mid-flight — every
    result must stay byte-identical to the serial reference."""
    nets = _nets()
    pool = [(net, arch) for net in nets for arch in ("baseline", "dd5")]
    n_clients, n_requests = 4, 16
    results: list = [None] * n_requests

    async def main():
        server = FlowServer(batch_window_s=0.001)

        async def client(ci):
            for j in range(ci, n_requests, n_clients):
                net, arch = pool[j % len(pool)]
                results[j] = await server.submit(
                    FlowRequest(net, arch, seed=0))

        async def evictor():
            # forced eviction between batches: full clears plus LRU
            # pressure on the pack/timing stores
            for _ in range(6):
                await asyncio.sleep(0.002)
                plan.clear_caches()
                plan.set_cache_cap("serve_packs", 1)
                plan.set_cache_cap("serve_timing", 1)

        try:
            await asyncio.gather(evictor(),
                                 *(client(c) for c in range(n_clients)))
        finally:
            await server.aclose()
            plan.set_cache_cap("serve_packs", 256)
            plan.set_cache_cap("serve_timing", 2048)

    asyncio.run(main())
    refs: dict = {}
    for j in range(n_requests):
        net, arch = pool[j % len(pool)]
        key = (net.name, arch)
        if key not in refs:
            refs[key] = pack_and_analyze(net, arch, seeds=(0,))
        for k in _METRIC_KEYS:
            assert results[j].record[k] == refs[key][k]


# ---------------------------------------------------------------------------
# netlist-delta fast path
# ---------------------------------------------------------------------------


def test_pack_digest_ignores_truth_tables_only():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    tt_edit = copy.deepcopy(net)
    tt_edit.lut_tt[0] ^= 0xFFFF
    assert tt_edit.content_digest() != net.content_digest()
    assert tt_edit.pack_digest() == net.pack_digest()
    structural = kratos_gemm(m=4, n=4, width=6, sparsity=0.5)
    assert structural.pack_digest() != net.pack_digest()


def test_serve_delta_tt_only_reuses_pack_and_timing():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    base_digest = net.content_digest()
    tt_edit = copy.deepcopy(net)
    tt_edit.lut_tt[0] ^= 0xFFFF

    async def main():
        server = FlowServer()
        r0 = await server.submit(FlowRequest(net, "baseline"))
        r1 = await server.submit(FlowRequest(tt_edit, "baseline",
                                             base_digest=base_digest))
        await server.aclose()
        return r0, r1, dict(server.stats)

    r0, r1, stats = asyncio.run(main())
    assert r1.delta["mode"] == "tt_only"
    assert r1.delta["n_changed"] == 0
    assert r1.batch["pack_cached"] and r1.batch["timing_cached"]
    assert stats["n_delta_pack_reuse"] == 1
    # the reused record is still bit-identical to a fresh serial flow of
    # the *edited* netlist (tt independence of pack + timing)
    _assert_record_matches(r1.record, tt_edit, "baseline", 0)
    assert r1.record["critical_path_ps"] == r0.record["critical_path_ps"]


def test_serve_delta_structural_attribution():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    edited = kratos_gemm(m=4, n=4, width=6, sparsity=0.5)
    base_digest = net.content_digest()

    async def main():
        server = FlowServer()
        await server.submit(FlowRequest(net, "baseline"))
        r = await server.submit(FlowRequest(edited, "baseline",
                                            base_digest=base_digest))
        await server.aclose()
        return r

    r = asyncio.run(main())
    assert r.delta["mode"] == "structural"
    assert r.delta["n_lbs_base"] >= 1 and r.delta["n_lbs_new"] >= 1
    assert 0 <= r.delta["unchanged_frac"] <= 1
    _assert_record_matches(r.record, edited, "baseline", 0)


def test_serve_delta_dirty_set_incremental_path():
    """A single-LUT fanin rewire with ``base_digest`` set rides the
    dirty-set path end to end: incremental repack, dirty-column IR
    patch, scoped per-cluster proof — and the served record is still
    bit-identical to a fresh serial flow of the edited netlist."""
    import random

    from repro.core.alm import ARCHS
    from repro.core.edits import (clone_netlist, edit_rewire_fanin,
                                  safe_rewire_sources)
    from repro.core.repack import (pack_prefix_delta, repack_delta,
                                   repack_with_log)

    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    arch = ARCHS["dd5"]
    prefix = pack_prefix(net, seed=0)
    _, log = repack_with_log(prefix, arch)
    # probe for an edit that stays on the incremental path (some rewires
    # legally fall back at the absorption/pairing gates)
    rng = random.Random(7)
    edited = None
    for _ in range(50):
        li = rng.randrange(net.n_luts)
        srcs = safe_rewire_sources(net, li)
        if not srcs:
            continue
        pin = rng.randrange(len(net.lut_inputs[li]))
        src = rng.choice(srcs)
        if net.lut_inputs[li][pin] == src:
            continue
        cand = clone_netlist(net)
        edit_rewire_fanin(cand, li, pin, src)
        np_, pinfo = pack_prefix_delta(prefix, cand, base_log=log)
        if np_ is None or pinfo["mode"] != "incremental":
            continue
        _, rinfo = repack_delta(np_, log, arch,
                                dirty_atoms=pinfo["dirty_atoms"])
        if rinfo["mode"] == "incremental":
            edited = cand
            break
    assert edited is not None, "no incremental-path rewire found"
    plan.clear_caches()

    async def main():
        server = FlowServer()
        base = await server.submit(FlowRequest(net, "dd5"))
        r = await server.submit(FlowRequest(edited, "dd5",
                                            base_digest=base.digest))
        await server.aclose()
        return r, dict(server.stats)

    r, stats = asyncio.run(main())
    assert r.delta["mode"] == "structural"
    assert r.delta["repack"]["mode"] == "incremental"
    assert r.delta["repack"]["n_frozen_lbs"] >= 1
    assert r.delta["verify"]["method"] == "symbolic_scoped"
    assert r.delta["verify"]["equivalent"] is True
    # moved-vs-re-clustered attribution is present and partitions
    assert (r.delta["n_frozen"] + r.delta["n_moved"]
            + r.delta["n_reclustered"]) >= r.delta["n_lbs_new"] - 1
    assert stats["n_delta_incremental"] == 1
    assert stats["n_delta_fallback"] == 0
    assert stats["n_verify_scoped"] == 1 and stats["n_verify_full"] == 0
    _assert_record_matches(r.record, edited, "dd5", 0)


def test_cluster_delta_identical_and_disjoint():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    arch = flow._arch("baseline")
    p = repack(pack_prefix(net, seed=0), arch)
    d = cluster_delta(p, p)
    assert d["n_changed"] == 0 and d["unchanged_frac"] == 1.0
    other = repack(pack_prefix(sha_like(rounds=1), seed=0), arch)
    d2 = cluster_delta(p, other)
    assert d2["n_changed"] == max(d2["n_lbs_base"], d2["n_lbs_new"])


# ---------------------------------------------------------------------------
# eval analysis
# ---------------------------------------------------------------------------


def test_serve_eval_matches_oracle_and_memoizes():
    net = sha_like(rounds=1)
    lanes = flow.random_lanes(net, 2, seed=0)
    ref = eval_netlist_jax(net, lanes, 2)

    async def main():
        server = FlowServer()
        r0 = await server.submit(FlowRequest(net, "baseline",
                                             analyses=("eval",),
                                             n_lane_words=2))
        r1 = await server.submit(FlowRequest(net, "dd5",
                                             analyses=("eval",),
                                             n_lane_words=2))
        await server.aclose()
        return r0, r1, dict(server.stats)

    r0, r1, stats = asyncio.run(main())
    for name, bus in net.pos.items():
        want = ref[np.asarray(bus, dtype=np.int64)]
        assert np.array_equal(r0.analyses["eval"][name], want)
        assert np.array_equal(r1.analyses["eval"][name], want)
    # eval is arch-independent: the second request (different arch, same
    # lane config) must be a memo hit, and eval-only requests never pack
    assert stats["n_eval_hits"] == 1
    assert r1.record is None and "eval" in r1.analyses
    assert r0.record is None


# ---------------------------------------------------------------------------
# server surface
# ---------------------------------------------------------------------------


def test_serve_request_validation_and_close():
    net = sha_like(rounds=1)
    with pytest.raises(ValueError, match="unknown analyses"):
        FlowRequest(net, "baseline", analyses=("timing", "power"))
    with pytest.raises(ValueError, match="no analyses"):
        FlowRequest(net, "baseline", analyses=())
    with pytest.raises(ValueError, match="backend"):
        FlowServer(timing_backend="fpga")

    async def main():
        server = FlowServer(batch_window_s=10.0)  # never fires in time
        fut = server.submit_nowait(FlowRequest(net, "baseline"))
        await asyncio.sleep(0)
        await server.aclose()
        with pytest.raises(RuntimeError, match="closed"):
            await fut

    asyncio.run(main())


def test_serve_cache_stats_surface():
    net = sha_like(rounds=1)

    async def main():
        server = FlowServer()
        await server.submit(FlowRequest(net, "baseline"))
        await server.submit(FlowRequest(net, "baseline"))
        st = server.cache_stats()
        await server.aclose()
        return st, dict(server.stats)

    st, stats = asyncio.run(main())
    for name in ("serve_packs", "serve_timing", "serve_programs",
                 "serve_digests", "pack_prefix"):
        assert name in st and st[name]["cap"] >= 1
    # second identical request (separate batch): pack + timing memo hits
    assert stats["n_pack_hits"] == 1
    assert stats["n_timing_hits"] == 1
    assert st["serve_packs"]["hits"] >= 1


def test_serve_numpy_backend_parity():
    nets = _nets()[:2]
    reqs = [FlowRequest(net, arch) for net in nets
            for arch in ("baseline", "dd6")]
    results = serve_requests(reqs, timing_backend="numpy")
    for req, res in zip(reqs, results):
        _assert_record_matches(res.record, req.net, req.arch, req.seed)

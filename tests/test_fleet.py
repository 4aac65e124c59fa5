"""Fleet tier (fleet/): consistent-hash ownership, cross-replica
single-flight leases, peer-to-peer cache fetch over the replay wire
format, drain-time hot-set handoff, and the serialized-executable
(AOT) store that makes a joining replica warm in seconds.

The multi-replica tests run REAL aiohttp servers on localhost ports —
each "replica" is a full gateway app with its own FakeTransport, score
cache, and FleetCoordinator, sharing a static roster — so the
exactly-one-upstream and degrade-to-local acceptance criteria are
asserted against the actual peer protocol, not mocks of it.
"""

import asyncio
import os
import random
import time
from types import SimpleNamespace

import pytest

from llm_weighted_consensus_tpu import archive, registry
from llm_weighted_consensus_tpu.ballot import PrefixTree
from llm_weighted_consensus_tpu.cache import (
    CacheStore,
    ScoreCache,
    score_fingerprint,
)
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.score import ScoreClient
from llm_weighted_consensus_tpu.fleet import (
    FleetClient,
    FleetConfig,
    FleetCoordinator,
    FleetFaultPlan,
    FleetMembership,
    LeaseTable,
    PeerHealth,
    clean_chunk_objs,
)
from llm_weighted_consensus_tpu.serve import build_app
from llm_weighted_consensus_tpu.serve.config import Config
from llm_weighted_consensus_tpu.types.score_request import (
    ChatCompletionCreateParams as ScoreParams,
)
from llm_weighted_consensus_tpu.utils import jsonutil

from fakes import FakeTransport, Script, chunk_obj

SEED = 11
NO_RETRY = BackoffPolicy(max_elapsed_ms=0)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def ballot_keys(n):
    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, n, 20)
    return {idx: k for k, idx in tree.key_indices(rng)}


JUDGES = {"llms": [{"model": "j1"}]}


def score_body(**overrides):
    body = {
        "messages": [{"role": "user", "content": "q"}],
        "model": JUDGES,
        "choices": ["first", "second"],
    }
    body.update(overrides)
    return body


def winning_script():
    keys = ballot_keys(2)
    return Script([chunk_obj(f"pick {keys[1]}", finish="stop")])


def fp_of(body):
    return score_fingerprint(ScoreParams.from_json_obj(body))


# -- membership / ownership ring ----------------------------------------------


def fleet_cfg(self_url, peers, **kw):
    return FleetConfig(self_url=self_url, peers=list(peers), **kw)


URLS = ["http://10.0.0.1:5000", "http://10.0.0.2:5000", "http://10.0.0.3:5000"]


def test_ring_agrees_across_replicas():
    # ownership must be a pure function of (roster, key): every replica,
    # hashing independently, routes a fingerprint to the same owner
    rings = [FleetMembership(fleet_cfg(u, URLS)) for u in URLS]
    for i in range(64):
        owners = {m.owner(f"fp-{i}") for m in rings}
        assert len(owners) == 1
        assert owners.pop() in URLS


def test_ring_balance_and_share():
    m = FleetMembership(fleet_cfg(URLS[0], URLS))
    counts = {u: 0 for u in URLS}
    for i in range(600):
        counts[m.owner(f"key-{i}")] += 1
    for u in URLS:
        # 64 vnodes per peer keeps every share well away from 0 and 1
        assert 0.15 < counts[u] / 600 < 0.55, counts
    shares = [
        FleetMembership(fleet_cfg(u, URLS)).owned_share() for u in URLS
    ]
    assert abs(sum(shares) - 1.0) < 1e-9


def test_ring_stability_on_departure():
    # the consistent-hash property the drain handoff relies on: removing
    # one peer only moves the keys that peer owned
    full = FleetMembership(fleet_cfg(URLS[0], URLS))
    gone = URLS[2]
    shrunk = FleetMembership(fleet_cfg(URLS[0], URLS[:2]))
    moved = stayed = 0
    for i in range(300):
        key = f"key-{i}"
        before = full.owner(key)
        if before == gone:
            moved += 1
        else:
            assert shrunk.owner(key) == before
            stayed += 1
    assert moved > 0 and stayed > 0


def test_owner_excluding_self_is_the_post_departure_owner():
    me = URLS[1]
    mine = FleetMembership(fleet_cfg(me, URLS))
    without_me = FleetMembership(
        fleet_cfg(URLS[0], [u for u in URLS if u != me])
    )
    for i in range(200):
        key = f"key-{i}"
        assert mine.owner_excluding_self(key) == without_me.owner(key)
    # a roster of one has nowhere to hand off to
    alone = FleetMembership(fleet_cfg(me, [me]))
    assert alone.owner_excluding_self("any") is None


def test_peers_file_roster_reloads_on_mtime(tmp_path):
    roster = tmp_path / "peers.txt"
    roster.write_text("# fleet roster\nhttp://a:1/\n\nhttp://b:2\n")
    now = [100.0]
    m = FleetMembership(
        FleetConfig(self_url="http://a:1", peers_file=str(roster)),
        clock=lambda: now[0],
    )
    assert m.peers == ["http://a:1", "http://b:2"]
    roster.write_text("http://a:1\nhttp://b:2\nhttp://c:3\n")
    os.utime(roster, (time.time() + 5, time.time() + 5))
    # inside the reload interval the old roster is served
    now[0] += 0.5
    assert m.peers == ["http://a:1", "http://b:2"]
    now[0] += 1.0
    assert m.peers == ["http://a:1", "http://b:2", "http://c:3"]
    assert m.reloads == 1
    # a transiently missing file must NOT empty the fleet
    roster.unlink()
    now[0] += 2.0
    assert m.peers == ["http://a:1", "http://b:2", "http://c:3"]


def test_config_fleet_knobs_and_validation():
    base = {"SCORE_CACHE_TTL": "60", "FLEET_SELF": "http://a:1/"}
    c = Config.from_env(
        dict(base, FLEET_PEERS="http://a:1/, http://b:2 ,")
    )
    fc = c.fleet_config()
    assert fc is not None
    assert fc.self_url == "http://a:1"
    assert fc.peers == ["http://a:1", "http://b:2"]
    assert Config.from_env({}).fleet_config() is None
    with pytest.raises(ValueError, match="mutually exclusive"):
        Config.from_env(
            dict(base, FLEET_PEERS="http://a:1", FLEET_PEERS_FILE="/p")
        )
    with pytest.raises(ValueError, match="FLEET_SELF is not"):
        Config.from_env(
            {"SCORE_CACHE_TTL": "60", "FLEET_PEERS": "http://a:1"}
        )
    with pytest.raises(ValueError, match="no roster"):
        Config.from_env(base)
    with pytest.raises(ValueError, match="not in FLEET_PEERS"):
        Config.from_env(dict(base, FLEET_PEERS="http://b:2"))
    with pytest.raises(ValueError, match="SCORE_CACHE_TTL"):
        Config.from_env(
            {"FLEET_SELF": "http://a:1", "FLEET_PEERS": "http://a:1"}
        )
    with pytest.raises(ValueError, match="FLEET_LEASE_MILLIS"):
        Config.from_env(
            dict(base, FLEET_PEERS="http://a:1", FLEET_LEASE_MILLIS="0")
        )


# -- lease table --------------------------------------------------------------


def test_lease_grant_wait_publish():
    async def run():
        t = LeaseTable(10000)
        granted, fut = t.acquire("fp", "http://a:1")
        assert granted and fut is None
        granted, fut = t.acquire("fp", "http://b:2")
        assert not granted and fut is not None
        t.publish("fp")
        assert await t.wait(fut, 1.0) is True
        assert t.active() == 0
        assert t.stats()["granted"] == 1
        assert t.stats()["waits"] == 1
        assert t.stats()["published"] == 1

    go(run())


def test_lease_release_wakes_waiters_with_none():
    async def run():
        t = LeaseTable(10000)
        t.acquire("fp", "http://a:1")
        _, fut = t.acquire("fp", "http://b:2")
        t.release("fp", "http://nobody:9")  # wrong holder: no-op
        assert not fut.done()
        t.release("fp", "http://a:1")
        assert await t.wait(fut, 1.0) is None
        # the slot is free again
        granted, _ = t.acquire("fp", "http://b:2")
        assert granted

    go(run())


def test_lease_expiry_regrants_and_resolves_old_future():
    async def run():
        now = [0.0]
        t = LeaseTable(1000, clock=lambda: now[0])
        t.acquire("fp", "http://a:1")
        _, fut = t.acquire("fp", "http://b:2")
        now[0] = 1.5
        granted, _ = t.acquire("fp", "http://b:2")
        assert granted  # the dead holder's lease expired
        assert t.expirations == 1
        assert await t.wait(fut, 1.0) is None

    go(run())


def test_lease_same_holder_reclaim_extends():
    async def run():
        now = [0.0]
        t = LeaseTable(1000, clock=lambda: now[0])
        t.acquire("fp", "http://a:1")
        now[0] = 0.8
        granted, fut = t.acquire("fp", "http://a:1")
        assert granted and fut is None  # a retry keeps its own lease
        now[0] = 1.3  # past the ORIGINAL expiry, inside the extension
        assert t.holder_future("fp") is not None
        assert t.remaining_sec("fp") == pytest.approx(0.5)

    go(run())


def test_lease_wait_timeout_does_not_kill_shared_future():
    async def run():
        t = LeaseTable(10000)
        t.acquire("fp", "http://a:1")
        _, fut = t.acquire("fp", "http://b:2")
        assert await t.wait(fut, 0.01) is None  # timed out
        assert not fut.cancelled()  # other waiters still hold it
        t.publish("fp")
        assert await t.wait(fut, 1.0) is True

    go(run())


# -- wire-side replay admission guard -----------------------------------------


def recorded_chunks():
    """A real clean record: run one scored request and read the cache."""
    cache = ScoreCache(60, 1 << 20)
    transport = FakeTransport([winning_script()])
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
        cache=cache,
    )
    params = ScoreParams.from_json_obj(score_body())

    async def run():
        stream = await score.create_streaming(None, params)
        async for _ in stream:
            pass

    go(run())
    record = cache.get(score_fingerprint(params))
    assert record is not None
    return record


def test_wire_guard_accepts_a_clean_record():
    record = recorded_chunks()
    assert clean_chunk_objs(record) == record


def test_wire_guard_rejects_degraded_and_errored_records():
    record = recorded_chunks()
    degraded = jsonutil.loads(jsonutil.dumps(record))
    degraded[0]["degraded"] = True
    assert clean_chunk_objs(degraded) is None
    errored = jsonutil.loads(jsonutil.dumps(record))
    errored[0]["choices"][0]["error"] = {"message": "judge failed"}
    assert clean_chunk_objs(errored) is None


def test_wire_guard_rejects_garbage():
    assert clean_chunk_objs(None) is None
    assert clean_chunk_objs([]) is None
    assert clean_chunk_objs("not a list") is None
    assert clean_chunk_objs([1, 2]) is None
    assert clean_chunk_objs([{"not": "a chunk"}]) is None


# -- store: TTL override + hot entries ----------------------------------------


def test_put_ttl_override_clamped_never_extended():
    now = [0.0]
    store = CacheStore(10.0, 1 << 20, clock=lambda: now[0])
    store.put("short", "v", 1, ttl_sec=2.0)
    store.put("long", "v", 1, ttl_sec=99.0)  # clamped to the store TTL
    store.put("dead", "v", 1, ttl_sec=0.0)  # already expired: dropped
    assert store.get("dead") is None
    now[0] = 3.0
    assert store.get("short") is None
    assert store.get("long") == "v"
    now[0] = 11.0
    assert store.get("long") is None


def test_hot_entries_mru_first_and_live_only():
    now = [0.0]
    store = CacheStore(10.0, 1 << 20, clock=lambda: now[0])
    for i in range(4):
        store.put(f"fp{i}", f"v{i}", 1)
    store.get("fp1")  # touch: fp1 becomes MRU
    now[0] = 5.0
    store.put("fresh", "vf", 1)
    entries = store.hot_entries(3)
    assert [fp for fp, _, _ in entries] == ["fresh", "fp1", "fp3"]
    for _, _, remaining in entries:
        assert 0 < remaining <= 10.0
    now[0] = 12.0  # originals expired, "fresh" still live
    assert [fp for fp, _, _ in store.hot_entries(10)] == ["fresh"]


# -- coordinator (no HTTP) ----------------------------------------------------


def make_coordinator(self_url, peers, **kw):
    fleet = FleetCoordinator(fleet_cfg(self_url, peers, **kw))
    fleet.cache = ScoreCache(60, 1 << 20)
    return fleet


def test_begin_with_empty_roster_is_local():
    async def run():
        fleet = make_coordinator("http://a:1", [])
        assert await fleet.begin("fp") == ("local", None)
        assert fleet.local_fallbacks == 1

    go(run())


def test_owner_lease_lifecycle():
    async def run():
        fleet = make_coordinator("http://a:1", ["http://a:1"])
        assert await fleet.begin("fp") == ("lease", None)
        assert fleet.leases.active() == 1
        fleet.publish("fp", [{"any": "chunks"}])
        assert fleet.leases.active() == 0
        assert fleet.publishes == 1
        # abandon releases too
        assert await fleet.begin("fp2") == ("lease", None)
        fleet.abandon("fp2")
        assert fleet.leases.active() == 0

    go(run())


def test_owner_waits_out_dead_holder_then_takes_the_lease():
    async def run():
        fleet = make_coordinator(
            "http://a:1", ["http://a:1"], lease_millis=80.0
        )
        granted, _ = fleet.leases.acquire("fp", "http://dead:9")
        assert granted
        t0 = time.monotonic()
        plan, chunks = await fleet.begin("fp")
        # the dead remote holder's lease expired; we compute with a
        # fresh lease of our own — today's behavior, one TTL later
        assert (plan, chunks) == ("lease", None)
        assert time.monotonic() - t0 < 2.0
        assert fleet.leases.expirations == 1

    go(run())


def test_owner_waiter_wakes_on_publish_with_a_hit():
    async def run():
        fleet = make_coordinator("http://a:1", ["http://a:1"])
        fleet.leases.acquire("fp", "http://peer:2")
        record = [{"fake": "record"}]

        async def remote_publishes():
            await asyncio.sleep(0.02)
            fleet.cache.put("fp", record, 64)
            fleet.leases.publish("fp")

        task = asyncio.get_event_loop().create_task(remote_publishes())
        plan, chunks = await fleet.begin("fp")
        await task
        assert plan == "hit"
        assert chunks == record
        assert fleet.peer_hits == 1

    go(run())


def test_unreachable_owner_degrades_to_local_and_breaks():
    async def run():
        from aiohttp.test_utils import unused_port

        dead = f"http://127.0.0.1:{unused_port()}"
        me = "http://127.0.0.2:1"
        fleet = make_coordinator(
            me, [me, dead], fetch_timeout_millis=300.0
        )
        # a fingerprint the dead peer owns
        fp = next(
            f"fp-{i}"
            for i in range(1000)
            if fleet.membership.owner(f"fp-{i}") == dead
        )
        try:
            for _ in range(3):
                assert await fleet.begin(fp) == ("local", None)
            assert fleet.peer_errors >= 1
            assert fleet.local_fallbacks >= 3
            # connect failures trip the per-peer breaker: later begins
            # stop paying the connect attempt entirely
            snap = fleet.client.breakers.snapshot()
            assert any(b.get("state") == "open" for b in snap.values()), snap
            # ...and the third consecutive failure QUARANTINES the dead
            # peer: its keys re-home, so the next begin owns fp locally
            # (a lease, not a fallback) instead of paying for the corpse
            assert fleet.health.quarantined() == [dead]
            status, _ = await fleet.begin(fp)
            assert status == "lease"
            assert fleet.membership.owner(fp) == me
        finally:
            await fleet.close()

    go(run())


# -- multi-replica integration (real servers, real peer protocol) -------------


def make_node(scripts, self_url, peers, lease_ms, fetch_ms, **cfg_kw):
    cache = ScoreCache(60, 1 << 20)
    cfg = fleet_cfg(
        self_url,
        peers,
        lease_millis=lease_ms,
        fetch_timeout_millis=fetch_ms,
        **cfg_kw,
    )
    fleet = FleetCoordinator(cfg)
    fleet.cache = cache
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
        cache=cache,
        fleet=fleet,
    )
    app = build_app(chat, score, fleet=fleet)
    return SimpleNamespace(
        url=self_url, cache=cache, fleet=fleet, transport=transport, app=app
    )


async def start_cluster(
    scripts_by_node, lease_ms=10000.0, fetch_ms=2000.0, **cfg_kw
):
    from aiohttp.test_utils import TestClient, TestServer, unused_port

    ports = [unused_port() for _ in scripts_by_node]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    nodes = []
    for i, scripts in enumerate(scripts_by_node):
        node = make_node(
            scripts, urls[i], urls, lease_ms, fetch_ms, **cfg_kw
        )
        node.client = TestClient(TestServer(node.app, port=ports[i]))
        await node.client.start_server()
        nodes.append(node)
    return nodes


async def stop_cluster(nodes):
    for node in nodes:
        await node.fleet.close()
        await node.client.close()


def post_json(client, path, obj, headers=None):
    h = {"content-type": "application/json"}
    h.update(headers or {})
    return client.post(path, data=jsonutil.dumps(obj), headers=h)


def owner_of(nodes, body):
    url = nodes[0].fleet.membership.owner(fp_of(body))
    return next(n for n in nodes if n.url == url)


def body_owned_by(nodes, node):
    for i in range(1000):
        body = score_body(
            messages=[{"role": "user", "content": f"q-{i}"}]
        )
        if owner_of(nodes, body) is node:
            return body
    raise AssertionError("no fingerprint landed on the node")


def test_peer_fetch_serves_owner_hit_without_upstream():
    async def run():
        nodes = await start_cluster([[winning_script()], []])
        try:
            body = body_owned_by(nodes, nodes[0])
            body["stream"] = True
            resp = await post_json(
                nodes[0].client, "/score/completions", body
            )
            assert resp.status == 200
            miss = await resp.read()
            # the non-owner fetches the record from the owner and
            # replays it byte-identically — zero upstream calls
            resp = await post_json(
                nodes[1].client, "/score/completions", body
            )
            assert resp.status == 200
            assert await resp.read() == miss
            assert nodes[1].transport.requests == []
            assert nodes[1].fleet.peer_hits == 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_hot_key_stampede_hits_upstream_exactly_once():
    async def run():
        # every replica COULD serve upstream (each has a script), so
        # only the lease protocol explains a fan-out count of one
        nodes = await start_cluster(
            [[winning_script()] for _ in range(3)]
        )
        try:
            body = score_body(stream=True)
            resps = await asyncio.gather(
                *(
                    post_json(n.client, "/score/completions", body)
                    for n in nodes
                )
            )
            bodies = [await r.read() for r in resps]
            assert all(r.status == 200 for r in resps)
            assert bodies[0] == bodies[1] == bodies[2]
            upstream = sum(len(n.transport.requests) for n in nodes)
            assert upstream == 1, upstream
        finally:
            await stop_cluster(nodes)

    go(run())


def test_owner_death_degrades_to_local_compute():
    async def run():
        nodes = await start_cluster(
            [[], [winning_script()]], fetch_ms=500.0
        )
        try:
            body = body_owned_by(nodes, nodes[0])
            await nodes[0].client.close()  # the owner dies
            resp = await post_json(
                nodes[1].client, "/score/completions", body
            )
            assert resp.status == 200
            assert len(nodes[1].transport.requests) == 1
            assert nodes[1].fleet.local_fallbacks >= 1
            assert nodes[1].fleet.peer_errors >= 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_dead_lease_holder_expires_to_local_compute():
    async def run():
        nodes = await start_cluster(
            [[], [winning_script()]], lease_ms=150.0
        )
        try:
            body = body_owned_by(nodes, nodes[0])
            fp = fp_of(body)
            # a replica claimed the lease on the owner, then died
            # without publishing or releasing
            granted, _ = nodes[0].fleet.leases.acquire(fp, "http://dead:9")
            assert granted
            t0 = time.monotonic()
            resp = await post_json(
                nodes[1].client, "/score/completions", body
            )
            assert resp.status == 200
            # bounded by the lease TTL, then local compute — never stuck
            assert time.monotonic() - t0 < 5.0
            assert len(nodes[1].transport.requests) == 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_peer_wait_sheds_on_propagated_deadline():
    async def run():
        # the lease TTL is 20s; the request deadline is 1.5s.  The
        # "sheds instead of blocking" contract: peer legs spend at most
        # half the remaining budget, so local compute still fits
        nodes = await start_cluster(
            [[], [winning_script()]], lease_ms=20000.0
        )
        try:
            body = body_owned_by(nodes, nodes[0])
            fp = fp_of(body)
            nodes[0].fleet.leases.acquire(fp, "http://hung:9")
            t0 = time.monotonic()
            resp = await post_json(
                nodes[1].client,
                "/score/completions",
                body,
                headers={"x-deadline-ms": "1500"},
            )
            assert resp.status == 200
            assert time.monotonic() - t0 < 3.0  # nowhere near the TTL
            assert len(nodes[1].transport.requests) == 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_degraded_publish_is_rejected_at_the_wire():
    record = recorded_chunks()

    async def run():
        nodes = await start_cluster([[]])
        try:
            node = nodes[0]
            dirty = jsonutil.loads(jsonutil.dumps(record))
            dirty[0]["degraded"] = True
            resp = await node.client.put(
                "/fleet/v1/entry/fp-dirty",
                data=jsonutil.dumps(
                    {"holder": "http://evil:1", "chunks": dirty}
                ),
            )
            assert resp.status == 422
            assert node.fleet.rejected_publishes == 1
            assert node.cache.get("fp-dirty") is None
            # the clean original is accepted and servable
            resp = await node.client.put(
                "/fleet/v1/entry/fp-clean",
                data=jsonutil.dumps(
                    {"holder": "http://peer:1", "chunks": record}
                ),
            )
            assert resp.status == 200
            resp = await node.client.get("/fleet/v1/entry/fp-clean")
            assert resp.status == 200
            assert (await resp.json())["chunks"] == record
        finally:
            await stop_cluster(nodes)

    go(run())


def test_dirty_publish_releases_the_lease():
    record = recorded_chunks()
    dirty = jsonutil.loads(jsonutil.dumps(record))
    dirty[0]["choices"][0]["error"] = {"message": "boom"}

    async def run():
        nodes = await start_cluster([[]])
        try:
            node = nodes[0]
            node.fleet.leases.acquire("fp", "http://peer:1")
            resp = await node.client.put(
                "/fleet/v1/entry/fp",
                data=jsonutil.dumps(
                    {"holder": "http://peer:1", "chunks": dirty}
                ),
            )
            assert resp.status == 422
            # waiters must not ride out the TTL hoping for a record
            # that was refused
            assert node.fleet.leases.active() == 0
        finally:
            await stop_cluster(nodes)

    go(run())


def test_drain_handoff_moves_hot_set_to_new_owner():
    async def run():
        from llm_weighted_consensus_tpu.serve.lifecycle import (
            Lifecycle,
            health_handlers,
        )

        nodes = await start_cluster([[winning_script()], []])
        try:
            a, b = nodes
            body = body_owned_by(nodes, a)
            fp = fp_of(body)
            resp = await post_json(a.client, "/score/completions", body)
            assert resp.status == 200
            assert b.cache.get(fp) is None
            lifecycle = Lifecycle(
                fleet=a.fleet, caches=[a.cache], drain_timeout_ms=2000.0
            )
            clean = await lifecycle.begin_drain()
            assert clean
            # the hot entry now lives on its post-drain owner, which
            # can serve it without ever seeing the original request
            assert b.cache.get(fp) is not None
            assert b.fleet.handoff_received == 1
            assert a.fleet.handoff_sent == 1
            assert a.fleet.handoff_accepted == 1
            assert lifecycle.snapshot()["fleet_handoff_entries"] == 1
            resp = await post_json(b.client, "/score/completions", body)
            assert resp.status == 200
            assert b.transport.requests == []
            # /readyz surfaces membership while READY (checked on a
            # fresh lifecycle: the drained one reports 503/stopped)
            _, readyz = health_handlers(
                Lifecycle(fleet=b.fleet, caches=[b.cache])
            )
            ready_body = jsonutil.loads((await readyz(None)).text)
            assert ready_body["fleet"]["self"] == b.url
            assert set(ready_body["fleet"]["peers"]) == {a.url, b.url}
        finally:
            await stop_cluster(nodes)

    go(run())


def test_handoff_rejects_dirty_and_expired_entries():
    record = recorded_chunks()
    dirty = jsonutil.loads(jsonutil.dumps(record))
    dirty[0]["degraded"] = True

    async def run():
        nodes = await start_cluster([[]])
        try:
            node = nodes[0]
            resp = await node.client.post(
                "/fleet/v1/handoff",
                data=jsonutil.dumps(
                    {
                        "from": "http://peer:1",
                        "entries": [
                            {"fp": "ok", "chunks": record, "ttl_sec": 5.0},
                            {"fp": "bad", "chunks": dirty, "ttl_sec": 5.0},
                            {"fp": "old", "chunks": record, "ttl_sec": 0},
                        ],
                    }
                ),
            )
            assert resp.status == 200
            assert (await resp.json())["accepted"] == 1
            assert node.fleet.handoff_received == 1
            assert node.fleet.handoff_rejected == 2
            assert node.cache.get("ok") is not None
            assert node.cache.get("bad") is None
            assert node.cache.get("old") is None
        finally:
            await stop_cluster(nodes)

    go(run())


def test_fleet_metrics_sections_and_prom_families():
    async def run():
        nodes = await start_cluster([[winning_script()], []])
        try:
            body = body_owned_by(nodes, nodes[0])
            await post_json(nodes[0].client, "/score/completions", body)
            resp = await post_json(
                nodes[1].client, "/score/completions", body
            )
            assert resp.status == 200
            metrics = await (await nodes[1].client.get("/metrics")).json()
            fleet = metrics["fleet"]
            assert fleet["peer_fetch"]["hits"] == 1
            assert fleet["membership"]["self"] == nodes[1].url
            assert 0.0 < fleet["membership"]["owned_share"] < 1.0
            prom = await (
                await nodes[1].client.get("/metrics?format=prometheus")
            ).text()
            assert 'lwc_fleet_peer_fetches_total{result="hits"} 1' in prom
            assert "lwc_fleet_leases " in prom
        finally:
            await stop_cluster(nodes)

    go(run())


# -- failure plane: fault plan, ring epochs, takeover, quarantine -------------


def test_fault_plan_seeded_determinism_is_pair_local():
    # the contract the split-brain drill's replay leans on: each ordered
    # pair's fault sequence depends only on (seed, pair, pair-ordinal),
    # never on how the event loop interleaves other pairs
    probs = {"blackhole": 0.25, "slow": 0.25, "5xx": 0.2}
    a, b, c = "http://a:1", "http://b:1", "http://c:1"
    grouped = FleetFaultPlan(seed=7, probabilities=probs)
    seq_ab = [grouped.next_fault(a, b) for _ in range(8)]
    seq_ac = [grouped.next_fault(a, c) for _ in range(8)]
    interleaved = FleetFaultPlan(seed=7, probabilities=probs)
    mixed = [
        interleaved.next_fault(*pair) for _ in range(8) for pair in ((a, b), (a, c))
    ]
    assert seq_ab == mixed[0::2]
    assert seq_ac == mixed[1::2]
    # and a different seed draws a different sequence
    reseeded = FleetFaultPlan(seed=8, probabilities=probs)
    assert [reseeded.next_fault(a, b) for _ in range(8)] != seq_ab


def test_fault_plan_parse_script_scope_and_errors():
    plan = FleetFaultPlan.parse("seed=3,blackhole=0.5,slow_ms=40,to=http://b:1")
    assert plan.seed == 3 and plan.slow_ms == 40.0
    assert plan.probabilities["blackhole"] == 0.5
    # to= scopes sampled faults to legs TOWARD the listed peers
    assert all(
        plan.next_fault("http://a:1", "http://c:1") is None for _ in range(20)
    )

    scripted = FleetFaultPlan.parse("script=connect|ok|5xx")
    assert scripted.next_fault("http://a:1", "http://b:1") == "connect"
    assert scripted.next_fault("http://a:1", "http://b:1") is None
    assert scripted.next_fault("http://a:1", "http://b:1") == "5xx"
    assert scripted.next_fault("http://a:1", "http://b:1") is None  # past end
    # the script replays PER PAIR: a fresh pair starts from slot 0
    assert scripted.next_fault("http://a:1", "http://c:1") == "connect"

    for bad in ("nope", "bogus=1", "script=warp", "seed"):
        with pytest.raises(ValueError):
            FleetFaultPlan.parse(bad)


def test_fault_plan_partition_and_heal():
    a, b, c = "http://a:1", "http://b:1", "http://c:1"
    plan = FleetFaultPlan()
    plan.partition([[a], [b, c]])
    assert plan.next_fault(a, b) == "blackhole"
    assert plan.next_fault(b, a) == "blackhole"
    assert plan.next_fault(c, a) == "blackhole"
    assert plan.next_fault(b, c) is None  # same component: healthy
    plan.heal()
    assert plan.next_fault(a, b) is None
    assert plan.injected["blackhole"] == 3
    assert plan.snapshot()["rules"] == 0


def test_peer_5xx_opens_breaker():
    # regression: _request used to record breaker SUCCESS for any
    # answered request, so a peer stuck returning 500s never tripped it
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    async def run():
        app = web.Application()

        async def boom(request):
            return web.json_response(
                {"error": {"kind": "internal"}}, status=500
            )

        app.router.add_get("/fleet/v1/entry/{fp}", boom)
        server_client = TestClient(TestServer(app))
        await server_client.start_server()
        peer = str(server_client.make_url("")).rstrip("/")
        fc = FleetClient("http://me:1", fetch_timeout_ms=500.0)
        try:
            for _ in range(3):
                assert await fc.fetch_entry(peer, "fp") == ("error", None)
            assert fc.peer_5xx == 3
            states = {
                s["state"] for s in fc.breakers.snapshot().values()
            }
            assert "open" in states, states
            # open breaker: the next leg sheds without touching the wire
            assert await fc.fetch_entry(peer, "fp") == ("error", None)
            assert fc.peer_5xx == 3
        finally:
            await fc.close()
            await server_client.close()

    go(run())


def test_publish_without_running_loop_closes_coro_quietly():
    # _spawn must not call the deprecated get_event_loop() fallback: with
    # no running loop the coroutine is closed, not leaked or crashed
    fleet = make_coordinator(URLS[0], URLS)
    fp = next(
        f"fp-{i}"
        for i in range(1000)
        if fleet.membership.owner(f"fp-{i}") != URLS[0]
    )
    fleet.publish(fp, [])
    fleet.abandon(fp)
    assert fleet._tasks == set()


def test_publish_routes_on_pinned_view_across_roster_reload(tmp_path):
    async def run():
        me = "http://10.0.0.1:5000"
        other = "http://10.0.0.2:5000"
        peers_file = tmp_path / "peers.txt"
        peers_file.write_text(f"{me}\n")
        now = [0.0]
        cfg = FleetConfig(self_url=me, peers_file=str(peers_file))
        fleet = FleetCoordinator(cfg, clock=lambda: now[0])
        fleet.cache = ScoreCache(60, 1 << 20)
        # a fingerprint that moves to `other` once the roster grows
        probe = FleetMembership(fleet_cfg(me, [me, other]))
        fp = next(
            f"fp-{i}" for i in range(1000) if probe.owner(f"fp-{i}") == other
        )
        epoch0 = fleet.membership.epoch
        assert await fleet.begin(fp) == ("lease", None)
        assert fleet.leases.active() == 1
        # the roster grows MID-REQUEST: the live owner flips away from us
        peers_file.write_text(f"{me}\n{other}\n")
        os.utime(peers_file, (1e9, 1e9))
        now[0] += 2.0
        assert fleet.membership.owner(fp) == other
        assert fleet.membership.epoch > epoch0
        # the publish still routes on the view PINNED at begin: the local
        # lease retires (waiters wake) and nothing is pushed at the new
        # owner, who never granted anything
        fleet.publish(fp, [])
        assert fleet.leases.active() == 0
        assert fleet.leases.published == 1
        assert fleet._tasks == set()
        await fleet.close()

    go(run())


def test_split_roster_divergence_degrades_to_local():
    async def run():
        nodes = await start_cluster([[winning_script()], [winning_script()]])
        try:
            a, b = nodes
            body = body_owned_by(nodes, b)  # a must cross the wire
            # b's roster diverges (a staggered peers-file read: b now
            # believes it is alone) — its digest no longer matches a's
            b.fleet.membership._set_peers([b.url])
            resp = await post_json(a.client, "/score/completions", body)
            assert resp.status == 200
            assert a.fleet.ring_divergences == 1
            assert b.fleet.ring_rejects >= 1
            # a served the request itself rather than trusting the
            # divergent owner's lease table
            assert len(a.transport.requests) == 1
            assert a.fleet.local_fallbacks >= 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_dead_holder_early_takeover_and_late_publish_reconciled():
    record = recorded_chunks()

    async def run():
        from aiohttp.test_utils import unused_port

        nodes = await start_cluster(
            [[winning_script()]],
            lease_ms=30000.0,
            fetch_ms=300.0,
            probe_millis=60.0,
        )
        try:
            node = nodes[0]
            dead = f"http://127.0.0.1:{unused_port()}"
            body = body_owned_by(nodes, node)
            fp = fp_of(body)
            granted, _ = node.fleet.leases.acquire(fp, dead)
            assert granted
            t0 = time.monotonic()
            resp = await post_json(node.client, "/score/completions", body)
            assert resp.status == 200
            elapsed = time.monotonic() - t0
            # the waiter probed the dead holder and stole the lease in
            # ~one probe interval — nowhere near the 30 s TTL
            assert elapsed < 5.0, elapsed
            assert node.fleet.early_takeovers == 1
            assert node.fleet.leases.takeovers == 1
            assert len(node.transport.requests) == 1
            # the "dead" holder's publish arrives after the steal: a
            # LATE publish — cached and counted, but it must not retire
            # the live claimant's state or be double-counted as fresh
            resp = await node.client.put(
                f"/fleet/v1/entry/{fp}",
                data=jsonutil.dumps({"holder": dead, "chunks": record}),
                headers={"content-type": "application/json"},
            )
            assert resp.status == 200
            out = await resp.json()
            assert out["accepted"] is True
            assert out["retired"] is False
            assert node.fleet.leases.late_publishes == 1
            assert node.cache.get(fp) is not None
        finally:
            await stop_cluster(nodes)

    go(run())


def test_breaker_open_holder_is_taken_over_without_probe():
    async def run():
        nodes = await start_cluster(
            [[winning_script()], []], lease_ms=30000.0, probe_millis=60.0
        )
        try:
            a, b = nodes
            body = body_owned_by(nodes, a)
            fp = fp_of(body)
            # b holds a's lease while a's breaker for b is OPEN from
            # recent transport failures; b's server is alive and would
            # answer a ping, so only the breaker verdict explains a
            # takeover this fast
            granted, _ = a.fleet.leases.acquire(fp, b.url)
            assert granted
            breaker = a.fleet.client.breakers.get(b.url, "fleet")
            for _ in range(3):
                breaker.record_failure()
            t0 = time.monotonic()
            resp = await post_json(a.client, "/score/completions", body)
            assert resp.status == 200
            assert time.monotonic() - t0 < 5.0
            assert a.fleet.early_takeovers == 1
            assert len(a.transport.requests) == 1
        finally:
            await stop_cluster(nodes)

    go(run())


def test_peer_health_flap_quarantine_and_probe_readmission():
    now = [0.0]
    health = PeerHealth(3, 100.0, clock=lambda: now[0])
    peer = "http://p:1"
    # an up/down/up flapper never reaches 3 CONSECUTIVE failures, but
    # the transition count in the window trips the flap detector
    for ok in [True, False, True, False, True, False, True]:
        health.record(peer, ok)
    assert health.quarantined() == [peer]
    # once quarantined, traffic outcomes stop mattering — only probes
    # gate re-admission, at most one per interval
    health.record(peer, True)
    assert health.quarantined() == [peer]
    assert health.probes_due() == []  # interval not yet elapsed
    now[0] += 0.2
    assert health.probes_due() == [peer]
    assert health.probes_due() == []  # stamped: no double probe
    health.record_probe(peer, False)
    assert health.quarantined() == [peer]
    now[0] += 0.2
    assert health.probes_due() == [peer]
    health.record_probe(peer, True)
    assert health.quarantined() == []
    assert health.stats()["readmissions"] == 1
    # disabled (FLEET_QUARANTINE_FAILURES=0): inert, never quarantines
    off = PeerHealth(0, 100.0, clock=lambda: now[0])
    for _ in range(10):
        off.record(peer, False)
    assert off.quarantined() == []


def test_ring_digest_agrees_and_quarantine_stays_local():
    rings = [FleetMembership(fleet_cfg(u, URLS)) for u in URLS]
    assert len({m.ring_digest() for m in rings}) == 1
    m = rings[0]
    epoch0 = m.epoch
    m.set_quarantined({URLS[1]})
    assert m.epoch == epoch0 + 1
    # quarantine re-homes the sick peer's keys but does NOT change the
    # digest: it is local knowledge, not roster disagreement — otherwise
    # noticing a sick peer would make every healthy pair look divergent
    assert m.ring_digest() == rings[1].ring_digest()
    assert URLS[1] not in {m.owner(f"fp-{i}") for i in range(256)}
    m.set_quarantined({URLS[1]})  # no change: no rebuild, same epoch
    assert m.epoch == epoch0 + 1
    m.set_quarantined(set())
    assert m.epoch == epoch0 + 2
    assert URLS[1] in {m.owner(f"fp-{i}") for i in range(256)}


def test_departure_view_matches_bruteforce_ring_removal():
    import xxhash

    me = URLS[0]
    m = FleetMembership(fleet_cfg(me, URLS))

    def brute(fp):
        # the pre-optimization algorithm: nearest clockwise vnode over
        # every peer but self, O(peers x vnodes) per key
        key = xxhash.xxh3_64_intdigest(fp.encode())
        best = None
        for peer in m.peers:
            if peer == me:
                continue
            for i in range(m.config.vnodes):
                point = xxhash.xxh3_64_intdigest(f"{peer}#{i}".encode())
                distance = (point - key) % (1 << 64)
                if best is None or distance < best[0]:
                    best = (distance, peer)
        return best[1]

    for i in range(128):
        assert m.owner_excluding_self(f"fp-{i}") == brute(f"fp-{i}")


def test_handoff_pushes_targets_concurrently_under_partition():
    record = recorded_chunks()

    async def run():
        nodes = await start_cluster([[], [], []], fetch_ms=400.0)
        try:
            a = nodes[0]
            departure = a.fleet.membership.departure_view()
            fps = {}
            for i in range(1000):
                fps.setdefault(departure.owner(f"fp-{i}"), f"fp-{i}")
                if len(fps) == 2:
                    break
            for fp in fps.values():
                a.cache.put_chunks(fp, record)
            # both targets blackholed: each push burns the full fetch
            # budget — concurrently, or the drain pays it once per target
            plan = FleetFaultPlan()
            plan.partition([[a.url], [nodes[1].url, nodes[2].url]])
            a.fleet.client.fault_plan = plan
            t0 = time.monotonic()
            accepted = await a.fleet.handoff(a.cache)
            elapsed = time.monotonic() - t0
            assert accepted == 0
            assert elapsed < 0.75, elapsed  # ~one budget, not two
            assert plan.injected["blackhole"] == 2
        finally:
            await stop_cluster(nodes)

    go(run())


# -- AOT executable store -----------------------------------------------------


def test_aot_store_digest_namespaces_and_fail_open(tmp_path):
    from llm_weighted_consensus_tpu.models.aot_store import (
        AotStore,
        _key_name,
    )

    a = AotStore(str(tmp_path), meta={"jax": "1", "backend": "cpu"})
    b = AotStore(str(tmp_path), meta={"jax": "2", "backend": "cpu"})
    # any environment difference lands in a fresh namespace:
    # invalidation by construction
    assert a.dir != b.dir
    key = ("vote1", 4, 16, True)
    assert a.load(key, []) is None  # missing: silent miss, not a failure
    assert a.load_failures == 0
    os.makedirs(a.dir, exist_ok=True)
    with open(os.path.join(a.dir, _key_name(key)), "wb") as f:
        f.write(b"not a pickle")
    assert a.load(key, []) is None  # corrupt: fail open, count it
    assert a.load_failures == 1


def test_aot_store_failed_restore_is_logged_once(tmp_path, caplog):
    """A restore that fails falls back to compiling — with one warning
    that says why (a second bucket failing the same way is only counted)."""
    import logging

    from llm_weighted_consensus_tpu.models.aot_store import (
        AotStore,
        _key_name,
    )

    store = AotStore(str(tmp_path), meta={"jax": "1"})
    os.makedirs(store.dir, exist_ok=True)
    for key in (("vote1", 4, 16, True), ("embed", 16, 16)):
        with open(os.path.join(store.dir, _key_name(key)), "wb") as f:
            f.write(b"not a pickle")
    with caplog.at_level(logging.WARNING, logger="lwc.serve"):
        assert store.load(("vote1", 4, 16, True), []) is None
        assert store.load(("embed", 16, 16), []) is None
    assert store.load_failures == 2
    warnings = [r for r in caplog.records if "AOT store load" in r.getMessage()]
    assert len(warnings) == 1 and warnings[0].exc_info is not None


def test_aot_warmup_serializes_then_restores_without_compiling(tmp_path):
    jax = pytest.importorskip("jax")
    import numpy as np

    from llm_weighted_consensus_tpu.models import configs
    from llm_weighted_consensus_tpu.models.aot_store import AotStore
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    N, S, R = 4, 16, 2

    def make(store_root):
        e = TpuEmbedder(
            "test-tiny", config=configs.TEST_TINY, max_tokens=32, seed=3
        )
        e.aot_store = AotStore(str(store_root), meta=e.aot_cache_meta())
        return e

    e1 = make(tmp_path)
    timings1 = e1.aot_warmup([(N, S)], r_buckets=[R])
    assert len(timings1) == 4
    assert e1.aot_store.saves == 4
    assert e1.jit_stats()["aot_restored"] == 0

    # a new replica sharing the artifact dir deserializes every bucket
    e2 = make(tmp_path)
    timings2 = e2.aot_warmup([(N, S)], r_buckets=[R])
    assert e2.aot_store.loads == 4
    assert e2.jit_stats()["aot_restored"] == 4
    assert all("[deserialized]" in label for label, _ in timings2)

    # the acceptance: deserialize-only warmup serves warmed buckets with
    # ZERO new jit specializations, and computes the same numbers
    stats0 = e2.jit_stats()
    rng = np.random.default_rng(12)
    ids = rng.integers(3, configs.TEST_TINY.vocab_size, (N, S)).astype(
        np.int32
    )
    mask = np.ones((N, S), np.int32)
    got = np.asarray(e2.consensus_confidence_tokens(ids, mask))
    ref = np.asarray(e1.consensus_confidence_tokens(ids, mask))
    stats1 = e2.jit_stats()
    assert stats1["specializations"] == stats0["specializations"]
    np.testing.assert_allclose(got, ref, atol=1e-6)

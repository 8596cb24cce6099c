"""KV router: radix indexer, scheduler cost, routed end-to-end, recorder."""

import asyncio

import pytest

from dynamo_tpu.kv_router.indexer import KvIndexer, ShardedKvIndexer
from dynamo_tpu.kv_router.metrics_aggregator import KvMetricsAggregator
from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvCacheRemoved,
    KvCacheStored,
    RouterEvent,
)
from dynamo_tpu.kv_router.publisher import KvEventPublisher, KvMetricsPublisher
from dynamo_tpu.kv_router.recorder import KvRecorder, replay_events
from dynamo_tpu.kv_router.router import KvRouter
from dynamo_tpu.kv_router.scheduler import AllWorkersBusy, KvScheduler
from dynamo_tpu.llm.processor import KvRoutedClient
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime.client import Client
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.transports.memory import MemoryHub
from dynamo_tpu.tokens import compute_block_hashes


def stored(worker, hashes, parent=None):
    return RouterEvent(worker_id=worker, stored=KvCacheStored(hashes, parent))


def removed(worker, hashes):
    return RouterEvent(worker_id=worker, removed=KvCacheRemoved(hashes))


def test_radix_tree_match_and_remove():
    idx = KvIndexer(block_size=4)
    tokens = list(range(16))  # 4 blocks
    h = compute_block_hashes(tokens, 4)

    idx.apply_event(stored("w1", h))
    idx.apply_event(stored("w2", h[:2]))

    scores = idx.find_matches(h)
    assert scores.scores == {"w1": 4, "w2": 2}
    assert scores.frequencies == [2, 2, 1, 1]

    # divergent suffix only matches the shared prefix
    other = compute_block_hashes(list(range(8)) + [99] * 8, 4)
    scores2 = idx.find_matches(other)
    assert scores2.scores == {"w1": 2, "w2": 2}

    # removal of a middle block cuts the chain for that worker
    idx.apply_event(removed("w1", [h[1]]))
    scores3 = idx.find_matches(h)
    assert scores3.scores["w1"] == 1  # only block 0 still consecutive
    assert scores3.scores["w2"] == 2

    idx.remove_worker("w2")
    scores4 = idx.find_matches(h)
    assert "w2" not in scores4.scores


def test_radix_tree_orphan_parent():
    """Stored events whose parent is unknown still index standalone."""
    idx = KvIndexer(block_size=4)
    idx.apply_event(stored("w1", [111, 222], parent=999))  # 999 never stored
    # chain rooted at root: matching [111, 222] directly works
    scores = idx.find_matches([111, 222])
    assert scores.scores == {"w1": 2}


def test_sharded_indexer_merges():
    idx = ShardedKvIndexer(num_shards=3, block_size=4)
    tokens = list(range(12))
    h = compute_block_hashes(tokens, 4)
    for w in ("a", "b", "c", "d"):
        idx.apply_event(stored(w, h[:2] if w == "d" else h))
    scores = idx.find_matches(h)
    assert scores.scores["a"] == 3 and scores.scores["d"] == 2
    idx.remove_worker("a")
    assert "a" not in idx.find_matches(h).scores


def test_scheduler_cost_function():
    sched = KvScheduler(block_size=4)
    sched.update_metrics("idle", ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8,
        kv_active_blocks=0, kv_total_blocks=100,
    ))
    sched.update_metrics("busy", ForwardPassMetrics(
        request_active_slots=8, request_total_slots=8,
        kv_active_blocks=90, kv_total_blocks=100,
    ))
    from dynamo_tpu.kv_router.indexer import OverlapScores

    # no overlap → idle worker wins on load
    d = sched.schedule(16, OverlapScores())
    assert d.worker_id == "idle"

    # busy worker with full prefix overlap beats idle (2*1.0 - 0.9 - 1.0 > 0)
    d2 = sched.schedule(16, OverlapScores(scores={"busy": 4}))
    assert d2.worker_id == "busy"
    assert d2.prefix_hit_tokens == 16

    # predicted-state: repeated no-overlap requests spread over the idle one
    # but bump its predicted load each time
    before = sched.workers["idle"].predicted_active
    sched.schedule(16, OverlapScores())
    assert sched.workers["idle"].predicted_active == before + 1


def test_scheduler_all_busy():
    sched = KvScheduler(block_size=4, require_free_slot=True)
    sched.update_metrics("w", ForwardPassMetrics(
        request_active_slots=8, request_total_slots=8, kv_total_blocks=10,
    ))
    from dynamo_tpu.kv_router.indexer import OverlapScores

    with pytest.raises(AllWorkersBusy):
        sched.schedule(4, OverlapScores())


@pytest.mark.asyncio
async def test_kv_router_end_to_end_over_hub(tmp_path):
    """Two token-level workers publish KV events + metrics; the router
    sends a request with a matching prefix to the right worker."""
    hub = MemoryHub()
    w1_drt = DistributedRuntime.in_process(hub)
    w2_drt = DistributedRuntime.in_process(hub)
    r_drt = DistributedRuntime.in_process(hub)

    served = {"w-one": 0, "w-two": 0}

    def make_worker(drt, instance_id):
        ep = drt.namespace("prod").component("backend").endpoint("generate")

        async def handler(payload, ctx):
            served[instance_id] += 1
            req = PreprocessedRequest.from_wire(payload)
            yield {"token_ids": [req.token_ids[0]], "finish_reason": "length"}

        metrics = ForwardPassMetrics(
            request_active_slots=0, request_total_slots=4,
            kv_active_blocks=10, kv_total_blocks=100,
        )
        return ep, handler, metrics

    ep1, h1, m1 = make_worker(w1_drt, "w-one")
    pub1 = KvEventPublisher(ep1.component, "w-one")
    pub1.start()
    s1 = await ep1.serve(
        h1, instance_id="w-one",
        stats_handler=KvMetricsPublisher(m1.to_wire).stats_handler,
    )
    ep2, h2, m2 = make_worker(w2_drt, "w-two")
    pub2 = KvEventPublisher(ep2.component, "w-two")
    pub2.start()
    s2 = await ep2.serve(
        h2, instance_id="w-two",
        stats_handler=KvMetricsPublisher(m2.to_wire).stats_handler,
    )

    # router side
    r_ep = r_drt.namespace("prod").component("backend").endpoint("generate")
    client = Client(r_ep)
    router = await KvRouter(r_ep.component, client, block_size=4, poll_interval=0.02).start()
    await client.wait_for_instances(2)

    # w-two advertises the prefix of our request
    prompt = list(range(100, 116))
    hashes = compute_block_hashes(prompt, 4)
    pub2.publish_stored(hashes, None)
    await asyncio.sleep(0.05)  # event + metrics propagation
    assert router.indexer.find_matches(hashes).scores == {"w-two": 4}

    routed = KvRoutedClient(client, router)
    req = PreprocessedRequest(token_ids=prompt, stop_conditions=StopConditions(max_tokens=1))
    outs = [o async for o in routed.generate(Context(req))]
    assert outs and served["w-two"] == 1 and served["w-one"] == 0

    # worker death → index purged via aggregator on_remove
    await s2.stop()
    hub.expire_lease((await w2_drt.discovery.primary_lease()).id)
    for _ in range(50):     # the purge crosses two tasks: poll, up to 5 s
        await asyncio.sleep(0.1)
        if "w-two" not in router.indexer.find_matches(hashes).scores:
            break
    assert "w-two" not in router.indexer.find_matches(hashes).scores

    await router.stop()
    await s1.stop()
    for d in (w1_drt, w2_drt, r_drt):
        await d.close()


@pytest.mark.asyncio
async def test_recorder_and_replay(tmp_path):
    hub = MemoryHub()
    drt = DistributedRuntime.in_process(hub)
    comp = drt.namespace("p").component("c")
    path = str(tmp_path / "events.jsonl")

    rec = await KvRecorder(comp, path).start()
    pub = KvEventPublisher(comp, "w9")
    pub.start()
    tokens = list(range(8))
    h = compute_block_hashes(tokens, 4)
    pub.publish_stored(h, None)
    pub.publish_removed([h[1]])
    await asyncio.sleep(0.05)
    await rec.stop()
    assert rec.count == 2

    idx = KvIndexer(block_size=4)
    n = replay_events(path, idx)
    assert n == 2
    assert idx.find_matches(h).scores == {"w9": 1}
    await drt.close()


# ---------- staleness-aware cost function ----------


def test_scheduler_skips_stale_workers():
    """A worker whose scrape stopped keeps its last (usually flattering)
    snapshot forever; with a staleness bound the cost function stops
    trusting it and routes to fresh workers even at worse load."""
    from dynamo_tpu.kv_router.indexer import OverlapScores

    t = {"now": 0.0}
    sched = KvScheduler(block_size=4, staleness_bound_s=2.0,
                        clock=lambda: t["now"])
    # the stale worker LOOKS idle; the fresh one looks loaded
    sched.update_metrics("wedged", ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8,
        kv_active_blocks=0, kv_total_blocks=100,
    ))
    sched.update_metrics("alive", ForwardPassMetrics(
        request_active_slots=6, request_total_slots=8,
        kv_active_blocks=50, kv_total_blocks=100,
    ))
    # both fresh: the idle-looking one wins on load
    assert sched.schedule(16, OverlapScores()).worker_id == "wedged"

    # only "alive" keeps scraping; "wedged" ages past the bound
    t["now"] = 5.0
    sched.update_metrics("alive", ForwardPassMetrics(
        request_active_slots=6, request_total_slots=8,
        kv_active_blocks=50, kv_total_blocks=100,
    ))
    d = sched.schedule(16, OverlapScores())
    assert d.worker_id == "alive"
    assert sched.stale_skips == 1


def test_scheduler_all_stale_falls_back_to_routing():
    """Every snapshot stale (scrape loop hiccup) → route on old data
    rather than refusing every request."""
    from dynamo_tpu.kv_router.indexer import OverlapScores

    t = {"now": 0.0}
    sched = KvScheduler(block_size=4, staleness_bound_s=1.0,
                        clock=lambda: t["now"])
    sched.update_metrics("w1", ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8, kv_total_blocks=10,
    ))
    t["now"] = 60.0
    d = sched.schedule(4, OverlapScores())
    assert d.worker_id == "w1"
    assert sched.stale_skips == 0  # fallback is not a skip


def test_scheduler_without_bound_trusts_forever():
    from dynamo_tpu.kv_router.indexer import OverlapScores

    t = {"now": 0.0}
    sched = KvScheduler(block_size=4, clock=lambda: t["now"])
    sched.update_metrics("w1", ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8, kv_total_blocks=10,
    ))
    t["now"] = 1e6
    assert sched.schedule(4, OverlapScores()).worker_id == "w1"
    assert sched.stale_skips == 0

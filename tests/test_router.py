"""BlendedRouter / PrefixAffinityTracker: the fleet-routing blend.

Pins the round-4 scheduling contract: index
score dominates whenever real KV events exist; routed-affinity memory
breaks cold ties (load-aware first placement, then sticky); load breaks
the rest. The tracker is also the index-free `estimated` comparator, so
its LRU/TTL semantics are product code, not test-only logic.
"""


from llm_d_kv_cache_manager_tpu.kvcache import (
    BlendedRouter,
    KVCacheIndexer,
    KVCacheIndexerConfig,
    PrefixAffinityTracker,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock import TokenProcessorConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.keys import PodEntry

BS = 4
MODEL = "m"


def _tracker(n_pods=3, capacity=64, ttl=None):
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import ChunkedTokenDatabase

    return PrefixAffinityTracker(
        n_pods,
        capacity,
        ttl_s=ttl,
        token_processor=ChunkedTokenDatabase(TokenProcessorConfig(block_size=BS)),
    )


class TestPrefixAffinityTracker:
    def test_sticky_after_record(self):
        t = _tracker()
        toks = list(range(16))
        keys = t.keys(toks)
        assert all(t.score(keys, p) == 0 for p in range(3))
        t.record(keys, 1)
        assert t.score(keys, 1) == len(keys) == 4
        assert t.score(keys, 0) == 0

    def test_consecutive_prefix_semantics(self):
        t = _tracker()
        keys = t.keys(list(range(16)))
        # Record only the SECOND block: no consecutive prefix from block 0.
        t.record(keys[1:2], 2)
        assert t.score(keys, 2) == 0

    def test_capacity_lru_evicts_oldest(self):
        t = _tracker(capacity=4)
        a = t.keys(list(range(16)))  # 4 blocks — fills capacity
        b = t.keys(list(range(100, 116)))
        t.record(a, 0)
        t.record(b, 0)  # evicts a's blocks
        assert t.score(b, 0) == 4
        assert t.score(a, 0) == 0

    def test_ttl_expires_affinity(self):
        t = _tracker(ttl=5.0)
        keys = t.keys(list(range(16)))
        t.record(keys, 0, now=10.0)
        assert t.score(keys, 0, now=12.0) == 4
        assert t.score(keys, 0, now=16.1) == 0


class TestBlendedRouter:
    def _setup(self, loads):
        ix = KVCacheIndexer(
            KVCacheIndexerConfig(
                token_processor=TokenProcessorConfig(block_size=BS)
            )
        )
        pods = ["a", "b", "c"]
        tracker = _tracker()
        router = BlendedRouter(
            score_fn=lambda toks, p: ix.score_tokens(toks, MODEL, p),
            affinity=tracker,
            loads_fn=lambda p: [loads[x] for x in p],
        )
        return ix, pods, router

    def test_index_score_dominates(self):
        loads = {"a": 0, "b": 9, "c": 0}
        ix, pods, router = self._setup(loads)
        toks = list(range(16))
        keys = ix.token_processor.tokens_to_kv_block_keys(toks, MODEL)
        ix.kv_block_index.add(keys, [PodEntry("b", "tpu_hbm")])
        # b has the warm prefix: chosen despite the worst load.
        assert router.route(toks, pods).pod == "b"
        ix.shutdown()

    def test_cold_index_uses_load_then_sticks(self):
        loads = {"a": 3, "b": 1, "c": 2}
        ix, pods, router = self._setup(loads)
        toks = list(range(16))
        first = router.route(toks, pods)
        assert first.pod == "b"  # cold everywhere -> least load
        # Same prefix again with b now heavily loaded: affinity keeps it
        # co-located instead of scattering the group.
        loads["b"] = 99
        again = router.route(toks, pods)
        assert again.pod == "b"
        assert again.affinity_score > 0
        # A DIFFERENT prefix goes by load, not to b.
        other = router.route(list(range(200, 216)), pods)
        assert other.pod == "c"
        ix.shutdown()

    def test_decision_reports_decision_time_scores(self):
        loads = {"a": 0, "b": 0, "c": 0}
        ix, pods, router = self._setup(loads)
        toks = list(range(16))
        first = router.route(toks, pods)
        # First-ever placement: everything was cold AT DECISION TIME.
        assert first.index_score == 0 and first.affinity_score == 0
        again = router.route(toks, pods)
        assert again.pod == first.pod
        assert again.affinity_score == 4  # now sticky
        ix.shutdown()

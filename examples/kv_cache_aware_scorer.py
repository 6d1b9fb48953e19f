"""KV-cache-aware scorer plugin sketch for an inference scheduler.

Mirrors the reference's EPP plugin sketch
(``examples/kv_cache_aware_scorer/kvcache_aware_scorer.go:52-112``, which is
build-excluded upstream for the same reason this is an example): shows how a
request scheduler embeds the ``KVCacheIndexer`` as a pluggable pod *scorer* —
``get_pod_scores`` → normalize to [0, 1] per candidate pod — so KV-cache
locality can be weighted against other scorers (load, queue depth, ...).

The ``Scorer`` protocol below matches the shape scheduler frameworks expect:
``score(request, candidate_pods) -> {pod: float in [0,1]}``.

Run: ``python examples/kv_cache_aware_scorer.py``
"""

import os
import sys
from dataclasses import dataclass
from typing import Protocol, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_d_kv_cache_manager_tpu.kvcache import KVCacheIndexer, KVCacheIndexerConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock import PodEntry, TokenProcessorConfig
from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer


@dataclass
class LLMRequest:
    prompt: str
    target_model: str


class Scorer(Protocol):
    """Scheduler plugin interface (the llm-d EPP ``plugins.Scorer`` analogue)."""

    def score(self, request: LLMRequest, pods: Sequence[str]) -> dict[str, float]: ...


class KVCacheAwareScorer:
    """Normalizes indexer hit-depth to [0, 1] over the candidate set
    (reference ``kvcache_aware_scorer.go:85-112``)."""

    def __init__(self, indexer: KVCacheIndexer):
        self.indexer = indexer

    def score(self, request: LLMRequest, pods: Sequence[str]) -> dict[str, float]:
        raw = self.indexer.get_pod_scores(
            request.prompt, request.target_model, pod_identifiers=pods
        )
        scores = {pod: float(raw.get(pod, 0)) for pod in pods}
        max_score = max(scores.values(), default=0.0)
        if max_score == 0.0:
            return {pod: 0.0 for pod in pods}
        return {pod: s / max_score for pod, s in scores.items()}


class CharTokenizer(Tokenizer):
    def encode(self, prompt, model_name):
        return [ord(c) for c in prompt], [(i, i + 1) for i in range(len(prompt))]


def main() -> int:
    model = "meta-llama/Llama-3.1-8B-Instruct"
    indexer = KVCacheIndexer(
        KVCacheIndexerConfig(token_processor=TokenProcessorConfig(block_size=16)),
        tokenizer=CharTokenizer(),
    )
    indexer.run()
    try:
        prompt = "you are a helpful assistant. " * 8
        request = LLMRequest(prompt=prompt, target_model=model)
        pods = ["tpu-pod-1", "tpu-pod-2", "tpu-pod-3"]

        # Warm pod-1 with the whole prefix and pod-2 with half of it.
        keys = indexer.token_processor.tokens_to_kv_block_keys(
            [ord(c) for c in prompt], model
        )
        indexer.kv_block_index.add(keys, [PodEntry("tpu-pod-1")])
        indexer.kv_block_index.add(keys[: len(keys) // 2], [PodEntry("tpu-pod-2")])

        scorer: Scorer = KVCacheAwareScorer(indexer)
        scores = scorer.score(request, pods)
        print(f"normalized scores: {scores}")
        assert scores["tpu-pod-1"] == 1.0
        assert 0.0 < scores["tpu-pod-2"] < 1.0
        assert scores["tpu-pod-3"] == 0.0

        # For schedulers that want the whole decision (not just one scorer
        # in a blend), kvcache.BlendedRouter ships the measured-best blend:
        # index score -> routed-affinity tiebreak -> load.
        from llm_d_kv_cache_manager_tpu.kvcache import (
            BlendedRouter,
            PrefixAffinityTracker,
        )

        router = BlendedRouter(
            score_fn=lambda toks, names: indexer.score_tokens(toks, model, names),
            affinity=PrefixAffinityTracker(
                len(pods), capacity_blocks=4096,
                token_processor=indexer.token_processor,
            ),
            loads_fn=lambda names: [0.0] * len(names),  # wire real queue depths
        )
        decision = router.route([ord(c) for c in prompt], pods)
        print(f"blended decision: {decision}")
        assert decision.pod == "tpu-pod-1"  # warmest prefix wins
        print("OK")
        return 0
    finally:
        indexer.shutdown()


if __name__ == "__main__":
    sys.exit(main())

"""Prefix-store tests (reference ``lru_store_test.go``) for both stores."""

import pytest

from llm_d_kv_cache_manager_tpu.tokenization.prefixstore import (
    Config,
    ContainedTokenStore,
    LRUTokenStore,
)


def _fixture(block_size=4):
    """Deterministic prompt/token/offset fixture: 1 token per 2 bytes."""
    prompt = "abcdefghijklmnop"  # 16 bytes
    tokens = list(range(100, 108))  # 8 tokens
    offsets = [(i * 2, i * 2 + 2) for i in range(8)]
    return prompt, tokens, offsets


class TestLRUTokenStore:
    def test_full_match(self):
        store = LRUTokenStore(Config(block_size=4))
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)
        got, ratio = store.find_longest_contained_tokens(prompt, "m")
        assert got == tokens
        assert ratio == 1.0

    def test_partial_match_ratio(self):
        store = LRUTokenStore(Config(block_size=4))
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)
        # Same first 8 bytes (2 blocks), divergent afterwards.
        probe = prompt[:8] + "XXXXXXXX"
        got, ratio = store.find_longest_contained_tokens(probe, "m")
        assert got == tokens[:4]
        assert ratio == 0.5

    def test_no_match(self):
        store = LRUTokenStore(Config(block_size=4))
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)
        got, ratio = store.find_longest_contained_tokens("ZZZZZZZZ", "m")
        assert got == []
        assert ratio == 0.0

    def test_unknown_model(self):
        store = LRUTokenStore()
        got, ratio = store.find_longest_contained_tokens("abc", "nope")
        assert (got, ratio) == ([], 0.0)

    def test_short_prompt_no_full_block(self):
        store = LRUTokenStore(Config(block_size=256))
        store.add_tokenization("m", "short", [1], [(0, 5)])
        got, ratio = store.find_longest_contained_tokens("short", "m")
        assert (got, ratio) == ([], 0.0)

    def test_token_spanning_block_boundary_deferred(self):
        # Token with high offset beyond block end lands in the next block.
        store = LRUTokenStore(Config(block_size=4))
        prompt = "abcdefgh"
        tokens = [1, 2]
        offsets = [(0, 3), (3, 6)]  # token 2 crosses the 4-byte boundary
        store.add_tokenization("m", prompt, tokens, offsets)
        got, ratio = store.find_longest_contained_tokens(prompt[:4] + "XXXX", "m")
        assert got == [1]  # token 2 only contained in block 2, which missed
        # and the lookup says where the tokens it found end: byte 3, not 4
        assert store.find_longest_contained(prompt[:4] + "XXXX", "m") == (
            [1], 0.5, 3)
        assert store.find_longest_contained(prompt, "m") == ([1, 2], 1.0, 6)
        assert store.find_longest_contained("none", "m") == ([], 0.0, 0)
        # a special token behind the text has the offsets (0, 0): the end stays
        store.add_tokenization("s", "abcd", [1, 2, 9], [(0, 2), (2, 4), (0, 0)])
        assert store.find_longest_contained("abcdXY", "s") == ([1, 2, 9], 4 / 6, 4)

    def test_eviction(self):
        store = LRUTokenStore(Config(block_size=4, cache_size=2))
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)  # 4 blocks → only 2 kept
        got, ratio = store.find_longest_contained_tokens(prompt, "m")
        # first blocks were evicted → chain breaks immediately
        assert got == []
        assert ratio == 0.0

    def test_multibyte_prompt_uses_byte_blocks(self):
        store = LRUTokenStore(Config(block_size=4))
        prompt = "ééé"  # 3 chars, 6 bytes → one full 4-byte block
        tokens = [7]
        offsets = [(0, 2)]  # first é in bytes
        store.add_tokenization("m", prompt, tokens, offsets)
        got, ratio = store.find_longest_contained_tokens(prompt, "m")
        assert got == [7]
        assert ratio == pytest.approx(4 / 6)

    def test_mismatched_lengths_raise(self):
        store = LRUTokenStore()
        with pytest.raises(ValueError):
            store.add_tokenization("m", "abc", [1, 2], [(0, 1)])


class TestContainedTokenStore:
    def test_full_match(self):
        store = ContainedTokenStore()
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)
        got, ratio = store.find_longest_contained_tokens(prompt, "m")
        assert got == tokens
        assert ratio == 1.0

    def test_partial_match(self):
        store = ContainedTokenStore()
        prompt, tokens, offsets = _fixture()
        store.add_tokenization("m", prompt, tokens, offsets)
        probe = prompt[:6] + "ZZZ"
        got, ratio = store.find_longest_contained_tokens(probe, "m")
        # 6 chars matched → tokens with high ≤ 6 contained
        assert got == tokens[:3]
        assert ratio == pytest.approx(6 / 9)

    def test_zero_width_special_tokens_at_root(self):
        store = ContainedTokenStore()
        # CLS-style token with (0,0) offset, then a real token.
        store.add_tokenization("m", "ab", [101, 5], [(0, 0), (0, 2)])
        got, ratio = store.find_longest_contained_tokens("ab", "m")
        assert got == [101, 5]

    def test_no_intermediate_token_skipping(self):
        store = ContainedTokenStore()
        # Two tokens end at the same char position (zero-width second token):
        # both must be returned, in order.
        store.add_tokenization("m", "ab", [1, 2, 3], [(0, 1), (1, 1), (1, 2)])
        got, _ = store.find_longest_contained_tokens("ab", "m")
        assert got == [1, 2, 3]

    def test_no_cross_tokenization_splicing(self):
        # Overlapping inserts must never splice tokens from different
        # tokenizations into one returned sequence.
        store = ContainedTokenStore()
        store.add_tokenization("m", "abcd", [10, 11], [(0, 2), (2, 4)])
        store.add_tokenization("m", "abe", [20, 21], [(0, 1), (1, 3)])
        got, ratio = store.find_longest_contained_tokens("abcd", "m")
        # The newer insert overwrote the shared 'a'/'b' nodes; the walk must
        # stop at the generation change instead of returning [20, 11].
        assert got in ([], [20], [20, 21])  # never a spliced sequence
        assert 11 not in got
        assert ratio < 1.0
        # The newer tokenization itself is fully retrievable.
        got2, ratio2 = store.find_longest_contained_tokens("abe", "m")
        assert got2 == [20, 21]
        assert ratio2 == 1.0

    def test_bounded_growth_prunes_stale_paths(self):
        # The reference trie grows without limit; this store caps nodes per
        # model. Stale-generation subtrees (unreachable to lookups anyway)
        # are pruned once the budget is exceeded.
        store = ContainedTokenStore(Config(trie_max_nodes=32))
        for i in range(100):
            prompt = f"prompt-{i:03d}-" + "x" * 10
            toks = list(range(len(prompt)))
            offs = [(j, j + 1) for j in range(len(prompt))]
            store.add_tokenization("m", prompt, toks, offs)
            assert store.node_count("m") <= 32
        # The most recent insert stays fully retrievable after pruning
        # (its path is 21 chars < budget).
        last = "prompt-099-" + "x" * 10
        got, ratio = store.find_longest_contained_tokens(last, "m")
        assert ratio == 1.0
        assert got == list(range(len(last)))

    def test_budget_truncates_oversized_single_path(self):
        # One tokenization longer than the whole budget: keep a truncated
        # prefix rather than exceeding the cap.
        store = ContainedTokenStore(Config(trie_max_nodes=8))
        prompt = "a" * 50
        store.add_tokenization(
            "m", prompt, list(range(50)), [(j, j + 1) for j in range(50)]
        )
        assert store.node_count("m") <= 8
        got, ratio = store.find_longest_contained_tokens(prompt, "m")
        assert 0 < ratio < 1.0
        assert got == list(range(len(got)))  # a clean prefix, no gaps

    def test_model_lru_eviction(self):
        store = ContainedTokenStore()
        n = store.MAX_MODELS
        for i in range(n + 5):
            store.add_tokenization(f"model-{i}", "ab", [1, 2], [(0, 1), (1, 2)])
        assert len(store._tries) == n
        # Oldest models evicted whole; newest retrievable.
        assert store.find_longest_contained_tokens("ab", "model-0") == ([], 0.0)
        got, ratio = store.find_longest_contained_tokens("ab", f"model-{n + 4}")
        assert got == [1, 2] and ratio == 1.0

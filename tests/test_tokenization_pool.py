"""Tokenization pool tests with mock tokenizer
(reference ``pkg/tokenization/pool_test.go``)."""

import threading
import time

import pytest

from llm_d_kv_cache_manager_tpu.tokenization import (
    TokenizationPool,
    TokenizationPoolConfig,
    Tokenizer,
    char_offsets_to_byte_offsets,
)
from llm_d_kv_cache_manager_tpu.tokenization.prefixstore import Config, LRUTokenStore


class MockTokenizer(Tokenizer):
    """Deterministic: each char → one token (ord), offsets 1 byte each."""

    def __init__(self, fail_times: int = 0, delay: float = 0.0):
        self.calls = 0
        self.fail_times = fail_times
        self.delay = delay
        self._lock = threading.Lock()

    def encode(self, prompt, model_name):
        with self._lock:
            self.calls += 1
            if self.calls <= self.fail_times:
                raise RuntimeError("transient tokenizer failure")
        if self.delay:
            time.sleep(self.delay)
        tokens = [ord(c) for c in prompt]
        offsets = [(i, i + 1) for i in range(len(prompt))]
        return tokens, offsets


@pytest.fixture
def pool():
    p = TokenizationPool(
        TokenizationPoolConfig(workers_count=3),
        store=LRUTokenStore(Config(block_size=4)),
        tokenizer=MockTokenizer(),
    )
    p.run()
    yield p
    p.shutdown()


class TestTokenizationPool:
    def test_sync_tokenize_passthrough(self, pool):
        tokens = pool.tokenize("abcdefgh", "m")
        assert tokens == [ord(c) for c in "abcdefgh"]

    def test_prefix_store_fast_path(self):
        tok = MockTokenizer()
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=4)),
            tokenizer=tok,
        )
        p.run()
        try:
            p.tokenize("abcdefgh", "m")
            assert tok.calls == 1
            # Identical prompt: 100% overlap → no new tokenizer call.
            p.tokenize("abcdefgh", "m")
            assert tok.calls == 1
            # Mostly-shared prompt under threshold → full tokenize again.
            p.tokenize("abcdefghXXXXXXXXXXXX", "m")
            assert tok.calls == 2
        finally:
            p.shutdown()

    @pytest.mark.parametrize("cap, calls", [(1024, 1), (24, 2), (25, 1)])
    def test_a_long_uncovered_tail_is_tokenized_whatever_the_ratio(
        self, cap, calls, monkeypatch
    ):
        # 96 of 120 bytes cached: the ratio (0.8) takes the cached prefix;
        # the 24 bytes it leaves uncovered do not when the cap is that low
        from llm_d_kv_cache_manager_tpu.tokenization import pool as pool_module

        monkeypatch.setattr(pool_module, "MAX_UNCOVERED_BYTES", cap)
        tok = MockTokenizer()
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=4)),
            tokenizer=tok,
        )
        p.run()
        try:
            document = "abcd" * 24
            p.tokenize(document, "m")
            grown = p.tokenize(document + "wxyz" * 6, "m")
            assert tok.calls == calls
            assert len(grown) == (120 if calls == 2 else 96)
        finally:
            p.shutdown()

    def test_async_enqueue(self, pool):
        pool.enqueue_tokenization("abcdefgh", "m")
        deadline = time.time() + 5
        while time.time() < deadline:
            got, ratio = pool.indexer.find_longest_contained_tokens("abcdefgh", "m")
            if ratio == 1.0:
                break
            time.sleep(0.01)
        assert ratio == 1.0
        assert got == [ord(c) for c in "abcdefgh"]

    def test_retry_on_transient_failure(self):
        tok = MockTokenizer(fail_times=2)
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=4)),
            tokenizer=tok,
        )
        p.run()
        try:
            tokens = p.tokenize("abcd", "m", timeout=10)
            assert tokens == [ord(c) for c in "abcd"]
            assert tok.calls == 3
        finally:
            p.shutdown()

    def test_concurrent_callers(self, pool):
        results = {}

        def call(i):
            results[i] = pool.tokenize(f"prompt-{i:04d}-" + "x" * 32, "m")

        threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 16
        for i, tokens in results.items():
            assert tokens == [ord(c) for c in f"prompt-{i:04d}-" + "x" * 32]

    def test_shutdown_idempotent(self, pool):
        pool.shutdown()
        pool.shutdown()

    def test_permanent_failure_raises(self):
        from llm_d_kv_cache_manager_tpu.tokenization import TokenizationError

        tok = MockTokenizer(fail_times=10**6)
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=4)),
            tokenizer=tok,
        )
        p.run()
        try:
            with pytest.raises(TokenizationError):
                p.tokenize("abcd", "m", timeout=10)
        finally:
            p.shutdown()


class TestOffsetsConversion:
    def test_ascii_identity(self):
        assert char_offsets_to_byte_offsets("abc", [(0, 1), (1, 3)]) == [(0, 1), (1, 3)]

    def test_multibyte(self):
        # "héllo": h=1B, é=2B → char offsets (0,5) → byte offsets (0,6)
        assert char_offsets_to_byte_offsets("héllo", [(0, 5)]) == [(0, 6)]
        assert char_offsets_to_byte_offsets("héllo", [(1, 2)]) == [(1, 3)]

    def test_out_of_range_clamped(self):
        assert char_offsets_to_byte_offsets("ab", [(0, 99)]) == [(0, 2)]

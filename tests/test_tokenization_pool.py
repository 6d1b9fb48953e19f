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
        self.tails: list[str] = []
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

    def encode_tail(self, text, model_name):
        self.tails.append(text)
        return [ord(c) for c in text]


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
        # 96 of 120 bytes cached: the ratio (0.8) takes the cached prefix
        # and tokenizes the 24 bytes behind it on their own; when the cap is
        # that low the whole prompt is tokenized and written back
        from llm_d_kv_cache_manager_tpu.tokenization import pool as pool_module

        monkeypatch.setattr(pool_module, "MAX_UNCOVERED_BYTES", cap)
        tok = MockTokenizer()
        store = LRUTokenStore(Config(block_size=4))
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1), store=store, tokenizer=tok,
        )
        p.run()
        try:
            document = "abcd" * 24
            p.tokenize(document, "m")
            grown = document + "wxyz" * 6
            assert p.tokenize(grown, "m") == [ord(c) for c in grown]
            assert tok.calls == calls
            assert tok.tails == ([] if calls == 2 else ["wxyz" * 6])
            # a tail is not written back: the store holds what it held
            held = len(store.find_longest_contained_tokens(grown, "m")[0])
            assert held == (120 if calls == 2 else 96)
        finally:
            p.shutdown()

    def test_a_prompt_that_is_no_whole_blocks_comes_back_whole(self):
        # a thread of 1920 characters is seven and a half of the store's
        # blocks, and was only ever tokenized with a turn behind it: asked
        # for alone, its last 128 characters are tokenized on their own
        tok = MockTokenizer()
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=256)),
            tokenizer=tok,
        )
        p.run()
        try:
            thread = "".join(chr(97 + i % 23) for i in range(1920))
            p.tokenize(thread + "?" * 16, "m")
            assert p.tokenize(thread, "m") == [ord(c) for c in thread]
            assert tok.calls == 1 and tok.tails == [thread[1792:]]
            # and with another turn behind it
            turn = thread + "!" * 96
            assert p.tokenize(turn, "m") == [ord(c) for c in turn]
            assert tok.calls == 1 and tok.tails[-1] == turn[1792:]
        finally:
            p.shutdown()

    def test_the_tail_begins_where_the_cached_tokens_end(self):
        # tokens of three bytes over blocks of four: a token that lies
        # across a block's end belongs to the next block, so the cached
        # tokens end before the covered bytes do and the tail begins there
        class Threes(MockTokenizer):
            def encode(self, prompt, model_name):
                self.calls += 1
                n = len(prompt)
                return (
                    [sum(map(ord, prompt[i:i + 3])) for i in range(0, n, 3)],
                    [(i, min(i + 3, n)) for i in range(0, n, 3)],
                )

            def encode_tail(self, text, model_name):
                self.tails.append(text)
                return Threes.encode(self, text, model_name)[0]

        tok = Threes()
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=LRUTokenStore(Config(block_size=4)),
            tokenizer=tok,
        )
        p.run()
        try:
            text = "abcdefghijklmnopqrstuvwxyzABCDEF"  # 32 bytes: 8 blocks
            whole = p.tokenize(text, "m")
            # 28 of 34 bytes covered (0.82); the tokens found end at byte 27
            assert p.tokenize(text[:28] + "012345", "m") == [
                *whole[:9], *tok.encode(text[27] + "012345", "m")[0]]
            assert tok.tails == [text[27] + "012345"]
        finally:
            p.shutdown()

    def test_a_store_that_keeps_no_end_leaves_the_tail_out(self):
        from llm_d_kv_cache_manager_tpu.tokenization.prefixstore import (
            ContainedTokenStore,
        )

        tok = MockTokenizer()
        p = TokenizationPool(
            TokenizationPoolConfig(workers_count=1),
            store=ContainedTokenStore(Config()), tokenizer=tok,
        )
        p.run()
        try:
            document = "abcd" * 24
            p.tokenize(document, "m")
            assert len(p.tokenize(document + "wxyz" * 6, "m")) == 96
            assert tok.tails == []
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

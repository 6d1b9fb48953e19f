"""Event plane tests: schema round-trip, legacy tolerance, sharded ordering,
poison pills, and the end-to-end ZMQ offline-demo flow (reference §3.5)."""

import struct
import time

import msgpack

from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
    DeviceTier,
    InMemoryIndex,
    Key,
    PodEntry,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import (
    AllBlocksCleared,
    BlockRemoved,
    BlockStored,
    EventBatch,
    Heartbeat,
    IndexSnapshot,
    KVEventsPool,
    KVEventsPoolConfig,
    Message,
    ZMQPublisher,
    ZMQPublisherConfig,
    ZMQSubscriber,
    ZMQSubscriberConfig,
    decode_event_batch,
    fnv1a_32,
    parse_topic,
)

MODEL = "meta-llama/Llama-3-8B"


class TestEventSchema:
    def test_round_trip(self):
        batch = EventBatch(
            ts=123.5,
            events=[
                BlockStored(
                    block_hashes=[1, 2, 3],
                    parent_block_hash=7,
                    token_ids=[10, 11],
                    block_size=16,
                    medium="tpu_hbm",
                ),
                BlockRemoved(block_hashes=[2], medium="host_dram"),
                AllBlocksCleared(),
            ],
            data_parallel_rank=1,
        )
        decoded = decode_event_batch(batch.to_payload())
        assert decoded.ts == 123.5
        assert decoded.data_parallel_rank == 1
        bs, br, ac = decoded.events
        assert bs == batch.events[0]
        assert br == batch.events[1]
        assert isinstance(ac, AllBlocksCleared)

    def test_legacy_block_stored_without_medium(self):
        # Legacy arity: [tag, hashes, parent, tokens, block_size, lora_id]
        raw = [1000.0, [["BlockStored", [5, 6], None, [1, 2], 16, None]]]
        decoded = decode_event_batch(msgpack.packb(raw))
        (ev,) = decoded.events
        assert ev.block_hashes == [5, 6]
        assert ev.medium is None

    def test_legacy_block_removed_minimal(self):
        raw = [1000.0, [["BlockRemoved", [5]]]]
        decoded = decode_event_batch(msgpack.packb(raw))
        (ev,) = decoded.events
        assert ev.block_hashes == [5]
        assert ev.medium is None

    def test_unknown_tag_skipped(self):
        raw = [1.0, [["FutureEvent", 1, 2], ["BlockRemoved", [9]]]]
        decoded = decode_event_batch(msgpack.packb(raw))
        assert len(decoded.events) == 1
        assert decoded.events[0].block_hashes == [9]

    def test_poison_pill_returns_none(self):
        assert decode_event_batch(b"\xff\xfe not msgpack") is None
        assert decode_event_batch(msgpack.packb("just a string")) is None
        assert decode_event_batch(msgpack.packb([1.0])) is None
        assert decode_event_batch(msgpack.packb(["not-a-ts", []])) is None
        assert decode_event_batch(msgpack.packb([None, []])) is None

    def test_nested_raw_event_bytes(self):
        # Events may arrive as embedded msgpack blobs (reference RawMessage).
        inner = msgpack.packb(["BlockRemoved", [4], None])
        decoded = decode_event_batch(msgpack.packb([1.0, [inner]]))
        assert decoded.events[0].block_hashes == [4]

    def test_uint64_hashes_survive(self):
        big = 2**64 - 1
        batch = EventBatch(ts=0.0, events=[BlockStored(block_hashes=[big])])
        decoded = decode_event_batch(batch.to_payload())
        assert decoded.events[0].block_hashes == [big]

    def test_heartbeat_round_trip(self):
        batch = EventBatch(ts=1.0, events=[Heartbeat(dropped_batches=7)])
        (ev,) = decode_event_batch(batch.to_payload()).events
        assert ev == Heartbeat(dropped_batches=7)
        # bare legacy form: ["Heartbeat"] with no fields
        (ev,) = decode_event_batch(msgpack.packb([1.0, [["Heartbeat"]]])).events
        assert ev == Heartbeat(dropped_batches=0)

    def test_index_snapshot_round_trip(self):
        snap = IndexSnapshot(
            blocks_by_medium={"tpu_hbm": [1, 2, 2**64 - 1], "host_dram": []}
        )
        batch = EventBatch(ts=1.0, events=[snap])
        (ev,) = decode_event_batch(batch.to_payload()).events
        assert ev == snap

    def test_malformed_snapshot_skipped(self):
        cases = [
            [1.0, [["IndexSnapshot"]]],                       # no digest
            [1.0, [["IndexSnapshot", ["not", "a", "dict"]]]],
            [1.0, [["IndexSnapshot", {"tpu_hbm": "not-a-list"}]]],
            [1.0, [["Heartbeat", "not-an-int"]]],             # tolerated → 0
        ]
        for case in cases[:3]:
            decoded = decode_event_batch(msgpack.packb(case))
            assert decoded is not None and decoded.events == []
        (hb,) = decode_event_batch(msgpack.packb(cases[-1])).events
        assert hb == Heartbeat(dropped_batches=0)


class TestFNV:
    def test_known_vectors(self):
        # Standard FNV-1a 32-bit test vectors.
        assert fnv1a_32(b"") == 0x811C9DC5
        assert fnv1a_32(b"a") == 0xE40C292C
        assert fnv1a_32(b"foobar") == 0xBF9CF968


class TestTopicParsing:
    def test_valid(self):
        assert parse_topic("kv@pod-1@meta-llama/Llama-3-8B") == ("pod-1", "meta-llama/Llama-3-8B")

    def test_model_with_at(self):
        assert parse_topic("kv@pod@org/model@rev") == ("pod", "org/model@rev")

    def test_invalid(self):
        assert parse_topic("kv@podonly") is None
        assert parse_topic("nonsense") is None
        assert parse_topic("kv@@model") is None


def _stored_payload(hashes, medium=None):
    return EventBatch(
        ts=time.time(), events=[BlockStored(block_hashes=hashes, medium=medium)]
    ).to_payload()


def _removed_payload(hashes, medium=None):
    return EventBatch(
        ts=time.time(), events=[BlockRemoved(block_hashes=hashes, medium=medium)]
    ).to_payload()


class TestKVEventsPool:
    def test_add_and_remove_flow(self):
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=2))
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([1, 2, 3])))
            assert pool.drain()
            got = index.lookup([Key(MODEL, h) for h in (1, 2, 3)], set())
            assert all(got[Key(MODEL, h)] == ["pod-1"] for h in (1, 2, 3))

            pool.add_task(Message("t", "pod-1", MODEL, _removed_payload([2])))
            assert pool.drain()
            got = index.lookup([Key(MODEL, 2)], set())
            assert got.get(Key(MODEL, 2), []) == []
        finally:
            pool.shutdown()

    def test_medium_maps_to_tier(self):
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([7], medium="host_dram")))
            assert pool.drain()
            # evicting the hbm-tier entry must not remove the dram-tier entry
            index.evict(Key(MODEL, 7), [PodEntry("pod-1", DeviceTier.TPU_HBM)])
            got = index.lookup([Key(MODEL, 7)], set())
            assert got[Key(MODEL, 7)] == ["pod-1"]
        finally:
            pool.shutdown()

    def test_mediumless_remove_clears_all_tiers(self):
        # A legacy BlockRemoved (no medium) must evict the pod's entry even
        # when the block was stored with an explicit medium.
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([7], medium="host_dram")))
            assert pool.drain()
            pool.add_task(Message("t", "pod-1", MODEL, _removed_payload([7])))  # no medium
            assert pool.drain()
            got = index.lookup([Key(MODEL, 7)], set())
            assert got.get(Key(MODEL, 7), []) == []
        finally:
            pool.shutdown()

    def test_poison_pill_does_not_kill_worker(self):
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, b"\x00garbage"))
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([42])))
            assert pool.drain()
            got = index.lookup([Key(MODEL, 42)], set())
            assert got[Key(MODEL, 42)] == ["pod-1"]
        finally:
            pool.shutdown()

    def test_per_pod_ordering_under_concurrency(self):
        """Store/remove pairs for one pod must apply in order even with many
        interleaved pods; final state must reflect the last event per pod."""
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=4))
        pool.start()
        try:
            pods = [f"pod-{i}" for i in range(8)]
            for round_ in range(50):
                for pod in pods:
                    pool.add_task(Message("t", pod, MODEL, _stored_payload([round_])))
                    if round_ % 2 == 0:
                        pool.add_task(Message("t", pod, MODEL, _removed_payload([round_])))
            assert pool.drain(timeout=10)
            # odd rounds stored and never removed; even rounds removed last
            for round_ in range(50):
                got = index.lookup([Key(MODEL, round_)], set())
                pods_found = set(got.get(Key(MODEL, round_), []))
                if round_ % 2 == 0:
                    assert pods_found == set(), f"round {round_}: {pods_found}"
                else:
                    assert pods_found == set(pods), f"round {round_}: {pods_found}"
        finally:
            pool.shutdown()


class TestZMQEndToEnd:
    """The offline-demo acceptance flow (reference §3.5): score empty →
    publish BlockStored → score hits → publish BlockRemoved → score reduced."""

    def test_offline_demo_flow(self):
        from llm_d_kv_cache_manager_tpu.kvcache import KVCacheIndexer, KVCacheIndexerConfig
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock import TokenProcessorConfig
        from conftest import CharTokenizer as CharTok, free_tcp_port

        port = free_tcp_port()
        indexer = KVCacheIndexer(
            KVCacheIndexerConfig(token_processor=TokenProcessorConfig(block_size=4)),
            tokenizer=CharTok(),
        )
        indexer.run()
        pool = KVEventsPool(indexer.kv_block_index, KVEventsPoolConfig(concurrency=2))
        pool.start()
        sub = ZMQSubscriber(pool, ZMQSubscriberConfig(endpoint=f"tcp://*:{port}"))
        sub.start()

        prompt = "abcdefghijklmnop"  # 4 blocks of 4
        keys = indexer.token_processor.tokens_to_kv_block_keys(
            [ord(c) for c in prompt], MODEL
        )
        hashes = [k.chunk_hash for k in keys]

        try:
            pub = ZMQPublisher(
                ZMQPublisherConfig(
                    endpoint=f"tcp://localhost:{port}",
                    pod_identifier="tpu-pod-1",
                    model_name=MODEL,
                )
            )
            # PUB/SUB needs the subscription to propagate; retry-publish until
            # the subscriber sees it (slow-joiner handling).
            assert indexer.get_pod_scores(prompt, MODEL) == {}

            deadline = time.time() + 20
            scores = {}
            while time.time() < deadline and not scores:
                pub.publish([BlockStored(block_hashes=hashes, token_ids=[], block_size=4)])
                time.sleep(0.2)
                scores = indexer.get_pod_scores(prompt, MODEL)
            assert scores == {"tpu-pod-1": 4}

            # Remove the last two blocks → score drops to 2.
            pub.publish([BlockRemoved(block_hashes=hashes[2:])])
            deadline = time.time() + 10
            while time.time() < deadline:
                scores = indexer.get_pod_scores(prompt, MODEL)
                if scores == {"tpu-pod-1": 2}:
                    break
                time.sleep(0.1)
            assert scores == {"tpu-pod-1": 2}
            pub.close()
        finally:
            sub.shutdown()
            pool.shutdown()
            indexer.shutdown()


class TestPublisherHardening:
    """ISSUE 2 satellite: idempotent close and bounded send retry/backoff —
    a transient socket error must never raise into the engine loop."""

    @staticmethod
    def _pub():
        from conftest import free_tcp_port

        return ZMQPublisher(
            ZMQPublisherConfig(endpoint=f"tcp://localhost:{free_tcp_port()}")
        )

    def test_double_close_is_idempotent(self):
        pub = self._pub()
        pub.close()
        pub.close()  # second close must not hit the closed socket

    def test_publish_after_close_drops_without_raising(self):
        pub = self._pub()
        pub.close()
        assert pub.publish([BlockStored(block_hashes=[1], block_size=4)]) == -1
        assert pub.dropped_batches == 1

    def test_send_failure_retries_then_succeeds(self, monkeypatch):
        import zmq

        pub = self._pub()
        calls = []

        def flaky(frames):
            calls.append(frames)
            if len(calls) < 3:
                raise zmq.ZMQError()

        monkeypatch.setattr(pub._sock, "send_multipart", flaky)
        monkeypatch.setattr(time, "sleep", lambda s: None)
        seq = pub.publish([BlockStored(block_hashes=[1], block_size=4)])
        assert seq == 0 and len(calls) == 3
        assert pub.dropped_batches == 0
        pub.close()

    def test_send_failure_bounded_then_drops(self, monkeypatch):
        import zmq

        pub = self._pub()
        calls = []

        def dead(frames):
            calls.append(frames)
            raise zmq.ZMQError()

        monkeypatch.setattr(pub._sock, "send_multipart", dead)
        monkeypatch.setattr(time, "sleep", lambda s: None)
        # Never raises into the caller; attempts are bounded; the batch is
        # dropped and counted. The next publish still works (and keeps its
        # own seq, so subscribers see the gap).
        assert pub.publish([BlockStored(block_hashes=[1], block_size=4)]) == -1
        assert len(calls) == 3 and pub.dropped_batches == 1
        monkeypatch.setattr(pub._sock, "send_multipart", lambda frames: None)
        assert pub.publish([BlockStored(block_hashes=[2], block_size=4)]) == 1
        pub.close()


class TestZMQReconnect:
    """Failure-detection parity (SURVEY §5): the subscriber reconnects with
    backoff after socket errors — here the endpoint is initially occupied by
    another socket (bind fails repeatedly) and the subscriber must recover
    and deliver events once the port frees up."""

    def test_recovers_after_bind_conflict(self, monkeypatch):
        import zmq

        from llm_d_kv_cache_manager_tpu.kvcache.kvevents import zmq_subscriber

        monkeypatch.setattr(zmq_subscriber, "_RECONNECT_BACKOFF_S", 0.1)

        from chaos import wait_until
        from conftest import free_tcp_port

        port = free_tcp_port()
        ctx = zmq.Context.instance()
        squatter = ctx.socket(zmq.PUB)
        squatter.bind(f"tcp://*:{port}")

        from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
            InMemoryIndex,
            InMemoryIndexConfig,
            Key,
        )

        index = InMemoryIndex(InMemoryIndexConfig(size=100, pod_cache_size=4))
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        sub = ZMQSubscriber(pool, ZMQSubscriberConfig(endpoint=f"tcp://*:{port}"))
        binds = []
        run_subscriber = sub._run_subscriber
        sub._run_subscriber = lambda ctx: (binds.append(1), run_subscriber(ctx))[1]
        sub.start()
        try:
            # a few failed bind/backoff cycles: counted, not slept through
            assert wait_until(lambda: len(binds) >= 4, timeout=20)
            squatter.close(linger=0)

            pub = ZMQPublisher(
                ZMQPublisherConfig(
                    endpoint=f"tcp://localhost:{port}",
                    pod_identifier="pod-r",
                    model_name=MODEL,
                )
            )
            deadline = time.time() + 20
            found = {}
            while time.time() < deadline and not found:
                pub.publish(
                    [BlockStored(block_hashes=[7], token_ids=[], block_size=4)]
                )
                time.sleep(0.2)
                found = index.lookup([Key(MODEL, 7)], set())
            pub.close()
            assert found.get(Key(MODEL, 7)) == ["pod-r"]
        finally:
            sub.shutdown()
            pool.shutdown()


class TestDecodeFuzz:
    """Decoder robustness: arbitrary bytes and structurally-mutated msgpack
    must never raise — the reference drops poison pills, never crashes
    (pool.go:175-180), and the subscriber feeds the pool raw network input."""

    def test_random_bytes_never_raise(self):
        import random

        rng = random.Random(0)
        for _ in range(500):
            blob = bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 64)))
            decode_event_batch(blob)  # None or EventBatch; never an exception

    def test_mutated_valid_payloads_never_raise(self):
        import random

        rng = random.Random(1)
        base = EventBatch(
            ts=1.0,
            events=[
                BlockStored(block_hashes=[1, 2], token_ids=[3, 4], block_size=4),
                BlockRemoved(block_hashes=[2]),
            ],
        ).to_payload()
        for _ in range(500):
            blob = bytearray(base)
            for _ in range(rng.randrange(1, 4)):
                blob[rng.randrange(len(blob))] = rng.getrandbits(8)
            decode_event_batch(bytes(blob))

    def test_structural_garbage_never_raises(self):
        cases = [
            [1.0, [["BlockStored"]]],                      # missing all fields
            [1.0, [["BlockStored", "not-a-list", 1, 2, 3]]],
            [1.0, [["BlockStored", [None], None, None, "x", None, 5]]],
            [1.0, [["BlockRemoved", {"a": 1}]]],
            [1.0, [[123, [1]]]],                           # non-string tag
            [1.0, [None, 5, "str"]],                       # non-event entries
            ["ts", []],
            [1.0, "not-a-list"],
            [1.0, [["BlockStored", [1], None, [1], 4, None, 42]]],  # int medium
        ]
        for case in cases:
            decode_event_batch(msgpack.packb(case))

    def test_snapshot_and_heartbeat_through_pool(self):
        """Self-healing events flow through the worker pool: a snapshot
        replaces the pod's view; a heartbeat is a harmless no-op without an
        attached FleetHealth (legacy pools stay bit-identical)."""
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([1, 2])))
            snap = EventBatch(
                ts=0.0,
                events=[
                    Heartbeat(),
                    IndexSnapshot(blocks_by_medium={"tpu_hbm": [2, 3]}),
                ],
            ).to_payload()
            pool.add_task(Message("t", "pod-1", MODEL, snap))
            assert pool.drain()
            got = index.lookup([Key(MODEL, h) for h in (1, 2, 3)], set())
            assert got.get(Key(MODEL, 1), []) == []  # replaced away
            assert got[Key(MODEL, 2)] == ["pod-1"]
            assert got[Key(MODEL, 3)] == ["pod-1"]
        finally:
            pool.shutdown()

    def test_fuzz_through_pool_worker(self):
        """Same robustness at the pool level: garbage tasks never kill the
        worker; a valid task after 200 fuzzed ones still lands."""
        import random

        rng = random.Random(2)
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        try:
            for i in range(200):
                blob = bytes(rng.getrandbits(8) for _ in range(rng.randrange(1, 48)))
                pool.add_task(Message("t", f"pod-{i%3}", MODEL, blob))
            pool.add_task(Message("t", "pod-ok", MODEL, _stored_payload([99])))
            assert pool.drain(timeout=30)
            got = index.lookup([Key(MODEL, 99)], set())
            assert got[Key(MODEL, 99)] == ["pod-ok"]
        finally:
            pool.shutdown()


class TestSubscriberFrameHardening:
    """ISSUE 3 satellite: malformed messages — wrong frame count, short seq
    frame, undecodable topic — are counted and dropped; none may kill the
    receive loop."""

    @staticmethod
    def _sub():
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        return ZMQSubscriber(pool, ZMQSubscriberConfig()), pool, index

    def test_wrong_frame_count_dropped(self):
        sub, _, _ = self._sub()
        assert sub._parse_frames([b"kv@p@m"]) is None
        assert sub._parse_frames([b"a", b"b", b"c", b"d"]) is None
        assert sub.malformed_dropped["frames"] == 2

    def test_short_seq_frame_dropped(self):
        sub, _, _ = self._sub()
        # Pre-hardening this decoded with seq=0, silently poisoning gap
        # detection; now it is counted and dropped.
        assert sub._parse_frames([b"kv@p@m", b"\x00\x01", b"{}"]) is None
        assert sub._parse_frames([b"kv@p@m", b"\x00" * 9, b"{}"]) is None
        assert sub.malformed_dropped["seq"] == 2

    def test_undecodable_topic_dropped(self):
        sub, _, _ = self._sub()
        assert sub._parse_frames([b"\xff\xfe\xfd", b"\x00" * 8, b"{}"]) is None
        assert sub.malformed_dropped["topic"] == 1

    def test_unparseable_topic_dropped(self):
        sub, _, _ = self._sub()
        assert sub._parse_frames([b"not-kv-topic", b"\x00" * 8, b"{}"]) is None
        assert sub.malformed_dropped["topic"] == 1

    def test_valid_frames_still_parse(self):
        sub, _, _ = self._sub()
        msg = sub._parse_frames(
            [b"kv@pod-1@" + MODEL.encode(), struct.pack(">Q", 42), b"payload"]
        )
        assert msg is not None
        assert (msg.pod_identifier, msg.model_name, msg.seq) == ("pod-1", MODEL, 42)
        assert sum(sub.malformed_dropped.values()) == 0

    def test_receive_loop_survives_garbage_frames(self):
        """Over a real socket: malformed multipart messages precede a valid
        one; the loop must survive and deliver the valid event."""
        import zmq

        from conftest import free_tcp_port

        port = free_tcp_port()
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        sub = ZMQSubscriber(pool, ZMQSubscriberConfig(endpoint=f"tcp://*:{port}"))
        sub.start()
        try:
            ctx = zmq.Context.instance()
            raw = ctx.socket(zmq.PUB)
            raw.connect(f"tcp://localhost:{port}")
            topic = f"kv@pod-g@{MODEL}".encode()
            deadline = time.time() + 20
            found = {}
            while time.time() < deadline and not found:
                raw.send_multipart([topic, b"\x00" * 8])              # 2 frames
                raw.send_multipart([topic, b"\x01", b"x"])            # short seq
                raw.send_multipart([b"\xff\xfe", b"\x00" * 8, b"x"])  # bad utf-8... 
                # (note: SUB topic filter drops the bad-topic one early)
                raw.send_multipart(
                    [topic, struct.pack(">Q", 1), _stored_payload([5])]
                )
                time.sleep(0.2)
                found = index.lookup([Key(MODEL, 5)], set())
            raw.close(linger=0)
            assert found.get(Key(MODEL, 5)) == ["pod-g"]
            assert sub.malformed_dropped["frames"] >= 1
            assert sub.malformed_dropped["seq"] >= 1
        finally:
            sub.shutdown()
            pool.shutdown()


class TestPoolShutdownHardening:
    """ISSUE 3 satellite: shutdown idempotence and drain ordering."""

    def test_double_shutdown_is_idempotent(self):
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=2))
        pool.start()
        pool.shutdown()
        pool.shutdown()  # second call must be a no-op

    def test_shutdown_before_start_is_noop(self):
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=2))
        pool.shutdown()
        pool.start()  # still startable afterwards
        pool.shutdown()

    def test_shutdown_applies_queued_events_before_join(self):
        """Events accepted before shutdown land in the index: the poison
        pill queues BEHIND them, so shutdown drains rather than discards."""
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=2))
        pool.start()
        for i in range(200):
            pool.add_task(Message("t", f"pod-{i % 5}", MODEL, _stored_payload([i])))
        pool.shutdown()
        got = index.lookup([Key(MODEL, i) for i in range(200)], set())
        assert len(got) == 200

    def test_add_task_after_shutdown_rejected_not_parked(self):
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=1))
        pool.start()
        pool.shutdown()
        pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([1])))
        assert pool.rejected_after_shutdown == 1
        assert pool.drain(timeout=0.5)  # nothing left dangling

    def test_restart_after_shutdown_processes_again(self):
        index = InMemoryIndex()
        pool = KVEventsPool(index, KVEventsPoolConfig(concurrency=1))
        pool.start()
        pool.shutdown()
        pool.start()
        try:
            pool.add_task(Message("t", "pod-1", MODEL, _stored_payload([9])))
            assert pool.drain()
            assert index.lookup([Key(MODEL, 9)], set())[Key(MODEL, 9)] == ["pod-1"]
        finally:
            pool.shutdown()

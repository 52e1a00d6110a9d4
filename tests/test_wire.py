"""The wire hot path: zero-copy codec, encode-once fan-out, incremental
cohort staging — the PR-5 acceptance pins.

* golden-frame interop: the NEW encoder's frames are byte-identical to
  the seed encoder's, and each decoder accepts the other's frames (the
  seed codec is reimplemented verbatim here as the oracle);
* round-trip property over the nasty leaves (0-d, non-contiguous, bool,
  int8-quantized, empty) through BOTH the single-send and the
  ``send_many`` shared-payload paths;
* the encode-once pin: a ``send_many`` fan-out performs EXACTLY ONE
  shared-payload serialization (codec spy counter);
* torn/truncated frames raise ``ValueError`` from every decode entry and
  never kill a transport receive thread;
* incremental staging + donation: bit-identical to the seed
  stack-at-the-barrier path, with the defended jit still compiling once.
"""

import json
import logging
import struct
import threading

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.cross_silo import (FedAvgClientActor,
                                             FedAvgServerActor, MsgType)
from fedml_tpu.comm import message as message_mod
from fedml_tpu.comm.chaos import ChaosPlan, ChaosTransport, LinkChaos
from fedml_tpu.comm.local import LocalHub
from fedml_tpu.comm.message import (CODEC_COUNTS, Message, SharedPayload,
                                    build_fanout)
from fedml_tpu.comm.resilient import ResilientTransport, RetryPolicy
from fedml_tpu.robust.defense import make_defended_aggregate

_HDR = struct.Struct("<I")


# ---------------------------------------------------------------------------
# the seed codec, reimplemented verbatim (message.py @ PR 4) as the
# golden-frame oracle
# ---------------------------------------------------------------------------

def seed_to_bytes(msg: Message) -> bytes:
    header = {"plain": {}, "arrays": {}}
    buffers = []
    for key, value in msg.params.items():
        leaves, spec = message_mod._flatten_arrays(value)
        if leaves is None:
            header["plain"][key] = value
        else:
            descr = []
            for leaf in leaves:
                src = np.asarray(leaf)
                arr = np.ascontiguousarray(src)
                descr.append({"dtype": arr.dtype.str, "shape": src.shape,
                              "idx": len(buffers)})
                buffers.append(arr)
            header["arrays"][key] = {"spec": spec, "leaves": descr}
    hdr = json.dumps(header).encode()
    parts = [_HDR.pack(len(hdr)), hdr]
    for arr in buffers:
        parts.append(_HDR.pack(arr.nbytes))
        parts.append(arr.tobytes())
    return b"".join(parts)


def seed_from_bytes(data: bytes) -> Message:
    (hlen,) = _HDR.unpack_from(data, 0)
    header = json.loads(data[_HDR.size:_HDR.size + hlen])
    offset = _HDR.size + hlen
    buffers = []
    while offset < len(data):
        (n,) = _HDR.unpack_from(data, offset)
        offset += _HDR.size
        buffers.append(data[offset:offset + n])
        offset += n
    msg = Message.__new__(Message)
    msg._shared = None
    msg.params = dict(header["plain"])
    for key, info in header["arrays"].items():
        leaves = []
        for d in info["leaves"]:
            arr = np.frombuffer(buffers[d["idx"]], dtype=np.dtype(d["dtype"]))
            leaves.append(arr.reshape(d["shape"]))
        msg.params[key] = message_mod._unflatten_arrays(info["spec"], leaves)
    return msg


def _edge_tree(seed=0):
    """Every leaf shape the satellite names: 0-d, non-contiguous, bool,
    int8-quantized, empty — plus ordinary dense layers."""
    rng = np.random.RandomState(seed)
    return {
        "dense": {"kernel": rng.randn(16, 8).astype(np.float32),
                  "bias": rng.randn(8).astype(np.float32)},
        "zero_d": np.float32(3.25),
        "noncontig": rng.randn(6, 6).T,
        "strided": np.arange(20)[::2],
        "flags": np.array([True, False, True]),
        "quantized": {"codes": rng.randint(-128, 128, (32,)).astype(np.int8),
                      "scale": np.float64(0.017)},
        "empty": np.zeros((0, 4), np.float32),
        "half": rng.randn(5).astype(np.float16),
        "mixed": [np.int64(9), ("tag", np.ones((2, 2)))],
    }


def _assert_tree_equal(a, b):
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (a, b)
        np.testing.assert_array_equal(a, b)
    elif isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_tree_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    else:
        assert a == b


def _payload_msg(tree, msg_type=3, sender=1, receiver=0):
    return (Message(msg_type, sender, receiver)
            .add(Message.ARG_MODEL_PARAMS, tree)
            .add(Message.ARG_NUM_SAMPLES, 12)
            .add(Message.ARG_ROUND, 4))


class TestGoldenFrame:
    def test_new_encoder_is_byte_identical_to_seed(self):
        msg = _payload_msg(_edge_tree())
        assert msg.to_bytes() == seed_to_bytes(msg)

    def test_cross_decoding_both_directions(self):
        msg = _payload_msg(_edge_tree(1))
        via_old = seed_from_bytes(msg.to_bytes())
        via_new = Message.from_bytes(seed_to_bytes(msg))
        for out in (via_old, via_new):
            _assert_tree_equal(out.get(Message.ARG_MODEL_PARAMS),
                               msg.get(Message.ARG_MODEL_PARAMS))
            assert out.get(Message.ARG_NUM_SAMPLES) == 12

    def test_seed_decoder_accepts_send_many_frames(self):
        """A fan-out frame (shared block + spliced header) must decode on
        an OLD node: old/new interop is per-frame, not per-path."""
        tree = _edge_tree(2)
        msgs = build_fanout(1, 0, [1, 2],
                            {Message.ARG_MODEL_PARAMS: tree,
                             Message.ARG_ROUND: 7},
                            {1: {Message.ARG_CLIENT_INDEX: 4},
                             2: {Message.ARG_CLIENT_INDEX: 5}})
        for msg, idx in zip(msgs, (4, 5)):
            out = seed_from_bytes(msg.to_bytes())
            _assert_tree_equal(out.get(Message.ARG_MODEL_PARAMS), tree)
            assert out.get(Message.ARG_CLIENT_INDEX) == idx
            assert out.get(Message.ARG_ROUND) == 7


class TestRoundTripProperty:
    @pytest.mark.parametrize("path", ["single", "fanout_bytes",
                                      "fanout_parts"])
    def test_edge_leaves_roundtrip(self, path):
        for seed in range(5):
            tree = _edge_tree(seed)
            if path == "single":
                out = Message.from_bytes(_payload_msg(tree).to_bytes())
            else:
                (msg,) = build_fanout(
                    3, 1, [0], {Message.ARG_MODEL_PARAMS: tree},
                    {0: {Message.ARG_NUM_SAMPLES: 12}})
                if path == "fanout_bytes":
                    out = Message.from_bytes(msg.to_bytes())
                else:
                    out = Message.from_frame_parts(msg.frame_parts())
            _assert_tree_equal(out.get(Message.ARG_MODEL_PARAMS), tree)

    def test_decode_is_zero_copy_readonly_views(self):
        frame = _payload_msg(_edge_tree()).to_bytes()
        out = Message.from_bytes(frame)
        kernel = out.get(Message.ARG_MODEL_PARAMS)["dense"]["kernel"]
        assert not kernel.flags.writeable  # frames are immutable
        assert np.shares_memory(kernel, np.frombuffer(frame, np.uint8))

    def test_encode_pays_one_copy_per_contiguous_leaf(self):
        tree = {"a": np.ones((64, 64), np.float32),
                "b": np.ones(64, np.float32)}
        before = CODEC_COUNTS["leaf_copies"]
        Message(1, 0, 1).add("p", tree).to_bytes()
        assert CODEC_COUNTS["leaf_copies"] - before == 2


class TestEncodeOncePin:
    def test_send_many_serializes_shared_payload_exactly_once(self):
        """THE acceptance pin: an 8-silo fan-out costs ONE payload encode
        (the seed path cost eight)."""
        tree = _edge_tree()
        before = CODEC_COUNTS["payload_encodes"]
        msgs = build_fanout(1, 0, range(1, 9),
                            {Message.ARG_MODEL_PARAMS: tree},
                            {r: {Message.ARG_CLIENT_INDEX: r}
                             for r in range(1, 9)})
        frames = [m.to_bytes() for m in msgs]
        assert CODEC_COUNTS["payload_encodes"] - before == 1
        # and every frame still decodes to its own receiver's view
        for r, frame in enumerate(frames, start=1):
            out = Message.from_bytes(frame)
            assert out.get(Message.ARG_CLIENT_INDEX) == r
            _assert_tree_equal(out.get(Message.ARG_MODEL_PARAMS), tree)

    def test_server_broadcast_is_encode_once_over_the_hub(self):
        """The live path: a FedAvg round over the codec-roundtrip hub
        pays one payload encode per broadcast, not one per silo."""
        hub = LocalHub(codec_roundtrip=True)
        init = {"dense": {"kernel": np.ones((8, 4), np.float32),
                          "bias": np.zeros(4, np.float32)}}

        def train_fn(params, client_idx, round_idx):
            return jax.tree.map(lambda v: np.asarray(v), params), 10

        server = FedAvgServerActor(hub.transport(0), init, 4, 4, 1)
        silos = [FedAvgClientActor(i, hub.transport(i), train_fn)
                 for i in range(1, 5)]
        server.register_handlers()
        for s in silos:
            s.register_handlers()
        before = CODEC_COUNTS["payload_encodes"]
        server.start()  # round-0 broadcast to 4 silos
        # one broadcast encode; each silo's UPLOAD is its own single
        # encode (4), plus nothing else before the pump
        assert CODEC_COUNTS["payload_encodes"] - before == 1
        hub.pump()
        assert server.round_idx == 1

    def test_chaos_corruption_never_mutates_a_sibling_frame(self):
        """Copy-on-corrupt across a shared payload: the corrupted silo's
        frame is rebuilt privately; its siblings' frames and the shared
        block stay byte-identical."""
        tree = {"w": np.zeros((64,), np.float32)}
        hub = LocalHub(codec_roundtrip=True)
        received = {}

        class Collect:
            def __init__(self, node):
                self.node = node

            def receive_message(self, msg_type, msg):
                received[self.node] = msg.get("model_params")["w"]

        transports = {}
        for i in (1, 2):
            t = hub.transport(i)
            t.add_observer(Collect(i))
            transports[i] = t
        plan = ChaosPlan(seed=3, links={(0, 1): LinkChaos(corrupt_prob=1.0)})
        chaotic = ChaosTransport(hub.transport(0), plan)
        msgs = build_fanout(1, 0, [1, 2], {"model_params": tree})
        chaotic.send_many(msgs)
        hub.pump()
        assert not np.array_equal(received[1], tree["w"])  # corrupted
        np.testing.assert_array_equal(received[2], tree["w"])  # untouched
        # the shared source tree itself was never mutated
        np.testing.assert_array_equal(tree["w"], np.zeros(64, np.float32))

    def test_send_many_through_resilient_retries_per_link(self):
        """Per-link retry semantics survive the fan-out: one silo's flaky
        channel retries alone; everyone is delivered exactly once."""
        hub = LocalHub()
        got = []

        class Collect:
            def __init__(self, node):
                self.node = node

            def receive_message(self, msg_type, msg):
                got.append(self.node)

        for i in (1, 2, 3):
            hub.transport(i).add_observer(Collect(i))
        inner = hub.transport(0)
        fails = {"n": 0}
        real_send = inner.send_message

        def flaky(msg):
            if msg.receiver_id == 2 and fails["n"] < 2:
                fails["n"] += 1
                raise ConnectionError("flaky link to silo 2")
            real_send(msg)

        inner.send_message = flaky
        resilient = ResilientTransport(
            inner, RetryPolicy(max_attempts=5, base_backoff_s=0.01,
                               jitter_frac=0.0))
        import time as _t
        try:
            resilient.send_many(build_fanout(
                1, 0, [1, 2, 3], {"model_params": {"w": np.ones(8)}}))
            for _ in range(500):  # sender thread drains asynchronously
                if resilient.sent_ok >= 3:
                    break
                _t.sleep(0.01)
            hub.pump()
        finally:
            resilient.stop()
        assert sorted(got) == [1, 2, 3]
        assert resilient.retries == 2 and resilient.dead_letters == 0

    def test_wire_bytes_counters_match_frames(self):
        """PR-3 semantics hold on the fan-out path: the hub's wire-bytes
        counter per link equals that receiver's standalone frame size."""
        from fedml_tpu.obs import telemetry
        reg = telemetry.enable(telemetry.TelemetryRegistry())
        try:
            hub = LocalHub(codec_roundtrip=True)
            for i in (1, 2):
                hub.transport(i).add_observer(
                    type("N", (), {"receive_message":
                                   lambda self, t, m: None})())
            sender = hub.transport(0)
            msgs = build_fanout(1, 0, [1, 2],
                                {"model_params": _edge_tree()},
                                {1: {Message.ARG_CLIENT_INDEX: 1},
                                 2: {Message.ARG_CLIENT_INDEX: 2}})
            expected = {m.receiver_id: len(m.to_bytes()) for m in msgs}
            sender.send_many(msgs)
            hub.pump()
            snap = reg.snapshot()["counters"]
            for r, nbytes in expected.items():
                key = 'fedml_comm_wire_bytes_total{link="0->%d"}' % r
                assert snap[key] == nbytes, (key, snap)
        finally:
            telemetry.disable()


class TestTornFrames:
    def test_truncations_raise_value_error(self):
        frame = _payload_msg(_edge_tree()).to_bytes()
        cuts = [0, 2, _HDR.size, len(frame) // 2, len(frame) - 1]
        for cut in cuts:
            with pytest.raises(ValueError):
                Message.from_bytes(frame[:cut])

    def test_garbage_and_header_damage_raise_value_error(self):
        frame = bytearray(_payload_msg(_edge_tree()).to_bytes())
        with pytest.raises(ValueError):
            Message.from_bytes(b"\xff" * 64)          # not a frame at all
        frame[6] ^= 0xFF                               # damage header JSON
        with pytest.raises(ValueError):
            Message.from_bytes(bytes(frame))
        with pytest.raises(ValueError):                # huge declared hlen
            Message.from_bytes(_HDR.pack(2 ** 30) + b"xx")

    def test_bad_buffer_index_and_dtype_mismatch_raise(self):
        # header says idx 7, only 1 buffer arrives
        hdr = json.dumps({"plain": {}, "arrays": {
            "p": {"spec": {"k": "leaf"},
                  "leaves": [{"dtype": "<f4", "shape": [2], "idx": 7}]}}}
        ).encode()
        frame = _HDR.pack(len(hdr)) + hdr + _HDR.pack(8) + b"\0" * 8
        with pytest.raises(ValueError):
            Message.from_bytes(frame)
        # declared shape disagrees with the delivered byte count
        hdr = json.dumps({"plain": {}, "arrays": {
            "p": {"spec": {"k": "leaf"},
                  "leaves": [{"dtype": "<f4", "shape": [5], "idx": 0}]}}}
        ).encode()
        frame = _HDR.pack(len(hdr)) + hdr + _HDR.pack(8) + b"\0" * 8
        with pytest.raises(ValueError):
            Message.from_bytes(frame)

    def test_grpc_receive_thread_survives_torn_frame(self):
        grpc = pytest.importorskip("grpc")
        from fedml_tpu.comm.grpc_transport import (_METHOD, _SERVICE,
                                                   GrpcTransport)
        table = {0: "127.0.0.1", 1: "127.0.0.1"}
        a = GrpcTransport(0, table, base_port=56510)
        b = GrpcTransport(1, table, base_port=56510)
        try:
            got = []

            class Collect:
                def receive_message(self, msg_type, msg):
                    got.append(msg_type)
                    b.stop()

            b.add_observer(Collect())
            # fire a torn frame straight at node 1's RPC endpoint
            channel = grpc.insecure_channel("127.0.0.1:56511")
            call = channel.unary_unary(f"/{_SERVICE}/{_METHOD}",
                                       request_serializer=lambda x: x,
                                       response_deserializer=lambda x: x)
            call(b"\xde\xad\xbe\xef" * 3, timeout=10)
            channel.close()
            # the receive loop is alive: a valid frame still delivers
            a.send_message(_payload_msg({"w": np.ones(4, np.float32)},
                                        sender=0, receiver=1))
            b.run()
            assert got == [3]
        finally:
            a.stop()
            b.stop()

    def test_mqtt_callback_survives_torn_frame(self):
        import types
        from fedml_tpu.comm import mqtt_transport as mt
        from fedml_tpu.comm.mqtt_broker import MqttBroker
        with MqttBroker() as broker:
            t = mt.MqttTransport(0, "127.0.0.1", broker.port)
            try:
                t._on_message(None, None, types.SimpleNamespace(
                    topic="fedml_tpu/0", payload=b"\xff" * 9))
                assert t._inbox.empty()  # dropped, no exception
            finally:
                t.stop()


# ---------------------------------------------------------------------------
# incremental staging + donation
# ---------------------------------------------------------------------------

def _drift_train_fn(delta):
    def fn(params, client_idx, round_idx):
        return (jax.tree.map(
            lambda v: np.asarray(v) + np.float32(delta * (client_idx + 1)),
            params), 10 * (client_idx + 1))
    return fn


def _run_federation(encode_once, staging, n_silos=4, rounds=3,
                    defended=None, straggler=False):
    hub = LocalHub(codec_roundtrip=True)
    init = {"dense": {"kernel": np.ones((8, 4), np.float32),
                      "bias": np.zeros(4, np.float32)}}
    defended = defended or make_defended_aggregate("mean", norm_clip=5.0)
    server = FedAvgServerActor(
        hub.transport(0), init, n_silos, n_silos, rounds,
        aggregate_fn=defended, encode_once=encode_once,
        incremental_staging=staging,
        straggler_policy="drop" if straggler else "wait",
        round_timeout_s=0.2 if straggler else None,
        min_silo_frac=0.5 if straggler else 0.5)
    server.register_handlers()
    silos = []
    for i in range(1, n_silos + 1):
        if straggler and i == n_silos:
            class Deaf(FedAvgClientActor):
                def register_handlers(self):
                    self.register_handler(MsgType.S2C_FINISH,
                                          lambda m: self.finish())
            silo = Deaf(i, hub.transport(i), _drift_train_fn(0.01))
        else:
            silo = FedAvgClientActor(i, hub.transport(i),
                                     _drift_train_fn(0.01))
        silos.append(silo)
    for s in silos:
        s.register_handlers()
    if straggler:
        threads = [threading.Thread(target=s.run, daemon=True)
                   for s in silos]
        for th in threads:
            th.start()
        server.start()
        server.transport.run()
        for th in threads:
            th.join(timeout=5)
    else:
        server.start()
        hub.pump()
    assert server.round_idx == rounds
    return jax.tree.map(np.asarray, server.params), server


class TestIncrementalStaging:
    def test_staged_path_matches_seed_stacking_bitwise(self):
        seed_params, _ = _run_federation(encode_once=False, staging=False)
        new_params, server = _run_federation(encode_once=True, staging=True)
        jax.tree.map(np.testing.assert_array_equal, seed_params, new_params)
        # staging ran for every silo every round, and the cohort buffer
        # was RELEASED at round close (RSS returns to baseline between
        # rounds instead of pinning the cohort watermark)
        assert server._staged_seen == 3 * 4
        assert server._staging is None and not server._staged

    def test_staged_path_matches_seed_with_straggler_dropped(self):
        """A dropped silo's slot refills with the global at weight 0 —
        identical to the seed path's stack of the same cohort."""
        seed_params, s1 = _run_federation(encode_once=False, staging=False,
                                          straggler=True)
        new_params, s2 = _run_federation(encode_once=True, staging=True,
                                         straggler=True)
        assert s1.dropped_silos == s2.dropped_silos
        jax.tree.map(np.testing.assert_array_equal, seed_params, new_params)

    def test_jit_once_pin_with_staging(self):
        """Acceptance: _cache_size() == 1 across rounds with incremental
        staging enabled."""
        fn = make_defended_aggregate("mean", norm_clip=5.0)
        _, server = _run_federation(encode_once=True, staging=True,
                                    rounds=4, defended=fn)
        assert fn._cache_size() == 1
        assert server.round_idx == 4

    def test_host_mirror_shared_across_round_consumers(self):
        """broadcast/checkpoint/staging-fill read ONE device→host copy
        per params value."""
        init = {"w": np.ones(4, np.float32)}
        hub = LocalHub()
        server = FedAvgServerActor(hub.transport(0), init, 2, 2, 3,
                                   aggregate_fn=make_defended_aggregate(
                                       "mean"))
        h1 = server._host_params()
        assert server._host_params() is h1  # memoized
        server.params = {"w": np.zeros(4, np.float32)}
        assert server._host_params() is not h1  # invalidated by identity

    def test_staging_rejects_dtype_drift_loudly(self):
        """A matching treedef with a drifted leaf dtype must fail loudly,
        never silently cast into the template-typed staging buffer."""
        init = {"w": np.ones(4, np.float32)}
        hub = LocalHub()
        server = FedAvgServerActor(hub.transport(0), init, 2, 2, 1,
                                   aggregate_fn=make_defended_aggregate(
                                       "mean"))
        server._num_silos = 2
        with pytest.raises(ValueError, match="dtype"):
            server._stage(1, {"w": np.ones(4, np.float64)})

    def test_build_fanout_rejects_shared_key_override(self):
        with pytest.raises(ValueError, match="override shared"):
            build_fanout(1, 0, [1, 2],
                         {Message.ARG_ROUND: 5},
                         {2: {Message.ARG_ROUND: 6}})

    def test_staging_gauge_tracks_arrivals(self):
        from fedml_tpu.obs import telemetry
        reg = telemetry.enable(telemetry.TelemetryRegistry())
        try:
            _, server = _run_federation(encode_once=True, staging=True,
                                        rounds=2)
            snap = reg.snapshot()["gauges"]
            # the staged-uploads gauge zeroes at round close (the buffer
            # is released with it); the lifetime counter carries the
            # evidence that every arrival staged
            assert snap["fedml_wire_staged_uploads_total"] == 0.0
            assert server._staged_seen == 2 * 4
            counters = reg.snapshot()["counters"]
            # 2 rounds x 4-silo broadcast fan-outs
            assert counters["fedml_wire_fanout_total"] == 8.0
        finally:
            telemetry.disable()


class TestZeroCopyDecode:
    """The decode side never copies: every non-empty array leaf of a
    decoded frame is a read-only view into the inbound frame bytes.
    This is what lets the ingest arena gather frame->device with no
    intermediate host materialization (fedml_tpu/comm/ingest.py)."""

    def test_every_leaf_aliases_the_frame(self):
        data = _payload_msg(_edge_tree(5)).to_bytes()
        frame = np.frombuffer(data, np.uint8)
        out = Message.from_bytes(data)
        leaves = jax.tree.leaves(out.get(Message.ARG_MODEL_PARAMS))
        assert leaves
        for leaf in leaves:
            if not isinstance(leaf, np.ndarray):
                continue   # plain scalars/strings ride the JSON header
            arr = leaf
            if arr.size == 0:
                continue   # empty leaves own no bytes to share
            assert np.shares_memory(arr, frame), arr.dtype
            assert not arr.flags.writeable

    def test_aliasing_covers_awkward_dtypes_and_shapes(self):
        """0-d scalars, bools, int8 codes, float16, and leaves encoded
        from non-contiguous sources all decode as frame views — the
        encode-side ``ascontiguousarray`` is the only copy."""
        rng = np.random.RandomState(11)
        tree = {
            "zero_d": np.float32(3.25),
            "flags": np.array([True, False, True]),
            "codes": rng.randint(-128, 128, (32,)).astype(np.int8),
            "half": rng.randn(5).astype(np.float16),
            "noncontig": rng.randn(6, 6).T,
            "strided": np.arange(20)[::2],
        }
        data = _payload_msg(tree).to_bytes()
        frame = np.frombuffer(data, np.uint8)
        out = Message.from_bytes(data).get(Message.ARG_MODEL_PARAMS)
        _assert_tree_equal(out, jax.tree.map(np.asarray, tree))
        for key, leaf in out.items():
            assert np.shares_memory(np.asarray(leaf), frame), key

    def test_raw_payload_buffers_alias_the_frame(self):
        """``raw_payload`` — the arena's staging input — hands back the
        frame's own buffer views, not copies."""
        tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
        data = _payload_msg(tree).to_bytes()
        frame = np.frombuffer(data, np.uint8)
        out = Message.from_bytes(data)
        raw = out.raw_payload(Message.ARG_MODEL_PARAMS)
        assert raw is not None
        descr, spec, buffers = raw
        assert len(descr) == 1
        view = np.frombuffer(buffers[descr[0]["idx"]], np.float32)
        assert np.shares_memory(view, frame)
        np.testing.assert_array_equal(view.reshape(3, 4), tree["w"])

    def test_per_shard_slice_trees_alias_one_frame(self):
        """A sharded upload is several subtrees in ONE frame; each
        shard's decoded slices view the same frame bytes, so per-shard
        staging still costs zero host copies."""
        rng = np.random.RandomState(13)
        shards = {f"shard_{s}": {"w": rng.randn(8, 4).astype(np.float32)}
                  for s in range(3)}
        data = _payload_msg(shards).to_bytes()
        frame = np.frombuffer(data, np.uint8)
        out = Message.from_bytes(data).get(Message.ARG_MODEL_PARAMS)
        for name, sub in out.items():
            assert np.shares_memory(np.asarray(sub["w"]), frame), name

"""Multi-host ascent service: wire protocol, server/client loopback,
hetero-vs-remote parity, and mid-fit server-death resilience.

The subprocess tests spawn the real ``python -m repro.service.ascent_server``
(the same loopback path `--serve-ascent` drives); every blocking wait has an
explicit deadline so a wedged socket fails the test instead of hanging
tier-1 (`scripts/tier1.sh --service` adds a process-level timeout on top).
"""
import io
import itertools
import time

import jax
import numpy as np
import pytest

from repro import optim
from repro.core import MethodConfig, make_ascent_fn, slice_ascent_batch
from repro.core.ascent import Compressor, _topk_roundtrip
from repro.data.synthetic import ClassificationTask
from repro.engine import Engine, HeteroExecutor, RemoteExecutor, StalenessTelemetry
from repro.runtime import ExecutorConfig
from repro.service import protocol
from repro.service.ascent_server import AscentServer, spawn_server
from repro.service.client import RemoteAscentClient
from repro.service.protocol import FrameType, ProtocolError
from repro.service.testing import MLP_LOSS_SPEC, mlp_init, mlp_loss

TASK = ClassificationTask(n_classes=4, dim=8, seed=3)
BATCH = 64
WIDTHS = (8, 32, 4)


def _params(seed=0):
    return mlp_init(jax.random.PRNGKey(seed), WIDTHS)


def _batches(n, frac=0.5):
    return [{**b, "ascent": slice_ascent_batch(b, frac)}
            for b in TASK.train_batches(BATCH, n)]


def _grad_tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (50, 7)),
            "nested": {"b": jax.random.normal(jax.random.fold_in(k, 1), (33,))}}


# ---------------------------------------------------------------------------
# protocol: frames, checksums, pytree/grad codecs, wire-byte model
# ---------------------------------------------------------------------------

def test_frame_roundtrip_and_corruption_detection():
    frame = protocol.encode_frame(FrameType.JOB, b"payload bytes")
    ftype, payload = protocol.decode_frame(frame)
    assert ftype == FrameType.JOB and payload == b"payload bytes"
    # payload corruption -> checksum error
    bad = bytearray(frame)
    bad[-1] ^= 0xFF
    with pytest.raises(ProtocolError, match="checksum"):
        protocol.decode_frame(bytes(bad))
    # bad magic
    with pytest.raises(ProtocolError, match="magic"):
        protocol.decode_frame(b"XXXX" + frame[4:])
    # wrong version
    bad = bytearray(frame)
    bad[4] = 99
    with pytest.raises(ProtocolError, match="version"):
        protocol.decode_frame(bytes(bad))


def test_job_payload_roundtrip():
    params = jax.device_get(_params())
    batch = {"x": np.random.randn(16, 8).astype(np.float32),
             "y": np.arange(16, dtype=np.int32)}
    rng = jax.device_get(jax.random.PRNGKey(7))
    payload = protocol.encode_job(3, 11, params, batch, rng)
    gen, step, p2, b2, r2 = protocol.decode_job(payload)
    assert (gen, step) == (3, 11)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert np.array_equal(a, b)
    assert np.array_equal(batch["y"], b2["y"]) and np.array_equal(rng, r2)


def test_grad_payload_roundtrip_per_kind():
    g = jax.device_get(jax.tree.map(lambda x: x.astype(np.float32),
                                    _grad_tree()))
    treedef = jax.tree.structure(g)

    def roundtrip(tree, comp):
        payload = protocol.encode_grad(1, 2, 3.5, 0.01,
                                       jax.tree.leaves(tree), comp)
        gen, jstep, norm, dt, leaves, pool_meta = protocol.decode_grad(payload)
        assert (gen, jstep) == (1, 2) and norm == 3.5
        assert pool_meta == {}           # no pool prelude unless negotiated
        return jax.tree.unflatten(treedef, leaves)

    # none: bit-exact
    out = roundtrip(g, Compressor("none"))
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(g), jax.tree.leaves(out)))
    # topk: a k-sparse tree (what the server's compressor hands off) is exact
    frac = 0.1
    sparse = jax.device_get(jax.tree.map(
        lambda x: _topk_roundtrip(x, frac), g))
    out = roundtrip(sparse, Compressor("topk", topk_fraction=frac))
    assert all(np.allclose(a, b, atol=0) for a, b in
               zip(jax.tree.leaves(sparse), jax.tree.leaves(out)))
    # int8: exact up to one quantization ulp of the re-derived scale
    out = roundtrip(g, Compressor("int8"))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(out)):
        assert np.allclose(a, b, atol=float(np.max(np.abs(a))) / 127 + 1e-7)


@pytest.mark.parametrize("kind,frac", [("none", 0.0), ("int8", 0.0),
                                       ("topk", 0.05), ("topk", 0.5)])
def test_grad_frame_bytes_model_matches_serialized_length(kind, frac):
    """Satellite: wire_bytes models the payload; protocol adds frame overhead
    — together they must equal the actual serialized frame length."""
    g = jax.device_get(_grad_tree())
    comp = Compressor(kind, topk_fraction=frac or 0.01)
    payload = protocol.encode_grad(0, 0, 1.0, 0.0, jax.tree.leaves(g), comp)
    frame = protocol.encode_frame(FrameType.GRAD, payload)
    assert len(frame) == protocol.grad_frame_bytes(comp, g)
    assert len(payload) - protocol.GRAD_FIXED_BYTES >= comp.wire_bytes(g)
    # the revision-3 pool-telemetry prelude is modeled exactly too
    pooled = protocol.encode_grad(0, 0, 1.0, 0.0, jax.tree.leaves(g), comp,
                                  pool=(3, 0.25))
    pframe = protocol.encode_frame(FrameType.GRAD, pooled)
    assert len(pframe) == protocol.grad_frame_bytes(comp, g, pool=True)
    assert len(pframe) - len(frame) == protocol.GRAD_POOL_BYTES
    *_rest, leaves, pool_meta = protocol.decode_grad(pooled, pool=True)
    assert pool_meta == {"pool_depth": 3, "pool_wait_s": 0.25}


def test_parse_addr():
    assert protocol.parse_addr("unix:/tmp/x.sock") == ("unix", "/tmp/x.sock")
    assert protocol.parse_addr("127.0.0.1:7431") == ("tcp", ("127.0.0.1", 7431))
    with pytest.raises(ValueError):
        protocol.parse_addr("7431")


# ---------------------------------------------------------------------------
# v2 JOB payloads: delta codec, shadow sync, exact frame-length model
# ---------------------------------------------------------------------------

def _job_aux(seed=0):
    rs = np.random.RandomState(seed)
    batch = {"x": rs.randn(16, 8).astype(np.float32),
             "y": np.arange(16, dtype=np.int32)}
    rng = np.asarray(jax.device_get(jax.random.PRNGKey(7)))
    return batch, rng


def _caps_v2():
    return True, {"none", "int8", "topk"}


def test_job_v2_snapshot_roundtrip_and_length_model():
    params = jax.device_get(_params())
    batch, rng = _job_aux()
    payload = protocol.encode_job_v2(1, 0, 3, 11, batch, rng, params=params)
    frame = protocol.encode_frame(FrameType.JOB_DELTA, payload)
    assert len(frame) == protocol.job_frame_bytes("none", params, batch, rng)
    assert len(frame) == protocol.job_frame_bytes("int8", params, batch, rng,
                                                  delta=False)
    sync, seq, gen, step, kind, p2, b2, r2, sections = \
        protocol.decode_job_v2(payload)
    assert (sync, seq, gen, step, kind) == (1, 0, 3, 11, "snapshot")
    assert sections == []
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert np.array_equal(a, b)
    assert np.array_equal(batch["y"], b2["y"]) and np.array_equal(rng, r2)


@pytest.mark.parametrize("encoding,frac", [("int8", 0.01), ("topk", 0.1)])
def test_job_delta_roundtrip_shadow_bitwise_and_length_model(encoding, frac):
    """The load-bearing invariant: after every delta the server's numpy
    shadow equals the client encoder's shadow bit for bit, the reconstructed
    params track the true params within the quantization step, and the frame
    length equals `job_frame_bytes` exactly."""
    from repro.service.delta import JobEncoder, ShadowState
    params = jax.device_get(_grad_tree())
    batch, rng = _job_aux()
    enc = JobEncoder(encoding, topk_fraction=frac, delta=True,
                     caps_fn=_caps_v2)
    srv = ShadowState()
    rs = np.random.RandomState(1)
    for step in range(4):
        job = enc.encode(0, params, batch, rng, step)
        payload = protocol.encode_job_v2(job.sync, job.seq, job.gen, job.step,
                                         job.batch, job.rng, params=job.params,
                                         kind=job.kind, deltas=job.deltas)
        frame = protocol.encode_frame(FrameType.JOB_DELTA, payload)
        assert len(frame) == protocol.job_frame_bytes(
            encoding, params, batch, rng, delta=(job.kind != "snapshot"),
            topk_fraction=frac)
        sync, seq, gen, jstep, kind, p2, b2, r2, sections = \
            protocol.decode_job_v2(payload)
        assert kind == ("snapshot" if step == 0 else encoding)
        if kind == "snapshot":
            srv.install(p2, sync)
        else:
            srv.apply(kind, sections, sync, seq)
        cli_shadow = [np.asarray(jax.device_get(s)) for s in enc._shadow]
        for a, b in zip(cli_shadow, srv.bufs):
            np.testing.assert_array_equal(a, b)
        # the walk keeps the reconstruction within the coder's granularity
        if encoding == "int8":
            for a, b in zip(jax.tree.leaves(srv.params()),
                            jax.tree.leaves(params)):
                amax = float(np.max(np.abs(np.asarray(b)))) or 1.0
                assert np.allclose(a, b, atol=2 * amax / 127 + 1e-7)
        params = jax.tree.map(
            lambda x: x + np.float32(0.02) * rs.randn(*x.shape)
            .astype(np.float32), params)


def test_delta_encoder_error_feedback_converges():
    """With params held FIXED, error feedback drives the topk shadow to the
    true params even though each delta ships only a fraction of entries."""
    from repro.service.delta import JobEncoder
    params = jax.device_get(_grad_tree())
    batch, rng = _job_aux()
    enc = JobEncoder("topk", topk_fraction=0.2, delta=True, caps_fn=_caps_v2)
    for step in range(12):
        enc.encode(0, params, batch, rng, step)
    shadow_tree = None
    from repro.utils import buckets
    host = [np.asarray(jax.device_get(s)) for s in enc._shadow]
    shadow_tree = buckets.host_buckets_to_tree(host, enc._layout,
                                               enc._leaf_dtypes)
    for a, b in zip(jax.tree.leaves(shadow_tree), jax.tree.leaves(params)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_resync_frame_recovers_skewed_stream():
    """A delta the server's shadow cannot extend draws a RESYNC (not an
    error); a fresh snapshot then re-installs and deltas flow again."""
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    params = jax.device_get(_params())
    batch = jax.device_get(_batches(1)[0]["ascent"])
    rng = np.asarray(jax.device_get(jax.random.PRNGKey(5)))
    from repro.utils import buckets
    layout = buckets.bucket_layout(params)
    sock = protocol.connect(server.address)
    try:
        protocol.send_frame(sock, FrameType.HELLO,
                            protocol.encode_hello(Compressor("none")))
        ftype, payload, _ = protocol.recv_frame(sock, timeout=30.0)
        assert ftype == FrameType.HELLO_ACK
        _, ack = protocol.decode_hello(payload)
        assert ack.get("proto") == protocol.PROTO_REVISION
        assert set(ack.get("job_encodings")) == set(protocol.JOB_ENCODINGS)

        def snapshot(sync):
            protocol.send_frame(sock, FrameType.JOB_DELTA,
                                protocol.encode_job_v2(sync, 0, 0, 0, batch,
                                                       rng, params=params))
            ftype, _p, _ = protocol.recv_frame(sock, timeout=120.0)
            return ftype

        def zero_delta(sync, seq):
            deltas = [(1.0, np.zeros(g.size, np.int8)) for g in layout.groups]
            protocol.send_frame(
                sock, FrameType.JOB_DELTA,
                protocol.encode_job_v2(sync, seq, 0, 0, batch, rng,
                                       kind="int8", deltas=deltas))
            ftype, _p, _ = protocol.recv_frame(sock, timeout=120.0)
            return ftype

        assert snapshot(1) == FrameType.GRAD
        assert zero_delta(1, 1) == FrameType.GRAD       # extends the shadow
        assert zero_delta(1, 5) == FrameType.RESYNC     # seq gap -> resync
        assert zero_delta(2, 1) == FrameType.RESYNC     # unknown sync
        assert server.resyncs_sent == 2
        assert snapshot(2) == FrameType.GRAD            # re-install
        assert zero_delta(2, 1) == FrameType.GRAD       # stream flows again
    finally:
        sock.close()
        server.close()


def test_corrupted_job_delta_drops_connection_without_poisoning_shadow():
    """A checksummed-but-malformed JOB_DELTA must drop the connection before
    any buffer is touched; the server survives and serves the next client."""
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    params = jax.device_get(_params())
    batch = jax.device_get(_batches(1)[0]["ascent"])
    rng = np.asarray(jax.device_get(jax.random.PRNGKey(5)))
    from repro.utils import buckets
    layout = buckets.bucket_layout(params)

    def connect():
        sock = protocol.connect(server.address)
        protocol.send_frame(sock, FrameType.HELLO,
                            protocol.encode_hello(Compressor("none")))
        ftype, _p, _ = protocol.recv_frame(sock, timeout=30.0)
        assert ftype == FrameType.HELLO_ACK
        return sock

    sock = connect()
    try:
        protocol.send_frame(sock, FrameType.JOB_DELTA,
                            protocol.encode_job_v2(1, 0, 0, 0, batch, rng,
                                                   params=params))
        ftype, _p, _ = protocol.recv_frame(sock, timeout=120.0)
        assert ftype == FrameType.GRAD
        # truncated delta: the frame itself is valid (crc over the truncated
        # payload), the payload is not — decode must raise server-side and
        # the connection must drop without a half-applied shadow
        deltas = [(1.0, np.zeros(g.size, np.int8)) for g in layout.groups]
        good = protocol.encode_job_v2(1, 1, 0, 0, batch, rng,
                                      kind="int8", deltas=deltas)
        protocol.send_frame(sock, FrameType.JOB_DELTA, good[:-3])
        with pytest.raises((ConnectionError, TimeoutError)):
            protocol.recv_frame(sock, timeout=30.0)
    finally:
        sock.close()
    # the helper is still up: a fresh connection full-syncs and exchanges
    sock = connect()
    try:
        protocol.send_frame(sock, FrameType.JOB_DELTA,
                            protocol.encode_job_v2(1, 0, 0, 0, batch, rng,
                                                   params=params))
        ftype, _p, _ = protocol.recv_frame(sock, timeout=120.0)
        assert ftype == FrameType.GRAD
    finally:
        sock.close()
        server.close()


def test_new_client_old_server_degrades_to_full_snapshots():
    """Satellite: a delta-configured client against a revision-1 server must
    keep training on legacy full-snapshot JOB frames — no codec error, no
    drops, no JOB_DELTA frames on the wire."""
    server = AscentServer(mlp_loss, legacy_hello=True)
    server.serve_in_thread()
    client = RemoteAscentClient(server.address, Compressor("none"),
                                job_encoding="int8", job_delta=True)
    try:
        params = jax.device_get(_params())
        batch = jax.device_get(_batches(1)[0]["ascent"])
        for step in range(3):
            assert client.submit(0, params, batch, jax.random.PRNGKey(step),
                                 step)
            got = client.poll(block=True, timeout=120.0)
            assert got is not None and got[1] is not None
        assert client._v2_ok is False
        assert client.last_job_kind == "snapshot"
        assert client.job_encoder.delta_jobs == 0
        assert client.job_encoder.snapshot_jobs == 3
        assert client.drops == 0 and client.exchanges == 3
        assert server.deltas_applied == 0 and server.shadow_installs == 0
    finally:
        client.close()
        server.close()


@pytest.mark.parametrize("encoding", ["int8", "topk"])
def test_loopback_delta_exchange_tracks_true_gradient(encoding):
    """Delta-encoded JOBs: the server computes on its shadow reconstruction,
    so the gradient must track the true-params gradient (not bitwise);
    measured JOB frame bytes must equal the model for both job kinds."""
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    client = RemoteAscentClient(server.address, Compressor("none"),
                                job_encoding=encoding, job_delta=True,
                                job_topk_fraction=0.2)
    try:
        params = jax.device_get(_params())
        rng = jax.random.PRNGKey(5)
        batch = jax.device_get(_batches(1)[0]["ascent"])
        rs = np.random.RandomState(0)
        for step in range(4):
            assert client.submit(0, params, batch, rng, step)
            got = client.poll(block=True, timeout=120.0)
            assert got is not None and got[1] is not None
            _, g, norm, meta = got
            assert meta["job_bytes"] + meta["grad_bytes"] == meta["wire_bytes"]
            g_ref, _n, _ = jax.jit(make_ascent_fn(mlp_loss))(params, batch,
                                                             rng)
            num = sum(float(np.sum(a * np.asarray(b))) for a, b in
                      zip(jax.tree.leaves(g),
                          jax.tree.leaves(jax.device_get(g_ref))))
            na = np.sqrt(sum(float(np.sum(np.square(a)))
                             for a in jax.tree.leaves(g)))
            nb = np.sqrt(sum(float(np.sum(np.square(np.asarray(b))))
                             for b in jax.tree.leaves(jax.device_get(g_ref))))
            assert num / (na * nb + 1e-12) > 0.99
            params = jax.tree.map(
                lambda x: x + np.float32(0.01) * rs.randn(*x.shape)
                .astype(np.float32), params)
        host_rng = np.asarray(jax.device_get(rng))
        assert client.job_frame_measured["snapshot"] == \
            protocol.job_frame_bytes(encoding, params, batch, host_rng,
                                     delta=False)
        assert client.job_frame_measured[encoding] == \
            protocol.job_frame_bytes(encoding, params, batch, host_rng,
                                     delta=True, topk_fraction=0.2)
        assert client.job_encoder.delta_jobs == 3
        # the params direction shrank ~4x (whole-frame ratio is diluted at
        # toy scale by the shared batch/rng aux; the olmo-1b budget in
        # benchmarks/table_4_2_hetero.py pins the >=4x acceptance claim)
        if encoding == "int8":
            snap = protocol.job_frame_breakdown(encoding, params, batch,
                                                host_rng, delta=False)
            dlt = protocol.job_frame_breakdown(encoding, params, batch,
                                               host_rng, delta=True)
            measured_snap = client.job_frame_measured["snapshot"] - snap["aux"]
            measured_dlt = client.job_frame_measured["int8"] - dlt["aux"]
            assert measured_snap == snap["params"]
            assert measured_dlt == dlt["params"]
            assert measured_snap >= 4.0 * measured_dlt
    finally:
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# server/client exchange (in-process server thread: fast, no subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["none", "int8"])
def test_loopback_exchange_matches_local_ascent(kind):
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    client = RemoteAscentClient(server.address,
                                Compressor(kind, topk_fraction=0.1))
    try:
        params = jax.device_get(_params())
        batch = jax.device_get(_batches(1)[0]["ascent"])
        rng = jax.random.PRNGKey(5)
        assert client.submit(0, params, batch, rng, 0)
        got = client.poll(block=True, timeout=120.0)
        assert got is not None, "no gradient came back"
        gen, g, norm, meta = got
        assert gen == 0
        assert meta["wire_bytes"] > 0 and meta["rtt_s"] > 0
        # measured GRAD frame length == the protocol's exact model (a
        # proto-3 pair always carries the pool-telemetry prelude)
        assert meta["wire_in_bytes"] == protocol.grad_frame_bytes(
            client._compressor, g, pool=True)
        assert "pool_depth" in meta and "pool_wait_s" in meta
        g_ref, n_ref, _ = jax.jit(make_ascent_fn(mlp_loss))(params, batch, rng)
        if kind == "none":
            assert np.isclose(norm, float(n_ref), rtol=1e-5)
            for a, b in zip(jax.tree.leaves(g),
                            jax.tree.leaves(jax.device_get(g_ref))):
                assert np.allclose(a, b, atol=1e-6)
        else:   # lossy channel: direction preserved, not bits
            cos = sum(float(np.sum(a * np.asarray(b))) for a, b in
                      zip(jax.tree.leaves(g), jax.tree.leaves(
                          jax.device_get(g_ref))))
            assert cos > 0
    finally:
        client.close()
        server.close()


def test_server_compute_error_keeps_connection(capsys):
    """A failing server-side exchange comes back as an ERROR frame: the
    client records and surfaces it, the connection survives, and the next
    well-formed job succeeds on the same socket."""
    server = AscentServer(mlp_loss)
    server.serve_in_thread()
    client = RemoteAscentClient(server.address, Compressor("none"))
    try:
        params = jax.device_get(_params())
        bad = {"x": np.ones((4, 3), np.float32),    # wrong feature dim
               "y": np.zeros(4, np.int32)}
        assert client.submit(0, params, bad, jax.random.PRNGKey(0), 0)
        deadline = time.monotonic() + 60
        while client.server_errors == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert client.server_errors == 1 and "server error" in client.last_error
        good = jax.device_get(_batches(1)[0]["ascent"])
        assert client.submit(0, params, good, jax.random.PRNGKey(0), 0)
        got = client.poll(block=True, timeout=120.0)
        if got is not None and got[1] is None:
            # the failed job's lost-exchange sentinel; the real result follows
            got = client.poll(block=True, timeout=120.0)
        assert got is not None and got[0] == 0 and got[1] is not None
        assert client.drops == 0          # the socket was never torn down
        assert server.connections == 1    # same connection throughout
    finally:
        client.close()
        server.close()


def test_unix_socket_exchange(tmp_path):
    server = AscentServer(mlp_loss, bind=f"unix:{tmp_path}/ascent.sock")
    server.serve_in_thread()
    assert server.address.startswith("unix:")
    client = RemoteAscentClient(server.address, Compressor("none"))
    try:
        params = jax.device_get(_params())
        batch = jax.device_get(_batches(1)[0]["ascent"])
        assert client.submit(0, params, batch, jax.random.PRNGKey(5), 0)
        got = client.poll(block=True, timeout=120.0)
        assert got is not None and got[0] == 0
    finally:
        client.close()
        server.close()
    # rebinding the same path must work (stale socket files are unlinked)
    server2 = AscentServer(mlp_loss, bind=f"unix:{tmp_path}/ascent.sock")
    server2.start()
    server2.close()


def test_client_never_connected_closes_promptly():
    """Satellite: shutdown-safe join — a client pointed at a dead address
    must not hang close()."""
    client = RemoteAscentClient("127.0.0.1:1", Compressor("none"),
                                reconnect_backoff_s=0.05)
    time.sleep(0.3)          # let the worker cycle through failed connects
    t0 = time.perf_counter()
    client.close()
    client.close()           # idempotent
    assert time.perf_counter() - t0 < 8.0
    assert not client._thread.is_alive()


def test_executor_close_with_unreachable_server_does_not_hang():
    ex = RemoteExecutor(mlp_loss, MethodConfig(name="async_sam"),
                        optim.sgd(0.1),
                        exec_cfg=ExecutorConfig(ascent_addr="127.0.0.1:1",
                                                reconnect_backoff_s=0.05))
    t0 = time.perf_counter()
    ex.close()
    ex.close()
    assert time.perf_counter() - t0 < 8.0


# ---------------------------------------------------------------------------
# loopback subprocess: parity + resilience (the acceptance criteria)
# ---------------------------------------------------------------------------

def _fit(executor, steps=8):
    with executor as ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        report = Engine(ex, _batches(steps)).fit(state, steps)
    return report


def test_remote_matches_hetero_step_for_step():
    """Acceptance: loopback --executor remote == --executor hetero on a fixed
    seed — same tau schedule, same losses — under the lockstep test mode
    (both lanes then consume every submitted gradient exactly one step
    later, removing queue-timing nondeterminism)."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.1, momentum=0.9)
    rep_h = _fit(HeteroExecutor(mlp_loss, mcfg, opt,
                                exec_cfg=ExecutorConfig(lockstep=True)))
    rep_r = _fit(RemoteExecutor(
        mlp_loss, mcfg, opt,
        exec_cfg=ExecutorConfig(lockstep=True, serve_ascent=True,
                                loss_spec=MLP_LOSS_SPEC)))
    taus_h = [h["tau"] for h in rep_h.metrics_history]
    taus_r = [h["tau"] for h in rep_r.metrics_history]
    assert taus_h == taus_r == [0.0] + [1.0] * (len(taus_h) - 1)
    losses_h = [h["loss"] for h in rep_h.metrics_history]
    losses_r = [h["loss"] for h in rep_r.metrics_history]
    np.testing.assert_allclose(losses_r, losses_h, rtol=1e-6, atol=1e-7)
    # remote metrics carry the wire telemetry; hetero's do not. wire_bytes
    # stays the sum of the per-direction split (backward compat)
    last = rep_r.metrics_history[-1]
    assert "wire_bytes" in last and "rtt_s" in last
    assert last["job_bytes"] + last["grad_bytes"] == last["wire_bytes"]
    assert "wire_bytes" not in rep_h.metrics_history[-1]


def test_remote_loopback_drives_loss_down_vs_fused():
    """Loopback remote training descends like the single-process executors."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.1, momentum=0.9)
    steps = 25
    rep = _fit(RemoteExecutor(
        mlp_loss, mcfg, opt,
        exec_cfg=ExecutorConfig(lockstep=True, serve_ascent=True,
                                loss_spec=MLP_LOSS_SPEC)), steps=steps)
    losses = [h["loss"] for h in rep.metrics_history]
    assert rep.steps_done == steps
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]


def test_server_killed_midfit_training_recovers(tmp_path):
    """Acceptance: killing the ascent server mid-fit must not crash the run —
    the loopback executor respawns it, the client reconnects (dropping the
    in-flight exchange), and the tau telemetry records the gap."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.05, momentum=0.9)
    xcfg = ExecutorConfig(serve_ascent=True, loss_spec=MLP_LOSS_SPEC,
                          max_staleness=2, max_server_respawns=1,
                          reconnect_backoff_s=0.1)
    telemetry = StalenessTelemetry(
        print_summary=False, jsonl_path=tmp_path / "remote.jsonl")
    pool = _batches(50)
    batches = ({**b} for b in itertools.cycle(pool))

    with RemoteExecutor(mlp_loss, mcfg, opt, exec_cfg=xcfg) as ex:
        eng = Engine(ex, batches, [telemetry])
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        # phase 1: step until the remote lane delivered its first gradient
        deadline = time.monotonic() + 120
        m = {"perturbed": 0.0}
        while time.monotonic() < deadline and m["perturbed"] != 1.0:
            state, m = ex.step(state, next(batches))
            time.sleep(0.02)
        assert m["perturbed"] == 1.0, "remote lane never delivered"
        assert m["wire_bytes"] > 0 and m["rtt_s"] > 0

        ex.server.proc.kill()
        ex.server.proc.wait()

        # phase 2: keep stepping through the outage; the run must keep
        # completing steps (tau grows, SGD fallback) and eventually recover
        saw_gap = recovered = False
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            state, m = ex.step(state, next(batches))
            telemetry.on_step(eng, state, m, 0.0)
            if m["perturbed"] == 0.0:
                saw_gap = True
            if saw_gap and m["perturbed"] == 1.0 and m["tau"] == 1:
                recovered = True
                break
            time.sleep(0.02)
        assert saw_gap, "tau telemetry shows no gap after server death"
        assert recovered, "client did not reconnect to the respawned server"
        assert ex.server_respawns == 1
        assert ex.client.reconnects >= 1 and ex.client.drops >= 1
    # the jsonl trace records the gap and the wire telemetry
    telemetry.on_fit_end(eng, None)
    import json
    records = [json.loads(l) for l in
               (tmp_path / "remote.jsonl").read_text().splitlines()]
    assert any(r["perturbed"] == 0.0 for r in records)
    assert any(r.get("wire_bytes", 0) > 0 and r.get("rtt_s", 0) > 0
               for r in records)


def _lockstep_delta_run(steps=12, kill_at=None):
    """One lockstep remote run with int8 JOB deltas; optionally kill the
    loopback server right before step `kill_at` (it respawns)."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.1, momentum=0.9)
    xcfg = ExecutorConfig(lockstep=True, serve_ascent=True,
                          loss_spec=MLP_LOSS_SPEC, job_compress="int8",
                          job_delta=True, max_server_respawns=2,
                          reconnect_backoff_s=0.1)
    losses, stats = [], {}
    with RemoteExecutor(mlp_loss, mcfg, opt, exec_cfg=xcfg) as ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        for i, b in enumerate(_batches(steps)):
            if kill_at is not None and i == kill_at:
                ex.server.proc.kill()
                ex.server.proc.wait()
            state, m = ex.step(state, b)
            losses.append(float(m["loss"]))
        stats = dict(respawns=ex.server_respawns,
                     reconnects=ex.client.reconnects,
                     retried=ex.client.retried_exchanges,
                     resyncs=ex.client.job_encoder.resyncs,
                     snapshots=ex.client.job_encoder.snapshot_jobs,
                     deltas=ex.client.job_encoder.delta_jobs)
    return losses, stats


def test_server_killed_midfit_delta_stream_reconverges_bitwise():
    """Satellite: killing the server mid-fit under lockstep with int8 JOB
    deltas must be invisible to the schedule — the client reconnects to the
    respawned server and falls back to a full-snapshot JOB of its shadow
    (exactly the params the lost delta encoded), so every loss matches the
    never-disconnected run bit for bit."""
    base, base_stats = _lockstep_delta_run()
    killed, stats = _lockstep_delta_run(kill_at=6)
    assert base_stats["respawns"] == 0 and base_stats["resyncs"] == 0
    assert stats["respawns"] == 1, stats
    assert stats["reconnects"] >= 1
    # the recovery went through the full-snapshot fallback: either the
    # in-flight exchange was resent as a snapshot (retried>0) or the next
    # delta drew a RESYNC from the fresh server (resyncs>0)
    assert stats["retried"] + stats["resyncs"] >= 1, stats
    assert stats["snapshots"] >= 2        # initial sync + the resync
    assert np.array_equal(np.asarray(killed), np.asarray(base)), \
        (base, killed)


def test_remote_calibration_probe_measures_the_wire():
    """calibrate() on the remote lane runs real round trips to the server."""
    mcfg = MethodConfig(name="async_sam", rho=0.05, ascent_fraction=0.5)
    opt = optim.sgd(0.1, momentum=0.9)
    with RemoteExecutor(mlp_loss, mcfg, opt, calibrate=True,
                        calibration_probes=1,
                        exec_cfg=ExecutorConfig(
                            serve_ascent=True,
                            loss_spec=MLP_LOSS_SPEC)) as ex:
        state = ex.init_state(_params(), jax.random.PRNGKey(1))
        report = Engine(ex, _batches(3)).fit(state, 3)
    assert report.pre_fit is not None
    frac = report.pre_fit["calibrated_ascent_fraction"]
    assert 0.05 <= frac <= 1.0
    assert ex.client.exchanges >= 2   # warmup + timed probe at minimum


def test_spawn_server_bad_loss_spec_fails_fast():
    with pytest.raises(RuntimeError, match="failed to start"):
        spawn_server("repro.service.testing:does_not_exist",
                     startup_timeout_s=60.0)


@pytest.mark.parametrize("device,platforms", [
    ("", "cpu"), ("cpu:0", "cpu"), ("tpu:1", None)])
def test_spawn_server_stays_off_the_accelerator(monkeypatch, device,
                                                platforms):
    """A loopback server would fight its parent for the chip: the child is
    held to the CPU unless `device` names another platform."""
    import repro.service.ascent_server as srv
    seen = {}

    class _Exited:
        def __init__(self, cmd, env, **kw):
            seen["env"] = env
            self.stdout = io.StringIO("")

        def poll(self):
            return 1

        def kill(self):
            pass

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(srv.subprocess, "Popen", _Exited)
    with pytest.raises(RuntimeError, match="failed to start"):
        spawn_server("repro.service.testing:mlp_loss", device=device,
                     startup_timeout_s=5.0)
    assert seen["env"].get("JAX_PLATFORMS") == platforms

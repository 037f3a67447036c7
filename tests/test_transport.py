"""Socket shard transport (``repro.index.transport``):

  * ``SocketShardClient`` fan-out is bit-identical (ids AND scores) to
    the in-process ``LocalShardClient`` router and to a single
    unsharded index, exact + LSH + the Theorem-1 set-sizes rerank,
  * a truncated frame, a corrupt frame, and a mid-response connection
    drop each surface as a clean per-dispatch ``TransportError`` /
    timeout -- never a hang, never a torn ``SearchResult``,
  * the service itself survives garbage input and keeps serving.
"""

import glob
import os
import socket
import struct
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.oph import OPH
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.sigshard import write_sig_shard
from repro.data.sparse import from_lists
from repro.data.synthetic import DatasetSpec
from repro.index import (BandingConfig, IndexSearcher, ShardService,
                         SocketShardClient, TransportError, build_index,
                         build_sharded, choose_band_config, load_index,
                         load_sharded, loopback_client_factory)
from repro.index.transport import _MAGIC, RemoteShardError, _pack_msg
from repro.kernels import SignatureEngine

K, S, B = 128, 16, 8


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Synthetic corpus: .sig shards, a 3-shard dir, one reference .idx."""
    tmp = str(tmp_path_factory.mktemp("transport_corpus"))
    spec = DatasetSpec("transport", n=300, D=1 << S, avg_nnz=48,
                       n_prototypes=8, overlap=0.8, seed=21)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=4)
    fam = OPH.create(jax.random.PRNGKey(4), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                      chunk_size=64)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    cfg = choose_band_config(K, B, threshold=0.5)
    idx_path = os.path.join(tmp, "single.idx")
    build_index(sig_paths, idx_path, cfg)
    shard_dir = os.path.join(tmp, "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=3)
    return tmp, shard_dir, idx_path


def test_socket_fanout_bit_identical(corpus):
    """Socket transport == local clients == single index, both modes."""
    _, shard_dir, idx_path = corpus
    single = IndexSearcher(load_index(idx_path), backend="interpret",
                           corpus_block=64)
    local = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                         dispatch="sequential")
    fac = loopback_client_factory(timeout_s=30.0)
    try:
        sock_router = load_sharded(shard_dir, backend="interpret",
                                   corpus_block=64, dispatch="sequential",
                                   client_factory=fac)
        n = single.index.n
        q = jnp.asarray(np.ascontiguousarray(
            single.index.words_host[[0, 3, n // 3, n // 2, n - 1]]))
        for mode in ("exact", "lsh"):
            want = single.search(q, 10, mode=mode)
            via_local = local.search(q, 10, mode=mode)
            got = sock_router.search(q, 10, mode=mode)
            for ref in (want, via_local):
                assert np.array_equal(got.indices, ref.indices), mode
                assert np.array_equal(got.scores, ref.scores), mode
            if mode == "lsh":
                assert np.array_equal(got.n_candidates, want.n_candidates)
        # the hello roundtrip reports per-shard doc counts
        assert [c.n for c in fac.clients] == \
            [s.index.n for s in sock_router.searchers]
    finally:
        fac.close()


def test_socket_set_sizes_rerank(tmp_path):
    """Theorem-1 rerank crosses the wire: query_sizes serialize too."""
    rng = np.random.default_rng(5)
    sets = [rng.choice(1 << S, rng.integers(30, 90), replace=False)
            for _ in range(96)]
    batch = from_lists(sets, max_nnz=128)
    fam = OPH.create(jax.random.PRNGKey(2), K, S, "2u", "rotation")
    wire = SignatureEngine(fam, b=B, packed=True).packed_signatures(batch)
    sizes = np.array([len(s) for s in sets], np.uint32)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"c{i}.sig")
        write_sig_shard(p, np.asarray(wire.data[i * 32:(i + 1) * 32]),
                        np.zeros(32, np.float32), k=K, b=B, code_bits=B)
        paths.append(p)
    cfg = BandingConfig(16, 2, B)
    build_index(paths, str(tmp_path / "one.idx"), cfg, set_sizes=sizes, s=S)
    build_sharded(paths, str(tmp_path / "sh"), cfg, n_shards=3,
                  set_sizes=sizes, s=S)
    single = IndexSearcher(load_index(str(tmp_path / "one.idx")),
                           backend="interpret", corpus_block=32)
    fac = loopback_client_factory()
    try:
        router = load_sharded(str(tmp_path / "sh"), backend="interpret",
                              corpus_block=32, client_factory=fac)
        want = single.search(wire[:5], 5, mode="exact",
                             query_sizes=sizes[:5])
        got = router.search(wire[:5], 5, mode="exact",
                            query_sizes=sizes[:5])
        assert np.array_equal(got.indices, want.indices)
        assert np.array_equal(got.scores, want.scores)
    finally:
        fac.close()


def test_service_survives_garbage_and_remote_errors(corpus):
    """Garbage bytes and failing requests never kill the service."""
    _, shard_dir, idx_path = corpus
    searcher = IndexSearcher(load_index(idx_path), backend="interpret",
                             corpus_block=64)
    svc = ShardService(searcher)
    try:
        # raw garbage: connection is dropped, service stays up
        with socket.create_connection(svc.address, timeout=5.0) as s:
            s.sendall(b"\x00" * 64)
        # a framed-but-invalid request gets an error frame
        client = SocketShardClient(svc.address, timeout_s=5.0)
        q = np.ascontiguousarray(searcher.index.words_host[:2])
        with pytest.raises(RemoteShardError):
            client.dispatch(q, 5, mode="nonsense")()
        # and a valid request still round-trips afterwards
        got = client.dispatch(q, 5)()
        want = searcher.dispatch(q, 5)()
        assert np.array_equal(got.indices, want.indices)
        assert np.array_equal(got.scores, want.scores)
        assert client.n == searcher.index.n
    finally:
        svc.close()


def _fake_server(handler):
    """One-connection fake shard server running ``handler(conn)``."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def run():
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        with conn:
            handler(conn)
        srv.close()
    threading.Thread(target=run, daemon=True).start()
    return srv.getsockname()


def _drain_request(conn):
    # read until the client has sent its (single) request frame; the
    # fake servers don't parse it, they just misbehave afterwards
    conn.settimeout(5.0)
    try:
        conn.recv(1 << 20)
    except OSError:
        pass


def test_truncated_response_is_clean_error():
    """A response cut mid-frame raises TransportError -- no hang, and no
    torn SearchResult can ever escape."""
    full = _pack_msg({"kind": "result"},
                     [("indices", np.zeros((1, 5), np.int64)),
                      ("scores", np.zeros((1, 5), np.float32))])

    def handler(conn):
        _drain_request(conn)
        conn.sendall(full[:len(full) // 2])   # then close: torn frame

    addr = _fake_server(handler)
    client = SocketShardClient(addr, timeout_s=5.0)
    harvest = client.dispatch(np.zeros((1, 4), np.uint32), 5)
    with pytest.raises(TransportError, match="mid-frame"):
        harvest()


def test_corrupt_frame_surfaces_as_transport_error():
    """Bad magic and an undecodable header are both clean errors."""
    def bad_magic(conn):
        _drain_request(conn)
        conn.sendall(b"XXXX" + struct.pack("<I", 4) + b"junk")

    def bad_header(conn):
        _drain_request(conn)
        payload = struct.pack("<I", 8) + b"\xff" * 8
        conn.sendall(_MAGIC + struct.pack("<I", len(payload)) + payload)

    for handler, match in ((bad_magic, "magic"), (bad_header, "corrupt")):
        client = SocketShardClient(_fake_server(handler), timeout_s=5.0)
        harvest = client.dispatch(np.zeros((1, 4), np.uint32), 5)
        with pytest.raises(TransportError, match=match):
            harvest()


def test_short_array_buffer_is_clean_error():
    """A result frame whose declared arrays outrun the payload is torn --
    the client must reject it, not hand back a short-read ndarray."""
    def handler(conn):
        _drain_request(conn)
        hdr = (b'{"kind": "result", "arrays": '
               b'[["indices", "<i8", [4, 10]]]}')
        payload = struct.pack("<I", len(hdr)) + hdr + b"\x00" * 16
        conn.sendall(_MAGIC + struct.pack("<I", len(payload)) + payload)

    client = SocketShardClient(_fake_server(handler), timeout_s=5.0)
    harvest = client.dispatch(np.zeros((1, 4), np.uint32), 5)
    with pytest.raises(TransportError, match="truncated"):
        harvest()


def test_unresponsive_server_times_out():
    """A server that accepts and goes silent trips the socket timeout
    (an OSError, so retry policies treat it like any transport fault)."""
    def handler(conn):
        _drain_request(conn)
        threading.Event().wait(2.0)           # say nothing

    client = SocketShardClient(_fake_server(handler), timeout_s=0.2)
    harvest = client.dispatch(np.zeros((1, 4), np.uint32), 5)
    with pytest.raises(OSError):
        harvest()

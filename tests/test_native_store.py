"""Native runtime tests: gpack container round-trip (native + numpy readers)
and the DistDataset store incl. a real TCP remote get against the local
server (the single-host analog of DDStore remote reads)."""

import ctypes
import pickle

import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample
from hydragnn_tpu.native import available, load_library


def _samples(n=10, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        nn = rng.randint(3, 9)
        ne = rng.randint(2, 12)
        out.append(GraphSample(
            x=rng.rand(nn, 3).astype(np.float32),
            pos=rng.rand(nn, 3).astype(np.float32),
            edge_index=rng.randint(0, nn, (2, ne)).astype(np.int32),
            graph_y=rng.rand(2).astype(np.float32),
            node_y=rng.rand(nn, 3).astype(np.float32),
        ))
    return out


def test_native_library_builds():
    assert available(), "native hydrastore library failed to build"


def test_native_cache_keyed_on_source_hash(tmp_path, monkeypatch):
    """The cached library is trusted by a hash of the tracked source kept
    beside it, never by mtime (a copied tree does not preserve mtimes): a
    library with no stamp, or a stamp naming another source, is stale."""
    from hydragnn_tpu import native

    lib = tmp_path / "libhydrastore.so"
    monkeypatch.setattr(native, "_LIB", str(lib))
    monkeypatch.setattr(native, "_STAMP", str(lib) + ".srchash")
    assert native._stale()                      # nothing built
    lib.write_bytes(b"an older build the copy brought along")
    assert native._stale()                      # library without a stamp
    (tmp_path / "libhydrastore.so.srchash").write_text("0" * 64 + "\n")
    assert native._stale()                      # stamp of another source
    native._build()
    assert not native._stale()
    assert (tmp_path / "libhydrastore.so.srchash").read_text().strip() \
        == native._src_hash()
    ctypes.CDLL(str(lib))                       # and it is a real library


@pytest.mark.parametrize("use_native", [True, False])
def test_gpack_roundtrip(tmp_path, use_native):
    from hydragnn_tpu.data.gpack import GpackDataset, GpackWriter

    samples = _samples(12)
    path = str(tmp_path / "ds.gpack")
    GpackWriter(path, rank=0, attrs={
        "pna_deg": [0, 3, 5], "minmax": [[0.0], [1.0]]}).save(samples)

    ds = GpackDataset(path, use_native=use_native)
    assert len(ds) == 12
    assert ds.attrs["pna_deg"] == [0, 3, 5]
    for i in (0, 5, 11):
        got = ds.get(i)
        np.testing.assert_array_equal(got.x, samples[i].x)
        np.testing.assert_array_equal(got.pos, samples[i].pos)
        np.testing.assert_array_equal(got.edge_index, samples[i].edge_index)
        np.testing.assert_array_equal(got.graph_y, samples[i].graph_y)
    ds.close()


def test_gpack_multipart_and_subset(tmp_path):
    from hydragnn_tpu.data.gpack import GpackDataset, GpackWriter

    s0, s1 = _samples(5, seed=1), _samples(7, seed=2)
    base = str(tmp_path / "multi.gpack")
    GpackWriter(base, rank=0).save(s0)
    GpackWriter(base, rank=1).save(s1)

    ds = GpackDataset(base)
    assert len(ds) == 12
    np.testing.assert_array_equal(ds.get(3).x, s0[3].x)
    np.testing.assert_array_equal(ds.get(5).x, s1[0].x)
    np.testing.assert_array_equal(ds.get(11).x, s1[6].x)

    ds.setsubset(5, 12, preload=True)
    assert len(ds) == 7
    np.testing.assert_array_equal(ds.get(0).x, s1[0].x)
    ds.close()


def test_distdataset_local_get():
    from hydragnn_tpu.data.distdataset import DistDataset

    samples = _samples(8, seed=3)
    ds = DistDataset(samples)
    assert len(ds) == 8
    for i in (0, 4, 7):
        got = ds.get(i)
        np.testing.assert_array_equal(got.x, samples[i].x)
    ds.close()


def test_dstore_tcp_remote_get():
    """Exercise the TCP path explicitly against the local server."""
    lib = load_library()
    store = lib.dstore_create(0)
    assert store
    port = lib.dstore_port(store)

    blobs = [pickle.dumps({"i": i, "a": np.arange(i + 1)}) for i in range(5)]
    sizes = np.asarray([len(b) for b in blobs], np.int64)
    lib.dstore_add(store, b"k", b"".join(blobs),
                   sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                   5, 100)  # global indices 100..104

    fd = lib.dstore_connect(b"127.0.0.1", port)
    assert fd >= 0
    buf = ctypes.create_string_buffer(1 << 16)
    for gidx in (100, 103, 104):
        n = lib.dstore_fetch(fd, b"k", gidx, buf, len(buf))
        assert n > 0
        obj = pickle.loads(buf.raw[:n])
        assert obj["i"] == gidx - 100
        np.testing.assert_array_equal(obj["a"], np.arange(gidx - 100 + 1))
    # missing index -> -1
    assert lib.dstore_fetch(fd, b"k", 99, buf, len(buf)) == -1
    lib.dstore_disconnect(fd)
    lib.dstore_destroy(store)


def test_dstore_connect_timeout_unreachable():
    """Connecting to a non-listening port fails fast, not forever."""
    import time

    lib = load_library()
    # grab a port nobody listens on
    import socket as pysock

    s = pysock.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()

    t0 = time.perf_counter()
    fd = lib.dstore_connect_timeout(b"127.0.0.1", dead_port, 1000)
    dt = time.perf_counter() - t0
    assert fd < 0
    assert dt < 5.0  # refused or timed out well within bounds


def test_dstore_kill_a_peer(tmp_path):
    """A server killed mid-conversation surfaces as a bounded error on the
    client, not a hang or short-read garbage (round-3 VERDICT item 9)."""
    import signal
    import subprocess
    import sys
    import time

    server_src = tmp_path / "server.py"
    server_src.write_text(
        "import ctypes, pickle, sys, time\n"
        "import numpy as np\n"
        "from hydragnn_tpu.native import load_library\n"
        "lib = load_library()\n"
        "store = lib.dstore_create(0)\n"
        "blob = pickle.dumps(np.arange(32))\n"
        "sizes = np.asarray([len(blob)], np.int64)\n"
        "lib.dstore_add(store, b'k', blob,\n"
        "    sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), 1, 0)\n"
        "print(lib.dstore_port(store), flush=True)\n"
        "time.sleep(600)\n")
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, str(server_src)], stdout=subprocess.PIPE, text=True,
        env=env, cwd=repo)
    try:
        port = int(proc.stdout.readline())
        lib = load_library()
        fd = lib.dstore_connect_timeout(b"127.0.0.1", port, 2000)
        assert fd >= 0
        buf = ctypes.create_string_buffer(1 << 12)
        n = lib.dstore_fetch(fd, b"k", 0, buf, len(buf))
        assert n > 0  # healthy fetch first

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

        t0 = time.perf_counter()
        n = lib.dstore_fetch(fd, b"k", 0, buf, len(buf))
        dt = time.perf_counter() - t0
        assert n == -3, f"expected I/O failure code, got {n}"
        assert dt < 10.0
        lib.dstore_disconnect(fd)
    finally:
        if proc.poll() is None:
            proc.kill()


def test_distdataset_dead_owner_raises(monkeypatch):
    """The Python wrapper turns a dead owner into a RuntimeError naming the
    peer, after one reconnect attempt — no silent hang, no assert."""
    import socket as pysock

    from hydragnn_tpu.data.distdataset import DistDataset

    monkeypatch.setenv("HYDRASTORE_TIMEOUT_MS", "800")
    ds = DistDataset(_samples(4), label="deadpeer")
    try:
        # forge a second, dead owner holding global indices 4..7
        s = pysock.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        ds.counts = [4, 4]
        ds.total = 8
        ds.addresses = list(ds.addresses) + [("127.0.0.1", dead_port)]

        with pytest.raises(RuntimeError, match="dstore owner 1"):
            ds.get(6)
    finally:
        ds.close()

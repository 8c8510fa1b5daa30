"""Zero-copy streaming checkpoint I/O engine: v2 format, ranged restore,
CRC-once, replica copy fan-out, bounded buffering, v1 read-compat."""
import io
import tracemalloc
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint import serialization as SER
from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.store import TieredStore


def _tree(rng):
    return {
        "w": rng.standard_normal((64, 32)).astype(np.float32),
        "b": rng.standard_normal((256,)).astype(np.float32),
        "step": np.int32(7),
        "scalar": np.float64(2.5),
    }


class CountingStore(TieredStore):
    """Counts payload bytes actually fetched through the ranged-read choke
    point (`_pread`)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.bytes_read = 0

    def _pread(self, path, offset, nbytes):
        data = super()._pread(path, offset, nbytes)
        self.bytes_read += len(data)
        return data


# ---------------------------------------------------------------------------
# format v2 + v1 read-compat
# ---------------------------------------------------------------------------

def test_v2_roundtrip(rng):
    tree = _tree(rng)
    recs = SER.tree_to_records(tree)
    data = SER.write_shard_bytes_v2(recs, meta={"k": 2})
    assert data[:8] == SER.MAGIC2 and data[-8:] == SER.MAGIC2
    named, meta = SER.read_shard_bytes(data)
    assert meta == {"k": 2}
    out = SER.restore_tree(tree, named)
    for name, a in SER.flatten_with_names(tree):
        b = dict(SER.flatten_with_names(out))[name]
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_v1_files_read_through_new_reader(rng):
    """Bytes produced by the seed-era v1 writer parse through every new
    reader: whole-buffer, ranged header, and leaf-granular store read."""
    tree = _tree(rng)
    recs = SER.tree_to_records(tree)
    v1 = SER.write_shard_bytes(recs, meta={"v": 1})
    assert v1[:8] == SER.MAGIC
    named, meta = SER.read_shard_bytes(v1)
    assert meta == {"v": 1}
    assert np.array_equal(named["w"], tree["w"])

    # ranged header read on v1 normalizes offsets to absolute
    def read_at(off, n):
        return v1[off:off + n]
    header = SER.read_shard_header(read_at, len(v1))
    assert header["format"] == 1
    got, _ = SER.read_shard_leaves(read_at, len(v1), ["b"])
    assert np.array_equal(got["b"], tree["b"])


def test_v1_shard_payload_ending_in_v2_magic_still_parses():
    """A v1 shard whose LAST payload bytes coincidentally equal the v2
    trailer magic must not be misread by the tail-probe fast path — the
    leading magic disambiguates, and the v1 parse still succeeds."""
    payload = b"x" * 24 + SER.MAGIC2
    arr = np.frombuffer(payload, dtype=np.uint8).copy()
    data = SER.write_shard_bytes([("a", arr)])
    assert data[:8] == SER.MAGIC and data[-8:] == SER.MAGIC2   # the collision
    named, _ = SER.read_shard_bytes(data)
    assert named["a"].tobytes() == payload

    def read_at(off, n):
        return data[off:off + n]
    header = SER.read_shard_header(read_at, len(data))
    assert header["format"] == 1


def test_v1_checkpoint_restores_through_new_manager(tmp_path, rng):
    """A checkpoint written via the legacy v1 path (seed byte layout) restores
    through the new ranged-read manager."""
    store = TieredStore(tmp_path)
    m1 = CheckpointManager(store, CheckpointPolicy(shard_format=1))
    tree = _tree(rng)
    m1.save(3, tree)
    m1.commit(3)
    shard = next(tmp_path.rglob("shard_*.bin"))
    assert shard.read_bytes()[:8] == SER.MAGIC   # really v1 on disk
    m2 = CheckpointManager(store)                # default v2 reader/writer
    out, man = m2.restore(tree)
    assert man["step"] == 3
    assert np.array_equal(out["w"], tree["w"])


def test_ranged_read_equals_full_read(rng):
    tree = _tree(rng)
    data = SER.write_shard_bytes_v2(SER.tree_to_records(tree))

    def read_at(off, n):
        return data[off:off + n]

    full, _ = SER.read_shard_leaves(read_at, len(data), None)
    for name in full:
        one, _ = SER.read_shard_leaves(read_at, len(data), [name])
        assert set(one) == {name}
        assert np.array_equal(one[name], full[name])
        assert one[name].dtype == full[name].dtype


def test_ranged_read_detects_corruption(rng):
    tree = _tree(rng)
    data = bytearray(SER.write_shard_bytes_v2(SER.tree_to_records(tree)))

    def read_at(off, n):
        return bytes(data[off:off + n])

    header = SER.read_shard_header(read_at, len(data))
    t0 = header["tensors"][0]
    data[t0["offset"] + 2] ^= 0xFF           # corrupt the first leaf's payload
    with pytest.raises(SER.ChecksumError):
        SER.read_shard_leaves(read_at, len(data), [t0["path"]])
    # untouched leaves still read clean through ranged access
    other = header["tensors"][-1]["path"]
    got, _ = SER.read_shard_leaves(read_at, len(data), [other])
    assert other in got


# ---------------------------------------------------------------------------
# CRC exactly once per leaf on the save path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("incremental", [False, True])
def test_crc_computed_once_per_leaf(tmp_path, rng, monkeypatch, incremental):
    """Exactly one CRC pass per leaf per save: folded inside the streaming
    writer (plain mode) or pre-computed as the diff key and trusted by the
    writer (incremental mode) — never both."""
    store = TieredStore(tmp_path)
    m = CheckpointManager(store, CheckpointPolicy(
        replicas=2, incremental=incremental, keep_last=10))
    tree = _tree(rng)
    n_leaves = len(SER.flatten_with_names(tree))
    if incremental:
        m.save(1, tree)       # establish a prev manifest so save 2 diffs
        m.commit(1)
        tree = dict(tree)
        tree["w"] = tree["w"] + 1

    calls = {"crc32": 0}
    real_crc32 = zlib.crc32

    def counting_crc32(buf, start=0):
        calls["crc32"] += 1
        return real_crc32(buf, start)

    monkeypatch.setattr(SER.zlib, "crc32", counting_crc32)
    try:
        m.save(2, tree)
    finally:
        monkeypatch.undo()
    # every leaf is small (< one chunk), so any double-CRC — e.g. the writer
    # re-hashing what leaf_checksum already hashed — would show as > n_leaves
    assert calls["crc32"] == n_leaves


def test_writer_trusts_precomputed_crcs(rng):
    arr = rng.standard_normal((8, 8)).astype(np.float32)
    fake_crc = 0xDEADBEEF
    buf = io.BytesIO()
    footer = SER.write_shard_stream(buf, [("w", arr)], crcs={"w": fake_crc})
    assert footer["tensors"][0]["crc32"] == fake_crc


# ---------------------------------------------------------------------------
# replica fan-out: serialize once, OS-copy k-1 times, byte-identical
# ---------------------------------------------------------------------------

def test_replica_fanout_writes_once_and_is_byte_identical(tmp_path, rng):
    store = TieredStore(tmp_path)
    n_stream_calls = {"n": 0}
    tree = _tree(rng)
    recs = SER.tree_to_records(tree)

    def write_fn(fp):
        n_stream_calls["n"] += 1
        return SER.write_shard_stream(fp, recs)

    paths = store.put_stream("shared", "ck/s.bin", write_fn, replicas=3)
    assert n_stream_calls["n"] == 1          # payload serialized exactly once
    assert len(paths) == 3
    blobs = [(tmp_path / p).read_bytes() for p in paths]
    assert all(b == blobs[0] for b in blobs)
    # hardlink-free copies: corrupting one replica must not corrupt the rest
    inodes = {(tmp_path / p).stat().st_ino for p in paths}
    assert len(inodes) == 3


def test_put_fanout_byte_identical(tmp_path):
    store = TieredStore(tmp_path)
    paths = store.put("shared", "a/b.json", b"{\"x\": 1}", replicas=3)
    assert len(paths) == 3
    blobs = [(tmp_path / p).read_bytes() for p in paths]
    assert all(b == b"{\"x\": 1}" for b in blobs)


def test_stale_replica_missing_leaf_falls_back(tmp_path, rng):
    """A replica that parses fine but lacks a requested leaf (stale write) is
    treated like any damaged replica: fall back to the intact one."""
    store = TieredStore(tmp_path)
    recs = SER.tree_to_records(_tree(rng))
    paths = store.put_stream(
        "shared", "ck/s.bin", lambda fp: SER.write_shard_stream(fp, recs),
        replicas=2)
    stale = SER.write_shard_bytes_v2(recs[:1])       # valid shard, fewer leaves
    (tmp_path / paths[0]).write_bytes(stale)
    want = recs[-1][0]
    got, _ = store.read_shard_leaves("shared", "ck/s.bin", [want])
    assert np.array_equal(got[want], dict(recs)[want])


def test_get_range(tmp_path):
    store = TieredStore(tmp_path)
    store.put("shared", "f.bin", b"0123456789", replicas=2)
    assert store.get_range("shared", "f.bin", 3, 4) == b"3456"
    # a range past EOF is a truncated read, never silently-shorter data
    with pytest.raises(FileNotFoundError, match="short read"):
        store.get_range("shared", "f.bin", 8, 100)


@pytest.mark.parametrize("how", ["pread", "pread_batch"])
def test_partial_reads_still_give_the_whole_range(tmp_path, monkeypatch, how):
    """A positional read may return fewer bytes than asked short of the
    file's end (network file systems do); the store reads on."""
    import os

    blob = bytes(range(256)) * 40
    store = TieredStore(tmp_path)
    store.direct_io = False
    store.put("shared", "f.bin", blob, replicas=1)
    (p,) = store.replica_paths("shared", "f.bin")
    real_pread, real_preadv = os.pread, os.preadv
    monkeypatch.setattr(os, "pread", lambda fd, n, off: real_pread(fd, min(n, 7), off))
    monkeypatch.setattr(os, "preadv", lambda fd, bufs, off:
                        real_preadv(fd, [memoryview(bufs[0])[:7]], off))
    ranges = [(3, 5000), (5003, 100), (9000, 1240)]
    if how == "pread":
        got = [store.pread("shared", p, off, n) for off, n in ranges]
    else:
        got = store.pread_batch("shared", [(p, off, n) for off, n in ranges])
    assert got == [blob[off:off + n] for off, n in ranges]


def test_short_read_past_the_end_names_the_file_size(tmp_path):
    store = TieredStore(tmp_path)
    store.put("shared", "f.bin", b"0123456789", replicas=1)
    (p,) = store.replica_paths("shared", "f.bin")
    with pytest.raises(OSError, match=r"short read 2/100 at offset 8 .*\(file size 10\)"):
        store.pread("shared", p, 8, 100)


def test_async_writer_bounds_inflight_tasks():
    import threading as th

    from repro.checkpoint.async_writer import AsyncWriter

    w = AsyncWriter(max_inflight=2)
    gate = th.Event()
    running = []

    def task():
        running.append(1)
        gate.wait(5)

    w.submit(task)
    w.submit(task)
    # third submit must block (2 unfinished tasks pinned) until one finishes
    t = th.Thread(target=lambda: w.submit(task), daemon=True)
    t.start()
    t.join(0.3)
    assert t.is_alive(), "submit exceeded the inflight bound"
    gate.set()
    t.join(5)
    assert not t.is_alive()
    w.close()
    assert len(running) == 3


def test_get_falls_back_on_oserror(tmp_path, monkeypatch):
    store = TieredStore(tmp_path)
    paths = store.put("shared", "f.bin", b"payload", replicas=2)
    bad = tmp_path / paths[0]
    real_read_bytes = Path.read_bytes

    def flaky_read_bytes(self):
        if self == bad:
            raise OSError("simulated torn replica")
        return real_read_bytes(self)

    monkeypatch.setattr(Path, "read_bytes", flaky_read_bytes)
    assert store.get("shared", "f.bin") == b"payload"


# ---------------------------------------------------------------------------
# ranged restore reads strictly fewer bytes than the full shard
# ---------------------------------------------------------------------------

def test_single_leaf_restore_reads_fewer_bytes(tmp_path, rng):
    store = CountingStore(tmp_path)
    m = CheckpointManager(store, CheckpointPolicy(replicas=1))
    tree = _tree(rng)
    m.save(1, tree)
    m.commit(1)
    shard_rel = next(e["file"] for e in m.read_manifest(1)["leaves"])
    full_size = store.size("shared", shard_rel)

    store.bytes_read = 0
    one, _ = store.read_shard_leaves("shared", shard_rel, ["step"])
    assert int(one["step"]) == 7
    assert 0 < store.bytes_read < full_size


def test_incremental_restore_skips_stale_base_leaves(tmp_path, rng):
    """The MxN/incremental path: restoring a manifest whose entries point at
    an old base shard must not re-read the base wholesale — the superseded
    (stale) byte ranges in the base are never fetched."""
    store = CountingStore(tmp_path)
    m = CheckpointManager(store, CheckpointPolicy(incremental=True, keep_last=10, replicas=1))
    tree = _tree(rng)
    tree["big"] = rng.standard_normal((256, 1024)).astype(np.float32)  # 1 MB
    m.save(1, tree)
    m.commit(1)
    tree2 = dict(tree)
    tree2["big"] = tree["big"] + 1           # the BIG leaf changes
    m.save(2, tree2)
    man2 = m.commit(2)
    base_rel = next(e["file"] for e in man2["leaves"] if e.get("reused"))
    delta_rel = next(e["file"] for e in man2["leaves"] if not e.get("reused"))
    total = store.size("shared", base_rel) + store.size("shared", delta_rel)

    store.bytes_read = 0
    out, _ = m.restore(tree, step=2)
    assert np.array_equal(out["big"], tree2["big"])
    assert np.array_equal(out["w"], tree["w"])
    # the old reader fetched base+delta in full (~2 MB); the ranged reader
    # skips the stale 1 MB "big" payload inside the base shard
    assert store.bytes_read < 0.7 * total, (store.bytes_read, total)


# ---------------------------------------------------------------------------
# streaming save: peak extra buffering bounded by one chunk
# ---------------------------------------------------------------------------

def test_streaming_save_bounded_buffering(tmp_path, rng):
    payload_mb = 32
    arr = rng.standard_normal((payload_mb * 1024 * 1024 // 4,)).astype(np.float32)
    recs = [("big", arr)]

    class NullSink(io.RawIOBase):
        def writable(self):
            return True

        def write(self, b):
            return len(b)

    tracemalloc.start()
    tracemalloc.reset_peak()
    SER.write_shard_stream(NullSink(), recs)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # legacy path buffered ~2x the payload (tobytes + BytesIO); streaming
    # must stay under one chunk (+ slack for the footer/index objects)
    assert peak < SER.CHUNK_BYTES + (1 << 20), f"peak={peak}"


# ---------------------------------------------------------------------------
# elastic GC: retired steps written under a different worker count
# ---------------------------------------------------------------------------

def test_gc_cleans_parts_from_different_worker_count(tmp_path, rng):
    store = TieredStore(tmp_path)
    tree = _tree(rng)
    # step 1 written by THREE workers
    for w in range(3):
        mw = CheckpointManager(store, CheckpointPolicy(incremental=True, keep_last=2), worker_id=w,
                               num_workers=3)
        mw.save(1, tree)
    m3 = CheckpointManager(store, CheckpointPolicy(incremental=True, keep_last=2), worker_id=0,
                           num_workers=3)
    m3.commit(1, num_workers=3)
    # elastic restart: ONE worker continues incrementally, reusing step-1 files
    m1 = CheckpointManager(store, CheckpointPolicy(incremental=True, keep_last=2), worker_id=0,
                           num_workers=1)
    m1.restore(tree)
    for s in (2, 3, 4):
        t = dict(tree)
        t["step"] = np.int32(s)
        m1.save(s, t)
        man = m1.commit(s)
    assert any(e.get("reused") for e in man["leaves"])   # still referencing base
    assert m1.steps() == [3, 4]
    # step 1 was retired while referenced: its manifest AND all 3 wpart files
    # (written under num_workers=3) must be gone, shard data kept
    sdir = "ckpt/step_0000000001"
    leftovers = [r for r in store.list_prefix("shared", sdir)
                 if Path(r).name.startswith(("wpart_", "MANIFEST"))]
    assert leftovers == [], leftovers
    assert any(Path(r).name.startswith("shard_")
               for r in store.list_prefix("shared", sdir))
    # and the referenced base leaves still restore
    out, _ = m1.restore(tree, step=4)
    assert np.array_equal(out["w"], tree["w"])


# ---------------------------------------------------------------------------
# async writer pool still serializes correctly under overlap
# ---------------------------------------------------------------------------

def test_async_pool_save_commit_restore(tmp_path, rng):
    store = TieredStore(tmp_path)
    m = CheckpointManager(store, CheckpointPolicy(mode="async", keep_last=10))
    tree = _tree(rng)
    for s in (1, 2, 3):
        t = dict(tree)
        t["step"] = np.int32(s)
        m.save(s, t)
        m.commit(s)
    out, man = m.restore(tree)
    assert man["step"] == 3
    assert int(out["step"]) == 3
    m.close()

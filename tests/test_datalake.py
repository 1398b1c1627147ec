"""Data-lake behaviour: versioning, filesets, sessions, metadata, provenance."""
import hashlib

import numpy as np
import pytest

from repro.core.datalake import storage as S
from repro.core.datalake.fileset import FileSetManager
from repro.core.datalake.metadata import MetadataStore
from repro.core.datalake.provenance import ProvenanceGraph
from repro.core.datalake.storage import DataLakeError, Storage


@pytest.fixture
def lake(tmp_path):
    storage = Storage(tmp_path)
    prov = ProvenanceGraph(tmp_path)
    fs = FileSetManager(storage, prov)
    meta = MetadataStore(tmp_path)
    return storage, fs, prov, meta


def test_versioning_sequential_no_gaps(lake):
    storage, *_ = lake
    for i in range(3):
        fv = storage.upload("/data/train.json", f"v{i}".encode())
        assert fv.version == i + 1
    assert storage.versions("/data/train.json") == [1, 2, 3]
    assert storage.download("/data/train.json") == b"v2"
    assert storage.download("/data/train.json@1") == b"v0"


def test_versions_immutable_content_addressed(lake):
    storage, *_ = lake
    storage.upload("/a", b"hello")
    storage.upload("/a", b"world")
    assert storage.download("/a@1") == b"hello"   # old version intact


def test_upload_session_transactional(lake):
    storage, *_ = lake
    sid = storage.begin_session(["/x", "/y"])
    storage.session_put(sid, "/x", b"1")
    with pytest.raises(DataLakeError):
        storage.commit_session(sid)           # /y missing -> no commit
    # failed commit must not burn version numbers
    assert storage.versions("/x") == []
    storage.session_put(sid, "/y", b"2")
    fvs = storage.commit_session(sid)
    assert sorted(f.version for f in fvs) == [1, 1]
    assert storage.session_state(sid) == "committed"


def test_session_abort(lake):
    storage, *_ = lake
    sid = storage.begin_session(["/z"])
    storage.session_put(sid, "/z", b"zz")
    storage.abort_session(sid)
    assert storage.session_state(sid) == "aborted"
    assert not storage.exists("/z")
    with pytest.raises(DataLakeError):
        storage.session_put(sid, "/z", b"again")


def test_session_survives_reload(tmp_path):
    s1 = Storage(tmp_path)
    sid = s1.begin_session(["/p"])
    s1.session_put(sid, "/p", b"data")
    # crash + restart: session state persisted, client free to continue
    s2 = Storage(tmp_path)
    assert s2.session_state(sid) == "pending"
    fvs = s2.commit_session(sid)
    assert fvs[0].version == 1


def test_fileset_merge_update_subset(lake):
    storage, fs, prov, _ = lake
    storage.upload("/data/train.json", b"t1")
    storage.upload("/data/dev.json", b"d1")
    storage.upload("/validation/val.json", b"v1")
    fs.create("HotpotQA", ["/data/train.json", "/validation/val.json"])
    fs.create("ColdpotQA", ["/data/dev.json"])
    # merging (paper example 1)
    merged = fs.merge("MergedQA", ["HotpotQA", "ColdpotQA"])
    assert set(merged.files) == {"/data/train.json", "/validation/val.json",
                                 "/data/dev.json"}
    # updating (paper example 2): new version of the file replaces old ref
    storage.upload("/data/train.json", b"t2")
    updated = fs.update("HotpotQA", ["/data/train.json"])
    assert updated.version == 2
    assert updated.files["/data/train.json"] == 2
    # old set version still pins the old file version
    assert fs.resolve("HotpotQA:1").files["/data/train.json"] == 1
    # subsetting (paper example 3)
    sub = fs.subset("HotpotQAValidationSet", "HotpotQA:1", "/validation/")
    assert set(sub.files) == {"/validation/val.json"}
    # dependencies recorded in provenance
    assert ("HotpotQA:1", {"action": "fileset_creation", "creator": ""}) in \
        prov.backward("HotpotQAValidationSet:1")


def test_fileset_file_at_set_version(lake):
    storage, fs, _, _ = lake
    storage.upload("/data/train.json", b"t1")
    fs.create("S", ["/data/train.json"])
    storage.upload("/data/train.json", b"t2")
    # '/data/train.json@S:1' resolves via the set
    got, _ = fs._expand_spec("/data/train.json@S:1")
    assert got == {"/data/train.json": 1}


def test_fileset_single_version_per_file(lake):
    storage, fs, _, _ = lake
    storage.upload("/a", b"1")
    storage.upload("/a", b"2")
    fsv = fs.create("S", ["/a@1", "/a@2"])
    # later spec wins; a set never holds two versions of one file
    assert fsv.files == {"/a": 2}


def test_materialize_unversioned(lake, tmp_path):
    storage, fs, _, _ = lake
    storage.upload("/data/train.json", b"payload")
    fs.create("S", ["/data/train.json"])
    out = fs.materialize("S", tmp_path / "job")
    assert len(out) == 1
    assert (tmp_path / "job/data/train.json").read_bytes() == b"payload"


def test_metadata_queries(lake):
    *_, meta = lake
    meta.register("job-1", kind="job", creator="john", model="BERT",
                  precision=0.7)
    meta.register("job-2", kind="job", creator="john", model="BERT",
                  precision=0.4)
    meta.register("job-3", kind="job", creator="mary", model="GPT",
                  precision=0.9)
    # the paper's exemplar query: john's BERT jobs with precision > 0.5
    hits = meta.find(creator="john", model="BERT", precision=(">", 0.5))
    assert hits == ["job-1"]
    assert meta.find_max("precision", kind="job") == "job-3"
    assert meta.find_min("precision", creator="john") == "job-2"
    rng = meta.find(precision=("range", 0.35, 0.75))
    assert rng == ["job-1", "job-2"]


def test_metadata_tags_and_reload(tmp_path):
    meta = MetadataStore(tmp_path)
    meta.register("f-1", kind="file")
    meta.tag("f-1", "best")
    meta2 = MetadataStore(tmp_path)
    assert meta2.find(tags=["best"]) == ["f-1"]


def test_provenance_dag_traversal(lake):
    _, _, prov, _ = lake
    prov.add_fileset("raw:1")
    prov.add_job_edge(src="raw:1", dst="features:1", job_id="job-etl")
    prov.add_job_edge(src="features:1", dst="model:1", job_id="job-train")
    assert prov.forward("raw:1")[0][0] == "features:1"
    assert prov.backward("model:1")[0][0] == "features:1"
    assert prov.ancestors("model:1") == ["features:1", "raw:1"]
    assert prov.lineage_jobs("model:1") == ["job-etl", "job-train"]
    assert prov.replay_order("model:1")[0] == "raw:1"
    assert prov.is_dag()


# -- the streamed blob write ----------------------------------------------
PIECE = 4096      # small pieces, so a few KB take the threaded path


def _payload(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _chunkings(data: bytes) -> dict:
    arr = np.frombuffer(data, np.uint8)
    return {
        "one": [data],
        "many uneven": [data[:5], data[5:PIECE + 7], data[PIECE + 7:]],
        "array views": [arr[:3 * PIECE], memoryview(arr[3 * PIECE:])],
        "float leaves": [np.frombuffer(data, np.float32).view(np.uint8)],
    }


def _stray_tmp(storage):
    return list(storage.blob_dir.glob("*.tmp-*"))


@pytest.mark.parametrize("size", [100, 10 * PIECE + 12])
@pytest.mark.parametrize("chunking", ["one", "many uneven", "array views",
                                      "float leaves"])
def test_streamed_blob_is_named_by_the_sha256_of_its_bytes(
        tmp_path, monkeypatch, size, chunking):
    monkeypatch.setattr(S, "PIECE_BYTES", PIECE)
    storage = Storage(tmp_path)
    data = _payload(size - size % 4)
    sid = storage.begin_session(["/f"])
    put = storage.session_put_stream(sid, "/f", _chunkings(data)[chunking])
    fv, = storage.commit_session(sid)
    blob = storage.blob_dir / put.blob
    assert blob.read_bytes() == data
    assert put.blob == fv.blob == hashlib.sha256(data).hexdigest()
    assert put.size == fv.size == len(data)
    assert put.hash_wait_s >= 0
    assert storage.download("/f") == data
    assert _stray_tmp(storage) == []


def test_streamed_blob_at_the_real_piece_size(tmp_path):
    storage = Storage(tmp_path)
    data = _payload(2 * S.PIECE_BYTES + 5)
    sid = storage.begin_session(["/big"])
    put = storage.session_put_stream(
        sid, "/big", [data[:S.PIECE_BYTES + 1], data[S.PIECE_BYTES + 1:]])
    storage.commit_session(sid)
    assert put.blob == hashlib.sha256(data).hexdigest()
    assert (storage.blob_dir / put.blob).read_bytes() == data


@pytest.mark.parametrize("size", [100, 10 * PIECE])
def test_identical_payloads_leave_one_blob(tmp_path, monkeypatch, size):
    monkeypatch.setattr(S, "PIECE_BYTES", PIECE)
    storage = Storage(tmp_path)
    data = _payload(size)
    storage.upload("/a", data)
    sid = storage.begin_session(["/b"])
    storage.session_put_stream(sid, "/b", [data[:7], data[7:]])
    storage.commit_session(sid)
    assert [p.name for p in storage.blob_dir.iterdir()] == \
        [hashlib.sha256(data).hexdigest()]
    assert storage.resolve("/a").blob == storage.resolve("/b").blob


@pytest.mark.parametrize("fail_after", [1, 6])
def test_a_stream_that_raises_leaves_no_blob(tmp_path, monkeypatch,
                                             fail_after):
    """Raising after one small chunk (before any write) or after six
    pieces (the hashing thread running, the file half written)."""
    monkeypatch.setattr(S, "PIECE_BYTES", PIECE)
    storage = Storage(tmp_path)
    data = _payload(PIECE)

    def chunks():
        for _ in range(fail_after):
            yield data[:PIECE // 2] if fail_after == 1 else data
        raise OSError("source lost")

    sid = storage.begin_session(["/f"])
    with pytest.raises(OSError, match="source lost"):
        storage.session_put_stream(sid, "/f", chunks())
    assert list(storage.blob_dir.iterdir()) == []
    assert storage.session_state(sid) == "pending"
    with pytest.raises(DataLakeError, match="incomplete"):
        storage.commit_session(sid)
    assert not storage.exists("/f")


def test_only_a_payload_of_a_piece_or_more_starts_a_thread(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(S, "PIECE_BYTES", PIECE)
    started = []

    class Thread(S.threading.Thread):
        def start(self):
            started.append(self.name)
            super().start()

    monkeypatch.setattr(S.threading, "Thread", Thread)
    storage = Storage(tmp_path)
    storage.upload("/small", _payload(PIECE - 1))
    sid = storage.begin_session(["/parts"])
    storage.session_put_stream(sid, "/parts", [b"x"] * (PIECE - 1))
    storage.commit_session(sid)
    assert started == []
    storage.upload("/large", _payload(PIECE))
    assert started == ["lake-hash"]

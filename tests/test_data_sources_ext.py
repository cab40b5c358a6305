"""Datasource breadth: webdataset shards, gated Mongo/BigQuery, ray:// client.

Reference counterparts: ``python/ray/data/datasource/webdataset_datasource.py``,
``mongo_datasource.py``, ``bigquery_datasource.py``; ``ray://`` client mode
(``python/ray/util/client/``).
"""

import json
import tarfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rdata


def _make_shard(path, n=4):
    with tarfile.open(path, "w") as tf:
        for i in range(n):
            for ext, payload in (
                ("txt", f"caption {i}".encode()),
                ("cls", str(i % 2).encode()),
                ("json", json.dumps({"idx": i}).encode()),
            ):
                import io

                info = tarfile.TarInfo(name=f"sample{i:04d}.{ext}")
                info.size = len(payload)
                tf.addfile(info, io.BytesIO(payload))


class TestWebDataset:
    def test_read_samples(self, ray_start_regular, tmp_path):
        shard = str(tmp_path / "data-0000.tar")
        _make_shard(shard, n=4)
        ds = rdata.read_webdataset(shard)
        rows = ds.take_all()
        assert len(rows) == 4
        assert rows[0]["txt"] == "caption 0"
        assert rows[0]["cls"] in (0, 1)
        assert rows[1]["json"]["idx"] == 1
        assert rows[2]["__key__"] == "sample0002"

    def test_multiple_shards_parallel(self, ray_start_regular, tmp_path):
        for i in range(3):
            _make_shard(str(tmp_path / f"data-{i:04d}.tar"), n=2)
        ds = rdata.read_webdataset(str(tmp_path / "data-*.tar"), parallelism=3)
        assert ds.count() == 6

    def test_no_decode(self, ray_start_regular, tmp_path):
        shard = str(tmp_path / "raw.tar")
        _make_shard(shard, n=1)
        rows = rdata.read_webdataset(shard, decode=False).take_all()
        assert rows[0]["txt"] == b"caption 0"


class TestGatedSources:
    def test_mongo_requires_pymongo(self):
        pytest.importorskip("ray_tpu")
        try:
            import pymongo  # noqa: F401

            pytest.skip("pymongo installed; gating not exercised")
        except ImportError:
            pass
        with pytest.raises(ImportError, match="pymongo"):
            rdata.read_mongo("mongodb://x", "db", "coll")

    def test_bigquery_requires_client(self):
        try:
            from google.cloud import bigquery  # noqa: F401

            pytest.skip("bigquery installed; gating not exercised")
        except ImportError:
            pass
        with pytest.raises(ImportError, match="bigquery"):
            rdata.read_bigquery("proj", query="select 1")


class TestRayClientScheme:
    def test_ray_scheme_attaches_over_tcp(self):
        """ray://host:port behaves as client mode against a live head."""
        from conftest import tcp_head_child

        # head in a separate process serving TCP
        with tcp_head_child() as addr:
            ray_tpu.init(address=f"ray://{addr}")
            try:

                @ray_tpu.remote
                def f(x):
                    return x * 7

                assert ray_tpu.get(f.remote(6), timeout=60) == 42
            finally:
                ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# round 5: long-tail sources (datasource_ext.py — VERDICT r4 #9)
# ---------------------------------------------------------------------------


def _zigzag(n: int) -> bytes:
    """Independent avro varint encoder for the reader round-trip (written
    from the spec, not from the module under test)."""
    u = (n << 1) ^ (n >> 63) if n < 0 else n << 1
    out = bytearray()
    while True:
        b = u & 0x7F
        u >>= 7
        if u:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _avro_file(rows, deflate=False) -> bytes:
    """Minimal OCF writer for {"a": long, "b": string} records."""
    import zlib

    schema = {
        "type": "record",
        "name": "R",
        "fields": [{"name": "a", "type": "long"}, {"name": "b", "type": "string"}],
    }
    sj = json.dumps(schema).encode()
    codec = b"deflate" if deflate else b"null"
    sync = b"S" * 16
    head = b"Obj\x01"
    head += _zigzag(2)  # metadata map: 2 entries
    head += _zigzag(len(b"avro.schema")) + b"avro.schema" + _zigzag(len(sj)) + sj
    head += _zigzag(len(b"avro.codec")) + b"avro.codec" + _zigzag(len(codec)) + codec
    head += _zigzag(0) + sync
    payload = b""
    for r in rows:
        b = r["b"].encode()
        payload += _zigzag(r["a"]) + _zigzag(len(b)) + b
    if deflate:
        comp = zlib.compressobj(wbits=-15)
        payload = comp.compress(payload) + comp.flush()
    return head + _zigzag(len(rows)) + _zigzag(len(payload)) + payload + sync


@pytest.mark.parametrize("deflate", [False, True])
def test_read_avro_roundtrip(ray_start_regular, tmp_path, deflate):
    rows = [{"a": i * 7 - 3, "b": f"row-{i}"} for i in range(20)]
    p = tmp_path / "data.avro"
    p.write_bytes(_avro_file(rows, deflate=deflate))
    out = rdata.read_avro(str(p)).take_all()
    assert out == rows


def test_read_orc_roundtrip(ray_start_regular, tmp_path):
    import pyarrow as pa
    from pyarrow import orc

    table = pa.table({"x": list(range(10)), "y": [f"s{i}" for i in range(10)]})
    p = tmp_path / "data.orc"
    orc.write_table(table, str(p))
    out = rdata.read_orc(str(p)).take_all()
    assert [r["x"] for r in out] == list(range(10))
    sub = rdata.read_orc(str(p), columns=["y"]).take_all()
    assert set(sub[0]) == {"y"}


def test_read_feather_roundtrip(ray_start_regular, tmp_path):
    import pyarrow as pa
    import pyarrow.feather as feather

    table = pa.table({"v": [1.5, 2.5, 3.5]})
    p = tmp_path / "data.feather"
    feather.write_feather(table, str(p))
    out = rdata.read_feather(str(p)).take_all()
    assert [r["v"] for r in out] == [1.5, 2.5, 3.5]


def test_read_audio_wav(ray_start_regular, tmp_path):
    import wave

    import numpy as np

    p = tmp_path / "tone.wav"
    samples = (np.sin(np.linspace(0, 440, 8000)) * 32767).astype(np.int16)
    with wave.open(str(p), "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(8000)
        w.writeframes(samples.tobytes())
    out = rdata.read_audio(str(p)).take_all()
    assert out[0]["sample_rate"] == 8000
    assert out[0]["amplitude"].shape == (8000, 1)
    assert out[0]["amplitude"][:100, 0].tolist() == samples[:100].tolist()


def test_read_xml(ray_start_regular, tmp_path):
    p = tmp_path / "rows.xml"
    p.write_text(
        "<root><item id='1'><name>ann</name><age>30</age></item>"
        "<item id='2'><name>bo</name><age>40</age></item></root>"
    )
    out = rdata.read_xml(str(p), record_tag="item").take_all()
    assert out == [
        {"id": "1", "name": "ann", "age": "30"},
        {"id": "2", "name": "bo", "age": "40"},
    ]


def test_read_delta_log_replay(ray_start_regular, tmp_path):
    import pyarrow as pa
    from pyarrow import parquet as pq

    # build a delta table by hand: v0 adds two files, v1 removes one and
    # adds a third -> live set is files 1 and 2
    for i in range(3):
        pq.write_table(pa.table({"v": [i * 10, i * 10 + 1]}), str(tmp_path / f"part-{i}.parquet"))
    log = tmp_path / "_delta_log"
    log.mkdir()
    (log / "00000000000000000000.json").write_text(
        json.dumps({"add": {"path": "part-0.parquet"}}) + "\n"
        + json.dumps({"add": {"path": "part-1.parquet"}}) + "\n"
    )
    (log / "00000000000000000001.json").write_text(
        json.dumps({"remove": {"path": "part-0.parquet"}}) + "\n"
        + json.dumps({"add": {"path": "part-2.parquet"}}) + "\n"
    )
    out = sorted(r["v"] for r in rdata.read_delta(str(tmp_path)).take_all())
    assert out == [10, 11, 20, 21]


def test_read_clickhouse_fake_transport(ray_start_regular):
    def transport(url, body):
        # runs inside the read worker: assert THERE (a driver-side list
        # would never see the worker's append)
        q = body.decode()
        assert "FORMAT JSONEachRow" in q and q.count("FORMAT") == 1, q
        assert url == "http://ch:8123"
        return b'{"a": 1, "b": "x"}\n{"a": 2, "b": "y"}\n'

    out = rdata.read_clickhouse(
        "http://ch:8123", "SELECT a, b FROM t;", transport=transport
    ).take_all()
    assert out == [{"a": 1, "b": "x"}, {"a": 2, "b": "y"}]


def test_read_databricks_fake_transport(ray_start_regular):
    def transport(url, body, headers):
        assert headers["Authorization"] == "Bearer tok"
        assert "/api/2.0/sql/statements" in url
        return json.dumps(
            {
                "status": {"state": "SUCCEEDED"},
                "manifest": {"schema": {"columns": [{"name": "id"}, {"name": "v"}]}},
                "result": {"data_array": [[1, "a"], [2, "b"]]},
            }
        ).encode()

    out = rdata.read_databricks_tables(
        host="https://dbx", token="tok", warehouse_id="w1",
        query="SELECT * FROM t", transport=transport,
    ).take_all()
    assert out == [{"id": 1, "v": "a"}, {"id": 2, "v": "b"}]


def test_read_snowflake_dbapi_factory(ray_start_regular, tmp_path):
    import sqlite3

    db = str(tmp_path / "sf.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE t (id INTEGER, name TEXT)")
    conn.executemany("INSERT INTO t VALUES (?, ?)", [(i, f"n{i}") for i in range(6)])
    conn.commit()
    conn.close()
    out = rdata.read_snowflake(
        "SELECT id, name FROM t",
        connection_factory=lambda: sqlite3.connect(db),
    ).take_all()
    assert sorted(r["id"] for r in out) == list(range(6))


def test_gated_sources_error_clearly(ray_start_regular):
    for fn, args, kwargs in [
        (rdata.read_lance, ("/nope",), {}),
        (rdata.read_iceberg, ("db.t",), {}),
        (rdata.read_hudi, ("/nope",), {}),
        (rdata.read_snowflake, ("q",), {"connection_parameters": {"user": "u"}}),
    ]:
        with pytest.raises(ImportError) as e:
            fn(*args, **kwargs)
        assert "not installed" in str(e.value)


def test_read_parquet_bulk_alias(ray_start_regular, tmp_path):
    import pyarrow as pa
    from pyarrow import parquet as pq

    for i in range(4):
        pq.write_table(pa.table({"v": [i]}), str(tmp_path / f"f{i}.parquet"))
    out = sorted(r["v"] for r in rdata.read_parquet_bulk(str(tmp_path)).take_all())
    assert out == [0, 1, 2, 3]

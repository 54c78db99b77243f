"""The broker merges the servers' columnar group tables as columns
(`cluster/broker.Broker._merge` -> `engine/combine.combine_group_arrays`),
and falls back to the dict merge where that returns None.

Two servers hold segments whose key sets overlap in part, so a group's
state comes from one server or from both: SUM / COUNT add, MIN / MAX take
the extreme, AVG and MINMAXRANGE finalize from two components (`fin_tags`
"div" and "sub"), each against NumPy over all the rows.
"""

from __future__ import annotations

import tempfile
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                               ServerInstance)
from pinot_tpu.engine import ir
from pinot_tpu.engine.results import GroupArrays
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.spi.data_types import Schema

ST = Schema.build("bcm", dimensions=[("k", "INT"), ("s", "STRING")],
                  metrics=[("v", "INT")])
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
ROWS, SEGMENTS = 500, 4


def _columns(i: int) -> dict:
    # segment i holds keys [12 i, 12 i + 20): neighbours share eight keys
    rng = np.random.default_rng(30 + i)
    k = rng.integers(12 * i, 12 * i + 20, ROWS).astype(np.int32)
    return {"k": k, "s": np.asarray([f"s{x // 4:02d}" for x in k], dtype=object),
            "v": rng.integers(-1000, 1000, ROWS).astype(np.int32)}


@pytest.fixture(scope="module")
def cluster():
    d = Path(tempfile.mkdtemp(prefix="bcm_"))
    store = PropertyStore()
    controller = ClusterController(store)
    servers = [ServerInstance(store, f"Server_{i}", backend="tpu")
               for i in range(2)]
    for s in servers:
        s.start()
    controller.add_schema(ST.to_json())
    t = controller.create_table({"tableName": "bcm", "replication": 1})
    for i in range(SEGMENTS):
        SegmentBuilder(ST, segment_name=f"bcm_{i}").build(
            _columns(i), d / f"bcm_{i}")
        controller.add_segment(t, f"bcm_{i}", {"location": str(d / f"bcm_{i}"),
                                               "numDocs": ROWS})
    broker = Broker(store)
    merged = []  # (the servers' intermediates, the merge's result)
    inner = broker._merge

    def spy(query, per_server):
        # `key_space` set: the composite group id overflows in the BROKER's
        # merge alone (the servers have answered by then)
        with mock.patch.object(ir, "SPARSE_KEY_SPACE",
                               spy.key_space or ir.SPARSE_KEY_SPACE):
            merged.append((per_server, inner(query, per_server)))
        return merged[-1][1]

    spy.key_space = 0
    broker._merge = spy
    yield broker, merged
    for s in servers:
        s.stop()


def _want(key: str) -> dict:
    cols = [_columns(i) for i in range(SEGMENTS)]
    k = np.concatenate([c[key] for c in cols])
    v = np.concatenate([c["v"] for c in cols]).astype(np.int64)
    return {g: v[k == g] for g in np.unique(k)}


AGGS = "SUM(v), MIN(v), MAX(v), AVG(v), COUNT(*), MINMAXRANGE(v)"


def _row(g, vs):
    return (g, float(vs.sum()), float(vs.min()), float(vs.max()),
            float(vs.sum()) / len(vs), len(vs), float(vs.max() - vs.min()))


@pytest.mark.parametrize("key,tail,fallback", [
    pytest.param("k", "ORDER BY k LIMIT 1000", False, id="int-key-by-key"),
    pytest.param("k", "ORDER BY SUM(v) DESC, k LIMIT 5", False,
                 id="int-key-top-5"),
    pytest.param("s", "ORDER BY s LIMIT 1000", False, id="string-key"),
    pytest.param("k", "ORDER BY k LIMIT 1000", True,
                 id="int-key-None-falls-back-to-the-dict-merge"),
])
def test_two_servers_tables_merge_to_numpys_answer(cluster, monkeypatch, key,
                                                   tail, fallback):
    broker, merged = cluster
    # a composite id at or above the key space: combine_group_arrays gives
    # up (None) and the broker merges dicts of groups
    monkeypatch.setattr(broker._merge, "key_space", 4 if fallback else 0)
    del merged[:]
    resp = broker.execute_sql(
        NOCACHE + f"SELECT {key}, {AGGS} FROM bcm GROUP BY {key} {tail}")
    assert not resp.exceptions, resp.exceptions
    assert resp.num_servers_queried == 2
    (per_server, out), = merged
    assert len(per_server) == 2
    assert all(isinstance(r, GroupArrays) for r in per_server)
    # the servers' key sets differ, and overlap
    sets = [set(r.key_cols[0].tolist()) for r in per_server]
    assert sets[0] - sets[1] and sets[1] - sets[0] and sets[0] & sets[1]
    assert isinstance(out, GroupArrays) != fallback
    want = [_row(g, vs) for g, vs in _want(key).items()]
    if "DESC" in tail:
        want = sorted(want, key=lambda r: (-r[1], r[0]))[:5]
    got = [tuple(r) for r in resp.result_table.rows]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[5] == w[5]
        assert g[1:5] + g[6:] == pytest.approx(w[1:5] + w[6:], rel=1e-12)

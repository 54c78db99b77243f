"""Two-way rolling-upgrade verifier.

Reference analogue: compatibility-verifier/compCheck.sh + its README —
build two git revisions and verify artifacts written by each are readable
by the other, plus a live mixed-version cluster. Here:

  OLD→NEW  the previous round's code (git worktree of OLD_REV) builds a
           segment, DataTable blobs, and serialized MSE plan stages; the
           CURRENT code reads all three and re-derives identical results.
  NEW→OLD  current code writes the same artifact set; the OLD code reads.
  MIXED    an OLD-code server process joins a NEW-code cluster through
           the networked property store and serves segments for a
           NEW-code broker's scatter/gather — the live wire protocol.

The OLD revision floats forward each round (it is "the previous release"),
unlike tests/golden/ whose committed bytes pin the oldest supported
format.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
# Round-4 final commit — the "previous release" for this round.
OLD_REV = "7104746"

# The version-portable writer/reader. Runs under BOTH revisions, so only
# APIs that exist in OLD_REV may appear here.
CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PINOT_TPU_DISABLE_NATIVE"] = "1"
import numpy as np

mode, art = sys.argv[1], sys.argv[2]

from pinot_tpu.cluster import datatable as dtmod
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.engine.reduce import BrokerReducer
from pinot_tpu.mse.fragmenter import fragment
from pinot_tpu.mse.logical import LogicalPlanner
from pinot_tpu.mse.parser import parse_relational
from pinot_tpu.mse.plan_serde import stage_from_json, stage_to_json
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

SCHEMA = Schema.build(
    "up",
    dimensions=[("s", "STRING"), ("i", "INT")],
    metrics=[("m", "INT"), ("d", "DOUBLE")])

AGG_SQL = "SELECT SUM(m), COUNT(*), DISTINCTCOUNT(s) FROM up WHERE i < 40"
GRP_SQL = "SELECT s, SUM(m), AVG(d) FROM up GROUP BY s ORDER BY s LIMIT 50"
MSE_SQL = ("SELECT a.s, SUM(a.m) FROM up a JOIN up b ON a.i = b.i "
           "GROUP BY a.s LIMIT 50")


def rows_of(resp):
    return [[v if not isinstance(v, float) else round(v, 6) for v in r]
            for r in resp.result_table.rows]


def build_data():
    rng = np.random.default_rng(42)
    n = 500
    return {
        "s": np.asarray(["a", "b", "c", "d"], dtype=object)[
            rng.integers(0, 4, n)],
        "i": rng.integers(0, 60, n).astype(np.int32),
        "m": rng.integers(-100, 1000, n).astype(np.int32),
        "d": np.round(rng.random(n) * 10, 4),
    }


if mode == "write":
    out = {}
    cols = build_data()
    SegmentBuilder(SCHEMA, segment_name="up0").build(cols, art + "/segment")
    seg = load_segment(art + "/segment")
    qe = QueryExecutor(backend="host")
    qe.add_table(SCHEMA, [seg])
    for tag, sql in (("agg", AGG_SQL), ("grp", GRP_SQL)):
        combined, stats = qe.execute_segments(parse_sql(sql), [seg])
        blob = dtmod.encode(combined, stats)
        open(f"{art}/dt_{tag}.bin", "wb").write(blob)
        out[f"rows_{tag}"] = rows_of(qe.execute_sql(sql))
    q = parse_relational(MSE_SQL)
    plan = LogicalPlanner(q, {"up": SCHEMA.column_names()}).plan()
    stages = fragment(plan)
    json.dump([stage_to_json(st) for st in stages],
              open(art + "/plan.json", "w"))
    out["num_stages"] = len(stages)
    json.dump(out, open(art + "/expect.json", "w"))
    print("WRITE OK")
elif mode == "read":
    expect = json.load(open(art + "/expect.json"))
    seg = load_segment(art + "/segment")
    assert seg.num_docs == 500, seg.num_docs
    qe = QueryExecutor(backend="host")
    qe.add_table(SCHEMA, [seg])
    for tag, sql in (("agg", AGG_SQL), ("grp", GRP_SQL)):
        got = rows_of(qe.execute_sql(sql))
        assert got == expect[f"rows_{tag}"], (tag, got, expect[f"rows_{tag}"])
        # the DataTable bytes the other version wrote must decode AND
        # broker-reduce to the same result rows
        combined, stats = dtmod.decode(open(f"{art}/dt_{tag}.bin", "rb").read())
        table = BrokerReducer(SCHEMA).reduce(parse_sql(sql), combined)
        red = [[v if not isinstance(v, float) else round(v, 6) for v in r]
               for r in table.rows]
        assert red == expect[f"rows_{tag}"], (tag, red)
    stages = [stage_from_json(d) for d in json.load(open(art + "/plan.json"))]
    assert len(stages) == expect["num_stages"]
    roundtrip = [stage_to_json(st) for st in stages]
    assert [d["stage_id"] for d in roundtrip] == \
        [d["stage_id"] for d in json.load(open(art + "/plan.json"))]
    print("READ OK")
else:
    raise SystemExit(f"unknown mode {mode}")
"""

MIXED_SERVER = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PINOT_TPU_DISABLE_NATIVE"] = "1"
from pinot_tpu.cluster.remote_store import RemoteStore
from pinot_tpu.cluster.server import ServerInstance

host, port = sys.argv[1], int(sys.argv[2])
store = RemoteStore(host, port)
server = ServerInstance(store, "OldServer_0", backend="host")
server.start()
print("SERVER UP", flush=True)
try:
    while store.get("/TEST/STOP") is None:
        time.sleep(0.05)
finally:
    server.stop()
    store.close()
"""


def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "")
    return env


def _run_child(code: str, args: list[str], pythonpath: Path, timeout=300):
    env = _clean_env()
    env["PYTHONPATH"] = str(pythonpath) + (
        os.pathsep + env["PYTHONPATH"] if env["PYTHONPATH"] else "")
    r = subprocess.run([sys.executable, "-c", code, *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=str(pythonpath))
    assert r.returncode == 0, \
        f"child failed under {pythonpath}:\n{r.stdout[-800:]}\n{r.stderr[-2000:]}"
    return r.stdout


@pytest.fixture(scope="module")
def old_checkout(tmp_path_factory):
    d = tmp_path_factory.mktemp("oldrev") / "repo"
    r = subprocess.run(
        ["git", "-C", str(REPO), "worktree", "add", "--detach", str(d),
         OLD_REV],
        capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        pytest.skip(f"cannot create {OLD_REV} worktree: {r.stderr[-300:]}")
    yield d
    subprocess.run(["git", "-C", str(REPO), "worktree", "remove", "--force",
                    str(d)], capture_output=True, timeout=120)


def test_old_writes_new_reads(old_checkout, tmp_path):
    art = tmp_path / "o2n"
    art.mkdir()
    assert "WRITE OK" in _run_child(CHILD, ["write", str(art)], old_checkout)
    assert "READ OK" in _run_child(CHILD, ["read", str(art)], REPO)


def test_new_writes_old_reads(old_checkout, tmp_path):
    art = tmp_path / "n2o"
    art.mkdir()
    assert "WRITE OK" in _run_child(CHILD, ["write", str(art)], REPO)
    assert "READ OK" in _run_child(CHILD, ["read", str(art)], old_checkout)


def test_mixed_cluster_old_server_new_broker(old_checkout, tmp_path):
    """Live wire: previous-release server process inside a current-release
    cluster (new store/controller/broker), serving real queries."""
    import numpy as np

    from pinot_tpu.cluster import Broker, ClusterController
    from pinot_tpu.cluster.remote_store import PropertyStoreServer
    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.spi.data_types import Schema

    schema = Schema.build(
        "mx", dimensions=[("g", "STRING")], metrics=[("v", "INT")])
    server_store = PropertyStoreServer()
    store = server_store.store
    controller = ClusterController(store)
    broker = Broker(store)
    controller.add_schema(schema.to_json())

    host, port = server_store.address
    env = _clean_env()
    env["PYTHONPATH"] = str(old_checkout) + (
        os.pathsep + env["PYTHONPATH"] if env["PYTHONPATH"] else "")
    child = subprocess.Popen(
        [sys.executable, "-c", MIXED_SERVER, host, str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=str(old_checkout))
    try:
        deadline = time.time() + 60
        while "OldServer_0" not in store.children("/LIVEINSTANCES"):
            assert child.poll() is None, child.stderr.read()[-2000:]
            assert time.time() < deadline, "old server never joined"
            time.sleep(0.05)

        table = controller.create_table({"tableName": "mx", "replication": 1})
        rng = np.random.default_rng(0)
        n = 400
        cols = {"g": np.asarray(["x", "y", "z"], dtype=object)[
                    rng.integers(0, 3, n)],
                "v": rng.integers(0, 100, n).astype(np.int32)}
        SegmentBuilder(schema, segment_name="mx0").build(cols, tmp_path / "mx0")
        controller.add_segment(table, "mx0",
                               {"location": str(tmp_path / "mx0"),
                                "numDocs": n})
        deadline = time.time() + 60
        while "OldServer_0" not in (
                store.get(f"/EXTERNALVIEW/{table}") or {}).get("mx0", {}):
            assert child.poll() is None, child.stderr.read()[-2000:]
            assert time.time() < deadline, "segment never online on old server"
            time.sleep(0.05)

        want = {}
        for g, v in zip(cols["g"], cols["v"]):
            want[g] = want.get(g, 0) + int(v)
        resp = broker.execute_sql(
            "SELECT g, SUM(v) FROM mx GROUP BY g LIMIT 10")
        assert not resp.exceptions, resp.exceptions
        assert {r[0]: r[1] for r in resp.result_table.rows} == want
    finally:
        store.set("/TEST/STOP", True)
        try:
            child.wait(timeout=15)
        except subprocess.TimeoutExpired:
            child.kill()
        server_store.close()
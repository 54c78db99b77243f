"""Text regex/BM25, vector recall story, pauseless completion.

Reference: native FST regex tests (pinot-segment-local/.../nativefst/),
Lucene BM25 scoring, HNSW recall expectations, and
PauselessSegmentCompletionFSM behavior.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from pinot_tpu.cluster.store import PropertyStore
from pinot_tpu.realtime.completion import SegmentCompletionManager
from pinot_tpu.realtime.manager import RealtimeTableDataManager
from pinot_tpu.segment.indexes import TextIndex, VectorIndex
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.stream import InMemoryStreamRegistry
from pinot_tpu.spi.table_config import (
    IngestionConfig,
    SegmentsValidationConfig,
    TableConfig,
    TableType,
)

DOCS = [
    "quick brown fox jumps over the lazy dog",
    "pack my box with five dozen liquor jugs",
    "the quick onyx goblin jumps over the lazy dwarf",
    "sphinx of black quartz judge my vow",
    "jackdaws love my big sphinx of quartz",
    None,
    "quickest of the quick brown foxes",
]


@pytest.fixture(scope="module")
def text_index():
    return TextIndex.build(DOCS)


def test_regex_term_matching(text_index):
    docs = text_index.docs_for_regex("qu.*")
    assert set(docs) == {0, 2, 3, 4, 6}  # quick/quartz/quickest/...
    docs = text_index.docs_for_regex("jump(s|ed)?")
    assert set(docs) == {0, 2}
    docs = text_index.docs_for_regex("j.ckd.ws")
    assert set(docs) == {4}
    assert len(text_index.docs_for_regex("zzz.*")) == 0
    # TEXT_MATCH syntax: /regex/ terms compose with the boolean operators
    mask = text_index.mask_match("/quick(est)?/ AND fox*", len(DOCS))
    assert set(np.nonzero(mask)[0]) == {0, 6}


def test_bm25_scoring(text_index):
    scores = text_index.bm25_scores("quick", len(DOCS))
    matched = {i for i in range(len(DOCS)) if scores[i] > 0}
    assert matched == {0, 2, 6}
    # doc 6 has "quick" once among 5 tokens; rarer-term docs outrank common
    sphinx = text_index.bm25_scores("sphinx quartz", len(DOCS))
    assert sphinx[3] > 0 and sphinx[4] > 0
    assert sphinx[3] > sphinx[0] == 0.0
    # phrase queries score by their terms
    ph = text_index.bm25_scores('"lazy dog"', len(DOCS))
    assert ph[0] > ph[2] > 0  # doc 0 has both terms, doc 2 only "lazy"


def test_vector_ivf_recall_story(rng):
    """The matmul+IVF design's recall contract: ≥95% recall@10 at the
    default probe width on clustered data (the HNSW-class recall story,
    achieved without pointer chasing)."""
    n, dim, n_clusters = 20_000, 64, 50
    centers = rng.normal(0, 1, (n_clusters, dim))
    data = (centers[rng.integers(0, n_clusters, n)]
            + rng.normal(0, 0.3, (n, dim))).astype(np.float32)
    idx = VectorIndex.build(data)  # nlist auto = sqrt(n)
    assert idx.centroids is not None

    norm = data / np.linalg.norm(data, axis=1, keepdims=True)
    recalls = []
    for _ in range(20):
        q = (centers[rng.integers(0, n_clusters)]
             + rng.normal(0, 0.3, dim)).astype(np.float32)
        qn = q / np.linalg.norm(q)
        exact = set(np.argsort(-(norm @ qn))[:10].tolist())
        approx, _ = idx.top_k(q, 10, nprobe=8)
        recalls.append(len(exact & set(approx.tolist())) / 10)
    assert np.mean(recalls) >= 0.95, np.mean(recalls)


# -- pauseless completion -----------------------------------------------------

SCHEMA = Schema.build(
    "ev", dimensions=[("u", "STRING"), ("ts", "LONG")], metrics=[("n", "INT")])


def _config(topic, flush_rows):
    return TableConfig(
        table_name="ev", table_type=TableType.REALTIME,
        validation=SegmentsValidationConfig(time_column_name="ts"),
        ingestion=IngestionConfig(stream_configs={
            "streamType": "inmemory",
            "stream.inmemory.topic.name": topic,
            "realtime.segment.flush.threshold.rows": flush_rows,
        }))


CEILING_S = 120.0  # bounds a hang, not a wait


def wait_until(pred, timeout=20.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_pauseless_successor_consumes_during_commit(monkeypatch, tmp_path):
    reg = InMemoryStreamRegistry()
    import pinot_tpu.spi.stream as stream_mod

    monkeypatch.setattr(stream_mod, "GLOBAL_STREAM_REGISTRY", reg)
    reg.create_topic("pl", num_partitions=1)
    store = PropertyStore()
    # no committer dies here: the lease outlasts every wait below
    completion = SegmentCompletionManager(store, num_replicas=1,
                                          commit_lease_s=4 * CEILING_S)
    # the hook is called between build and commitEnd, with the segment
    # sealed: it says so (`sealed`), and holds the commit until the test has
    # seen the successor consume (`release`). The test waits on these
    # events, not on a poll of `m._committing`, which the committer can set
    # and clear between two looks when the machine is busy. The ceilings
    # only bound a hang.
    sealed, release = threading.Event(), threading.Event()
    observed = {"overlap": False}

    def slow_commit(mgr):
        with m._lock:
            # ingestion never paused: the successor is consuming already
            observed["overlap"] = bool(m._committing and m._consuming)
        sealed.set()
        release.wait(CEILING_S)
        return False  # do not die — just slow

    m = RealtimeTableDataManager(
        SCHEMA, _config("pl", flush_rows=20), tmp_path,
        completion=completion, instance_id="A", pauseless=True,
        test_hooks={"die_before_commit_end": slow_commit})
    m.start()
    try:
        reg.publish("pl", [{"u": f"u{i}", "ts": 1_600_000_000_000 + i,
                            "n": 1} for i in range(25)])
        # while seg 0 commits (held), publish more: the successor consumes
        assert sealed.wait(CEILING_S)
        assert m._committing  # sealed, not committed
        assert observed["overlap"]  # committing + consuming coexisted
        reg.publish("pl", [{"u": f"v{i}", "ts": 1_600_000_100_000 + i,
                            "n": 1} for i in range(10)])
        assert wait_until(
            lambda: sum(s.num_docs for s in m.segments) == 35, CEILING_S)
        assert m._committing  # all 35 rows in while the commit was open
        release.set()
        assert wait_until(lambda: len(m._segment_names) >= 1, CEILING_S)
        assert wait_until(lambda: not m._committing, CEILING_S)  # landed
        # everything stays queryable, exactly once
        assert sum(s.num_docs for s in m.segments) == 35
    finally:
        release.set()
        m.stop()


def test_regex_alternation_and_case(text_index):
    # top-level alternation must not be narrowed to the first branch
    docs = text_index.docs_for_regex("fox(es)?|dog")
    assert set(docs) == {0, 6}
    # uppercase patterns match the lowercased terms
    docs = text_index.docs_for_regex("Quick.*")
    assert set(docs) == {0, 2, 6}

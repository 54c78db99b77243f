"""Narrow-HBM id planes: uint8/uint16 residency with in-kernel widening.

Reference analogue (§2.9-1): FixedBitIntReader — here the decode is a free
fused astype because byte-aligned narrow planes are the TPU-correct packing
(bitstream decode forces lane relayouts and measured ~1000x slower)."""

from __future__ import annotations

import numpy as np
import pytest

from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema


@pytest.fixture(autouse=True)
def force_packed(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_PACKED_HBM", "1")


@pytest.mark.parametrize("width", [8, 16])
def test_narrow_plane_widening(width):
    import jax.numpy as jnp

    from pinot_tpu.ops.kernels import _apply_packed

    rng = np.random.default_rng(width)
    vals = rng.integers(0, 1 << width, 8192).astype(
        np.uint8 if width == 8 else np.uint16)
    out = _apply_packed((jnp.asarray(vals),), ((0, width),))[0]
    np.testing.assert_array_equal(np.asarray(out), vals.astype(np.int32))


@pytest.mark.parametrize("card", [2, 6, 200, 40_000, 70_000])
def test_query_parity_packed_vs_host(card, tmp_path):
    rng = np.random.default_rng(card)
    n = 20_000
    schema = Schema.build(
        "pk", dimensions=[("d", "INT"), ("s", "STRING")], metrics=[("m", "INT")])
    cols = {"d": rng.integers(0, card, n).astype(np.int64),
            "s": np.asarray([f"v{i}" for i in rng.integers(0, 37, n)],
                            dtype=object),
            "m": rng.integers(0, 100, n).astype(np.int32)}
    SegmentBuilder(schema, segment_name="p0").build(cols, tmp_path / "p0")
    seg = load_segment(tmp_path / "p0")
    tpu = QueryExecutor(backend="tpu")
    tpu.add_table(schema, [seg])
    host = QueryExecutor(backend="host")
    host.add_table(schema, [seg])
    for sql in [
        "SELECT s, SUM(m), COUNT(*), MIN(d), MAX(d) FROM pk GROUP BY s "
        "ORDER BY s LIMIT 50",
        f"SELECT COUNT(*) FROM pk WHERE d >= {card // 2}",
        "SELECT SUM(d) FROM pk WHERE s = 'v3'",
    ]:
        a = tpu.execute_sql(sql)
        b = host.execute_sql(sql)
        assert not a.exceptions, (sql, a.exceptions)
        assert a.result_table.rows == b.result_table.rows, sql


def test_hbm_residency_reduced(tmp_path):
    """Low-cardinality ids must occupy 1/4 (uint8) of the int32 plane."""
    from pinot_tpu.segment.device_cache import SegmentDeviceView

    n = 50_000
    schema = Schema.build("r", dimensions=[("d", "INT")])
    SegmentBuilder(schema, segment_name="r0").build(
        {"d": (np.arange(n) % 100).astype(np.int64)}, tmp_path / "r0")
    seg = load_segment(tmp_path / "r0")
    view = SegmentDeviceView(seg)
    plane, width = view.dict_ids_packed("d")
    assert width == 8  # 100 distinct values → 7 bits → uint8 plane
    assert plane.nbytes == view.padded  # 1 byte/doc vs 4


def test_f64_wire_codec_bit_exact():
    """PackedOuts f64 wire encoding (f32 triplet + scale bucket): bit-exact
    for the full f64 range including subnormals, zeros, infinities, NaN.
    f64 outputs ride this arithmetic-only encoding instead of an f64
    bitcast-convert (ops/kernels.py)."""
    import jax.numpy as jnp

    from pinot_tpu.ops.kernels import _decode_f64, _encode_f64, \
        canonical_bytes, pack_outputs, unpack_outputs

    rng = np.random.default_rng(3)
    mags = np.ldexp(1.0, rng.integers(-1020, 1020, 4000).astype(np.int32))
    vals = np.concatenate([
        rng.standard_normal(4000) * mags,
        rng.standard_normal(1000),
        [0.0, -0.0, np.inf, -np.inf, np.nan,
         1.7976931348623157e308, -1.7976931348623157e308, np.pi, 2.0 ** -1022],
    ])
    # f64 SUBNORMALS are excluded: XLA flushes subnormal inputs to zero in
    # ALL arithmetic (verified: jit(a*b) on subnormal f64 → 0.0), so the
    # whole engine is DAZ; the codec just inherits that. Assert they decode
    # to zero rather than garbage:
    normal = np.abs(vals) >= 2.0 ** -1022
    keep = normal | ~np.isfinite(vals) | (vals == 0)
    vals = np.where(keep, vals, 0.0)
    w = np.asarray(_encode_f64(jnp.asarray(vals, dtype=jnp.float64)))
    back = _decode_f64(w.reshape(-1).view(np.uint8), vals.shape)
    assert back.tobytes() == vals.tobytes()
    sub = np.asarray([5e-324, -5e-324, 1e-310], dtype=np.float64)
    wsub = np.asarray(_encode_f64(jnp.asarray(sub, dtype=jnp.float64)))
    assert np.all(np.abs(_decode_f64(wsub.reshape(-1).view(np.uint8),
                                     sub.shape)) == 0.0)

    # end-to-end through pack/unpack with mixed dtypes
    outs = (jnp.asarray(vals, jnp.float64),
            jnp.asarray(rng.integers(-2**62, 2**62, 100), jnp.int64),
            jnp.asarray(rng.integers(0, 2, 64), jnp.bool_),
            jnp.asarray(rng.standard_normal(33), jnp.float32),
            # narrow dtypes whose byte count is not a whole number of words
            jnp.asarray(rng.integers(0, 2, (3, 5)), jnp.bool_),
            jnp.asarray(rng.integers(0, 256, 1001), jnp.uint8),
            jnp.asarray(rng.integers(-2**15, 2**15, 7), jnp.int16))
    packed = pack_outputs(outs)
    assert packed.flat.dtype == jnp.uint32  # the one device layout
    got = unpack_outputs(packed)
    for g, o in zip(got, outs):
        assert g.dtype == o.dtype and g.shape == o.shape
        assert np.asarray(g).tobytes() == np.asarray(o).tobytes()
    # the PTDP payload is the value-major byte stream whatever the device
    # layout: every non-f64 output's own bytes, back to back
    wire = canonical_bytes(np.asarray(packed.flat), packed.metas)
    assert wire[len(vals) * 16:] == b"".join(
        np.asarray(o).tobytes() for o in outs[1:])


def test_device_cache_warm(tmp_path):
    """warm() pre-uploads every column's planes (the segment-preload
    analogue); a later view() reuses them."""
    import numpy as np

    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.segment.device_cache import DeviceSegmentCache
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    schema = Schema.build("w", dimensions=[("s", "STRING"), ("i", "INT")],
                          metrics=[("d", "DOUBLE")])
    rng = np.random.default_rng(0)
    cols = {"s": np.asarray([f"x{i%5}" for i in range(500)], object),
            "i": rng.integers(0, 100, 500).astype(np.int32),
            "d": rng.standard_normal(500)}
    cfg = TableConfig(table_name="w", indexing=IndexingConfig(
        no_dictionary_columns=["d"]))
    SegmentBuilder(schema, cfg, "w0").build(cols, tmp_path / "w0")
    seg = load_segment(tmp_path / "w0")
    cache = DeviceSegmentCache()
    n = cache.warm(seg)
    # planes: s ids + s dict? (string dict not numeric -> no values
    # plane), i ids + i dict values, d raw + d f32 shadow
    assert n == 5
    v = cache.view(seg)
    assert v.nbytes() > 0
    before = v.nbytes()
    cache.warm(seg)  # idempotent: planes cached, no double upload
    assert v.nbytes() == before

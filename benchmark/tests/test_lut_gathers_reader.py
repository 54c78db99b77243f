"""`lut_gathers_per_query` as the benchmark reads it: the `span_attr` reader
over `family_dispatch` spans' `lutGathers`, as its metric file says — and
nothing from a trace whose spans do not carry the attribute (a program
from before it was added)."""

import json
import types

import pytest

from test_span_readers import HERE, _reader, _record


def _params():
    spec = json.loads(
        (HERE / "metrics" / "lut_gathers_per_query.json").read_text())
    assert spec["reader"] == "span_attr"
    return spec["params"]


def _dispatch(**attrs):
    return {"operator": "family_dispatch",
            "attributes": {"numSegments": 16, **attrs}}


def test_reads_the_mean_lut_gathers_over_traced_requests():
    params = _params()
    # the gather still runs: two LUTs a request, one dispatch each
    run = types.SimpleNamespace(records=[
        _record(0, 1, [_dispatch(lutGathers=2), {"operator": "DEVICE_FETCH"}]),
        _record(1, 2, [_dispatch(lutGathers=2)]),
        _record(2, 3, None)])
    assert _reader("span_attr").read(run, params) == pytest.approx(2.0)
    # the bit test: 0, read as 0 and not as nothing
    run = types.SimpleNamespace(records=[
        _record(0, 1, [_dispatch(lutGathers=0)]),
        _record(1, 2, [_dispatch(lutGathers=0), _dispatch(lutGathers=0)])])
    assert _reader("span_attr").read(run, params) == 0.0


def test_reads_nothing_from_spans_without_the_attribute():
    params = _params()
    run = types.SimpleNamespace(records=[
        _record(0, 1, [_dispatch(dictLookups="select:0,gather:0")]),
        _record(1, 2, None)])
    assert _reader("span_attr").read(run, params) is None

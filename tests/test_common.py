"""Tests for settings, serialization, and x-content."""

import struct

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import IllegalArgumentError, SearchEngineError
from elasticsearch_tpu.common.serialization import (
    PACK_MIN, NamedWriteable, NamedWriteableRegistry, StreamInput, StreamOutput,
)
from elasticsearch_tpu.common.settings import (
    Property, ScopedSettings, Setting, Settings, parse_byte_size, parse_time_value,
)
from elasticsearch_tpu.common import xcontent
from elasticsearch_tpu.common.xcontent import ObjectParser, XContentType
from tests import wire_reference


def test_settings_flatten_and_nest():
    s = Settings.of({"index": {"number_of_shards": 3, "refresh_interval": "1s"}})
    assert s.get("index.number_of_shards") == 3
    assert s.as_nested_dict()["index"]["refresh_interval"] == "1s"
    assert s.by_prefix("index.").get("number_of_shards") == 3


def test_typed_settings():
    shards = Setting.int_setting("index.number_of_shards", 1, Property.INDEX_SCOPE, min_value=1)
    s = Settings.of(index__number_of_shards="4")
    assert shards.get(s) == 4
    assert shards.get(Settings.EMPTY) == 1
    with pytest.raises(IllegalArgumentError):
        shards.get(Settings.of(index__number_of_shards="0"))


def test_time_and_bytes():
    assert parse_time_value("30s") == 30.0
    assert parse_time_value("500ms") == 0.5
    assert parse_time_value("-1") == -1
    assert parse_byte_size("2kb") == 2048
    assert parse_byte_size("1gb") == 1024 ** 3


def test_dynamic_settings_update():
    interval = Setting.time_setting("index.refresh_interval", "1s",
                                    Property.INDEX_SCOPE, Property.DYNAMIC)
    static = Setting.int_setting("index.number_of_shards", 1, Property.INDEX_SCOPE)
    scoped = ScopedSettings(Settings.EMPTY, [interval, static], Property.INDEX_SCOPE)
    seen = []
    scoped.add_settings_update_consumer(interval, seen.append)
    scoped.apply_settings(Settings.of({"index.refresh_interval": "5s"}))
    assert seen == [5.0]
    with pytest.raises(IllegalArgumentError):
        scoped.apply_settings(Settings.of({"index.number_of_shards": 2}))
    with pytest.raises(IllegalArgumentError):
        scoped.apply_settings(Settings.of({"bogus.key": 1}))


def test_stream_roundtrip():
    out = StreamOutput()
    out.write_vint(12345)
    out.write_zlong(-42)
    out.write_string("héllo")
    out.write_optional_string(None)
    out.write_generic({"a": [1, 2.5, True, None], "b": "x"})
    inp = StreamInput(out.bytes())
    assert inp.read_vint() == 12345
    assert inp.read_zlong() == -42
    assert inp.read_string() == "héllo"
    assert inp.read_optional_string() is None
    assert inp.read_generic() == {"a": [1, 2.5, True, None], "b": "x"}
    assert inp.remaining() == 0


def _floats(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).tolist()


_PAYLOAD_NAN = struct.unpack(">d", bytes.fromhex("7ff8000000abcdef"))[0]
_SIGNALLING_NAN = struct.unpack(">d", bytes.fromhex("7ff0000000000001"))[0]
_SPECIALS = [float("nan"), float("inf"), float("-inf"), -0.0, 0.0, 5e-324,
             -2.2250738585072009e-308, 1.7976931348623157e308, _PAYLOAD_NAN,
             _SIGNALLING_NAN, 1.0, 1 / 3]

# (case, value, lists in it that take the array path)
_GENERIC_CASES = [
    ("floats_0", [], 0),
    ("floats_1", _floats(1), 0),
    ("floats_below_threshold", _floats(PACK_MIN - 1), 0),
    ("floats_at_threshold", _floats(PACK_MIN), 1),
    ("floats_256", _floats(256), 1),
    ("floats_768", _floats(768), 1),
    ("specials", list(_SPECIALS), 1),
    ("tuple", tuple(_floats(16)), 1),
    ("tuple_short", tuple(_floats(3)), 0),
    ("one_int", _floats(9) + [7] + _floats(9), 0),
    ("one_bool", _floats(9) + [True] + _floats(9), 0),
    ("one_none", [None] + _floats(12), 0),
    ("one_np_float64", _floats(12) + [np.float64(0.25)], 0),
    ("one_nested_list", _floats(9) + [_floats(3)] + _floats(9), 0),
    ("nested_packed_list", _floats(9) + [_floats(16)] + ["x"], 1),
    ("ints", list(range(-5, 20)), 0),
    ("strings", [f"s{i}" for i in range(12)], 0),
    ("document", {"op": "index", "id": "doc-17", "seq_no": 16, "primary_term": 1,
                  "version": 1, "source": {
                      "emb": _floats(256, 1), "title": "héllo", "views": 3,
                      "tags": ["a", "b"], "geo": {"lat": 1.5, "lon": -2.5},
                      "ok": True, "none": None, "short": [0.5, 1.5],
                      "more": [_floats(8, 2), _floats(768, 3)]}}, 3),
]


@pytest.mark.parametrize("case,value,packed", _GENERIC_CASES,
                         ids=[c[0] for c in _GENERIC_CASES])
def test_generic_bytes_are_the_element_walks(case, value, packed):
    """Whatever loop writes a list, the bytes are the plain walk's, and
    `read_generic` gives the walk's values back from them."""
    expected = wire_reference.generic(value)
    out = StreamOutput()
    out.write_generic(value)
    assert out.bytes() == expected
    assert out.packed_lists == packed
    inp = StreamInput(expected)
    back = inp.read_generic()
    assert inp.remaining() == 0
    # compared as bytes: NaN is not equal to itself, and -0.0 equals 0.0
    assert wire_reference.generic(back) == expected
    if isinstance(value, (list, tuple)) and set(map(type, value)) <= {float}:
        assert type(back) is list and all(type(x) is float for x in back)


@pytest.mark.parametrize("cut", [1, 9, 9 * PACK_MIN, 9 * 255])
def test_generic_float_list_cut_short_is_refused(cut):
    data = wire_reference.generic(_floats(256))
    with pytest.raises(SearchEngineError, match="truncated"):
        StreamInput(data[:-cut]).read_generic()


class _Probe(NamedWriteable):
    def __init__(self, x):
        self.x = x

    def writeable_name(self):
        return "probe"

    def write_to(self, out):
        out.write_vint(self.x)


def test_named_writeable():
    reg = NamedWriteableRegistry()
    reg.register(_Probe, "probe", lambda inp: _Probe(inp.read_vint()))
    out = StreamOutput()
    out.write_named_writeable(_Probe(7))
    inp = StreamInput(out.bytes(), registry=reg)
    assert inp.read_named_writeable(_Probe).x == 7


def test_xcontent_json_and_cbor():
    doc = {"name": "tpu", "dims": 768, "v": [0.5, -1.25], "ok": True, "none": None}
    for ct in (XContentType.JSON, XContentType.CBOR):
        data = xcontent.dumps(doc, ct)
        assert xcontent.loads(data, ct) == doc
    assert xcontent.loads_auto(xcontent.dumps(doc, XContentType.CBOR)) == doc
    # YAML and SMILE are full codecs too (see test_xcontent_formats.py)
    for ct in (XContentType.YAML, XContentType.SMILE):
        assert xcontent.loads(xcontent.dumps(doc, ct), ct) == doc


def test_object_parser():
    class Req:
        def __init__(self):
            self.size = 10
            self.query = None

    p = ObjectParser("search", Req)
    p.declare_field("size", lambda o, v: setattr(o, "size", v))
    p.declare_field("query", lambda o, v: setattr(o, "query", v))
    r = p.parse({"size": 5, "query": {"match_all": {}}})
    assert r.size == 5 and r.query == {"match_all": {}}
    from elasticsearch_tpu.common.errors import ParsingError
    with pytest.raises(ParsingError):
        p.parse({"sizee": 5})

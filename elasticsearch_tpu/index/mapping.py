"""Mappings: field types, document parsing, dynamic mapping.

Re-design of the reference's mapper layer (`index/mapper/` — MapperService,
DocumentMapper, DocumentParser, FieldMapper subclasses; SURVEY.md §2.4).
A mapping is a tree of typed field definitions; parsing a JSON document
produces a `ParsedDocument`: analyzed terms for the inverted index, typed
values for doc-values columns, dense vectors for the device matrix, and the
stored `_source`.

Field types covered: text, keyword, long/integer/short/byte, double/float/
half_float, boolean, date, ip, geo_point, dense_vector, object, nested
(stored flattened with nested paths), plus dynamic inference for unmapped
fields (reference `DynamicTemplates`/`DocumentParser.parseDynamicValue`).

dense_vector follows `x-pack/plugin/vectors/.../DenseVectorFieldMapper.java:45`
semantics: fixed `dims`, float array values, one vector per doc — but the
2048-dim cap is lifted (the TPU path has no BinaryDocValues encoding limit)
and a `similarity` parameter selects the device metric at index time.
"""

from __future__ import annotations

import datetime as _dt
import ipaddress
import math
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.common.errors import IllegalArgumentError, MapperParsingError
from elasticsearch_tpu.index.analysis import AnalysisRegistry, DEFAULT_REGISTRY

# ---------------------------------------------------------------------------
# Parsed output containers
# ---------------------------------------------------------------------------


class ParsedDocument:
    """Everything the engine needs to index one document."""

    __slots__ = ("doc_id", "source", "terms", "term_positions", "doc_values",
                 "vectors", "field_lengths", "dynamic_updates")

    def __init__(self, doc_id: str, source: dict):
        self.doc_id = doc_id
        self.source = source
        # field -> list of terms (with duplicates, for tf)
        self.terms: Dict[str, List[str]] = {}
        # field -> term -> positions list
        self.term_positions: Dict[str, Dict[str, List[int]]] = {}
        # field -> scalar or list (kept typed: int/float/str/bool)
        self.doc_values: Dict[str, Any] = {}
        # field -> np.ndarray[dims] f32
        self.vectors: Dict[str, np.ndarray] = {}
        # field -> token count (for BM25 norms)
        self.field_lengths: Dict[str, int] = {}
        # mapping updates triggered by dynamic fields (field path -> mapper def)
        self.dynamic_updates: Dict[str, dict] = {}


# ---------------------------------------------------------------------------
# Field mappers
# ---------------------------------------------------------------------------

EPOCH = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
_DATE_PATTERNS = (
    "%Y-%m-%dT%H:%M:%S.%f%z", "%Y-%m-%dT%H:%M:%S%z", "%Y-%m-%dT%H:%M:%S.%f",
    "%Y-%m-%dT%H:%M:%S", "%Y-%m-%d %H:%M:%S", "%Y-%m-%d", "%Y/%m/%d",
)


_DATE_MATH_TOKEN = re.compile(r"([+\-/])(\d*)([yMwdhHms])")
_ROUND_SPAN_MS = {"s": 1000, "m": 60_000, "h": 3_600_000, "H": 3_600_000,
                  "d": 86_400_000, "w": 7 * 86_400_000}


def _apply_date_math(millis: int, expr: str, round_up: bool = False) -> int:
    """Date-math suffix (`||+1d/d`, `now-1h`): +/- offsets and /unit
    rounding (reference: JavaDateMathParser). `round_up` rounds to the END
    of the unit (the reference rounds up for gt/lte bounds). Malformed
    expressions raise — a typo must fail loudly, not query the wrong
    window."""
    tokens = _DATE_MATH_TOKEN.findall(expr)
    if "".join(op + num + unit for op, num, unit in tokens) \
            != expr.replace(" ", ""):
        raise MapperParsingError(
            f"failed to parse date math expression [{expr}]")
    for op, num, unit in tokens:
        if op == "/":
            if num:
                raise MapperParsingError(
                    f"rounding does not take a number [{op}{num}{unit}]")
            d = _dt.datetime.fromtimestamp(millis / 1000.0,
                                           tz=_dt.timezone.utc)
            if unit == "d":
                d = d.replace(hour=0, minute=0, second=0, microsecond=0)
            elif unit in ("h", "H"):
                d = d.replace(minute=0, second=0, microsecond=0)
            elif unit == "m":
                d = d.replace(second=0, microsecond=0)
            elif unit == "s":
                d = d.replace(microsecond=0)
            elif unit == "M":
                d = d.replace(day=1, hour=0, minute=0, second=0,
                              microsecond=0)
            elif unit == "y":
                d = d.replace(month=1, day=1, hour=0, minute=0, second=0,
                              microsecond=0)
            elif unit == "w":
                d = (d - _dt.timedelta(days=d.weekday())).replace(
                    hour=0, minute=0, second=0, microsecond=0)
            millis = int(d.timestamp() * 1000)
            if round_up:
                if unit in _ROUND_SPAN_MS:
                    millis += _ROUND_SPAN_MS[unit] - 1
                else:  # month/year: start of NEXT unit minus 1ms
                    months = 12 if unit == "y" else 1
                    millis = _shift_months(millis, months) - 1
            continue
        n = int(num or 1)
        if unit in _ROUND_SPAN_MS:
            delta = n * _ROUND_SPAN_MS[unit]
            millis += delta if op == "+" else -delta
        else:  # calendar months/years, day-clamped like the reference
            months = n * (12 if unit == "y" else 1)
            millis = _shift_months(millis, months if op == "+" else -months)
    return millis


def _shift_months(millis: int, months: int) -> int:
    import calendar
    d = _dt.datetime.fromtimestamp(millis / 1000.0, tz=_dt.timezone.utc)
    total = d.month - 1 + months
    year = d.year + total // 12
    month = total % 12 + 1
    day = min(d.day, calendar.monthrange(year, month)[1])
    return int(d.replace(year=year, month=month, day=day).timestamp() * 1000)


def parse_date_millis(value: Any, round_up: bool = False) -> int:
    """Parse a date into epoch millis (reference: DateFieldMapper,
    strict_date_optional_time||epoch_millis + date math). `round_up`
    applies to /unit rounding (gt/lte query bounds round to unit end)."""
    if isinstance(value, bool):
        raise MapperParsingError(f"cannot parse date from boolean [{value}]")
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value).strip()
    if s.startswith("now"):
        import time as _time
        return _apply_date_math(int(_time.time() * 1000), s[3:], round_up)
    if "||" in s:
        base, _, math_expr = s.partition("||")
        return _apply_date_math(parse_date_millis(base), math_expr, round_up)
    if round_up and re.fullmatch(r"\d{4}-\d{2}-\d{2}", s):
        # partial date on a gt/lte bound fills missing fields to unit END
        # (DateMathParser roundUpProperty): "2014-11-18" -> 23:59:59.999
        return parse_date_millis(s) + 86_400_000 - 1
    if re.fullmatch(r"-?\d{5,}", s):
        # epoch_millis claims any numeric string except bare 4-digit
        # years, which strict_date_optional_time parses as yyyy
        return int(s)
    norm = s.replace("Z", "+0000")
    if re.search(r"[+-]\d{2}:\d{2}$", norm):
        norm = norm[:-3] + norm[-2:]
    for pat in _DATE_PATTERNS:
        try:
            dt = _dt.datetime.strptime(norm, pat)
            if dt.tzinfo is None:
                dt = dt.replace(tzinfo=_dt.timezone.utc)
            return int(dt.timestamp() * 1000)
        except ValueError:
            continue
    raise MapperParsingError(f"failed to parse date field [{value}]")


class FieldMapper:
    type_name = "base"

    def __init__(self, name: str, params: Optional[dict] = None):
        self.name = name
        self.params = dict(params or {})

    # returns list of index terms; default: none
    def index_terms(self, value: Any) -> List[str]:
        return []

    # returns the doc-values representation (comparable/sortable), or None
    def doc_value(self, value: Any) -> Any:
        return None

    def to_def(self) -> dict:
        d = {"type": self.type_name}
        d.update(self.params)
        return d


class KeywordFieldMapper(FieldMapper):
    type_name = "keyword"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.ignore_above = self.params.get("ignore_above")

    def index_terms(self, value):
        s = str(value)
        if self.ignore_above is not None and len(s) > self.ignore_above:
            return []
        return [s]

    def doc_value(self, value):
        return str(value)


class TextFieldMapper(FieldMapper):
    type_name = "text"

    def __init__(self, name, params=None, registry: AnalysisRegistry = DEFAULT_REGISTRY):
        super().__init__(name, params)
        self.analyzer = registry.get(self.params.get("analyzer", "standard"))
        self.search_analyzer = registry.get(
            self.params.get("search_analyzer", self.params.get("analyzer", "standard")))

    def analyze(self, value) -> List[str]:
        return self.analyzer.terms(str(value))

    def analyze_positions(self, value):
        return self.analyzer.analyze(str(value))

    def index_terms(self, value):
        return self.analyze(value)

    def doc_value(self, value):
        return None  # text has no doc_values (reference: fielddata disabled by default)


class _NumericMapper(FieldMapper):
    py_type = float

    def coerce(self, value: Any):
        if isinstance(value, bool):
            raise MapperParsingError(
                f"failed to parse field [{self.name}] of type [{self.type_name}]: boolean")
        try:
            v = self.py_type(value)
        except (TypeError, ValueError):
            raise MapperParsingError(
                f"failed to parse field [{self.name}] of type [{self.type_name}] value [{value}]")
        if isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
            raise MapperParsingError(f"[{self.name}] non-finite value [{value}]")
        return v

    def index_terms(self, value):
        return [repr(self.coerce(value))]

    def doc_value(self, value):
        return self.coerce(value)


class LongFieldMapper(_NumericMapper):
    type_name = "long"
    py_type = int


class IntegerFieldMapper(LongFieldMapper):
    type_name = "integer"


class ShortFieldMapper(LongFieldMapper):
    type_name = "short"


class ByteFieldMapper(LongFieldMapper):
    type_name = "byte"


class DoubleFieldMapper(_NumericMapper):
    type_name = "double"
    py_type = float


class FloatFieldMapper(DoubleFieldMapper):
    type_name = "float"


class HalfFloatFieldMapper(DoubleFieldMapper):
    type_name = "half_float"


class ScaledFloatFieldMapper(_NumericMapper):
    type_name = "scaled_float"
    py_type = float

    def doc_value(self, value):
        """The stored long `Math.round(value x factor)` (half UP: 0.125
        at 100 is 13 cents, -0.125 is -12), read back over the factor."""
        factor = self.params.get("scaling_factor", 100)
        return round_half_up(self.coerce(value) * factor) / factor


def round_half_up(x: float) -> int:
    """Java's `Math.round` of a double: the nearest whole number, a half
    toward positive infinity (Python's `round` takes the even one)."""
    r = math.floor(x)
    return r + 1 if x - r >= 0.5 else r


class BooleanFieldMapper(FieldMapper):
    type_name = "boolean"

    def coerce(self, value):
        if isinstance(value, bool):
            return value
        if value in ("true", "True"):
            return True
        if value in ("false", "False", ""):
            return False
        raise MapperParsingError(f"failed to parse boolean field [{self.name}] value [{value}]")

    def index_terms(self, value):
        return ["T" if self.coerce(value) else "F"]

    def doc_value(self, value):
        return self.coerce(value)


_LOCALE_NAMES = {
    # localized day/month tokens normalize to English before strptime
    # (the reference delegates to java.time locale-aware formatters)
    "de": {"Mo": "Mon", "Di": "Tue", "Mi": "Wed", "Do": "Thu", "Fr": "Fri",
           "Sa": "Sat", "So": "Sun",
           "Jan": "Jan", "Feb": "Feb", "Mär": "Mar", "Mrz": "Mar",
           "Apr": "Apr", "Mai": "May", "Jun": "Jun", "Jul": "Jul",
           "Aug": "Aug", "Sep": "Sep", "Okt": "Oct", "Nov": "Nov",
           "Dez": "Dec"},
}


def parse_custom_date(value: str, fmt: str, locale: str = "") -> int:
    """Parse with a joda-style custom pattern (E, d MMM yyyy HH:mm:ss Z)
    honoring the mapping's locale for day/month names."""
    import datetime as _dt

    s = str(value).strip()
    names = _LOCALE_NAMES.get(str(locale or "").split("_")[0].lower())
    if names:
        for loc, eng in names.items():
            s = re.sub(rf"\b{loc}\b", eng, s)
    py = fmt
    for joda, strp in (("yyyy", "%Y"), ("yy", "%y"), ("MMMM", "%B"),
                       ("MMM", "%b"), ("MM", "%m"), ("dd", "%d"),
                       ("EEEE", "%A"), ("E", "%a"), ("HH", "%H"),
                       ("mm", "%M"), ("ss", "%S"), ("Z", "%z")):
        py = py.replace(joda, strp)
    py = re.sub(r"(?<!%)\bd\b", "%d", py)
    py = re.sub(r"(?<!%)\bM\b", "%m", py)
    d = _dt.datetime.strptime(s, py)
    if d.tzinfo is None:
        d = d.replace(tzinfo=_dt.timezone.utc)
    return int(d.timestamp() * 1000)


def parse_date_in_format(value: Any, fmt: Any,
                         round_up: bool = False) -> Optional[int]:
    """A date bound in a query's own `format` (`dd/MM/yyyy`): the first
    pattern of `fmt` (`||`-separated) that reads it. With `round_up` (a
    `gt` / `lte` bound) the fields the pattern leaves out take their
    greatest values, as JavaDateMathParser's roundUp does: `21/01/2015`
    is 23:59:59.999 of that day. None where `fmt` names no pattern that
    reads it (a named format, date math): the caller parses as before."""
    if not isinstance(value, str) or not fmt or "||" in value:
        return None
    for pat in str(fmt).split("||"):
        pat = pat.strip()
        if not re.search(r"yy|MM|dd|HH", pat):
            continue
        try:
            millis = parse_custom_date(value, pat)
        except (ValueError, MapperParsingError):
            continue
        if not round_up or "SSS" in pat:
            return millis
        for field, span in (("ss", 1000), ("mm", 60_000),
                            ("HH", 3_600_000), ("dd", 86_400_000)):
            if field in pat:
                return millis + span - 1
        d = _dt.datetime.fromtimestamp(millis / 1000, _dt.timezone.utc)
        if "MM" in pat:
            nxt = d.replace(year=d.year + d.month // 12,
                            month=d.month % 12 + 1)
        else:
            nxt = d.replace(year=d.year + 1)
        return int(nxt.timestamp() * 1000) - 1
    return None


class DateFieldMapper(FieldMapper):
    type_name = "date"

    _CUSTOM_PATTERN_RE = re.compile(r"[EM]{1,4}|,")

    def _parse(self, value):
        # an explicit epoch_second format scales numeric inputs
        # (DateFormatters EpochSecond); everything else rides the default
        # strict_date_optional_time||epoch_millis chain
        fmt = str(self.params.get("format", ""))
        if "epoch_second" in fmt:
            try:
                return int(float(value) * 1000)
            except (TypeError, ValueError):
                pass
        if isinstance(value, str) and fmt and ("E" in fmt or "MMM" in fmt):
            try:
                return parse_custom_date(value, fmt,
                                         self.params.get("locale", ""))
            except (ValueError, MapperParsingError):
                pass
        return parse_date_millis(value)

    def index_terms(self, value):
        return [str(self._parse(value))]

    def doc_value(self, value):
        return self._parse(value)


def parse_date_nanos(value: Any) -> int:
    """Epoch NANOS (DateFieldMapper.Resolution.NANOSECONDS): numbers are
    epoch millis; strings keep up to 9 fractional-second digits."""
    if isinstance(value, bool):
        raise MapperParsingError(f"cannot parse date from boolean [{value}]")
    if isinstance(value, (int, float)):
        return int(value) * 1_000_000
    s = str(value).strip()
    if re.fullmatch(r"-?\d{10,}", s):
        return int(s) * 1_000_000
    m = re.match(r"^(.*?)(?:\.(\d{1,9}))?((?:Z|[+-]\d{2}:?\d{2})?)$", s)
    base, frac, tz = m.groups()
    millis = parse_date_millis(base + (tz or ""))
    return millis * 1_000_000 + int((frac or "0").ljust(9, "0")[:9])


class DateNanosFieldMapper(DateFieldMapper):
    """date_nanos: nanosecond-resolution dates (the reference stores nanos
    since epoch; `DateFieldMapper` with Resolution.NANOSECONDS)."""

    type_name = "date_nanos"

    def index_terms(self, value):
        return [str(parse_date_nanos(value))]

    def doc_value(self, value):
        nanos = parse_date_nanos(value)
        if nanos < 0:
            raise MapperParsingError(
                f"failed to parse field [{self.name}]",
                caused_by={"reason": f"date[{value}] is before the epoch in "
                           "1970 and cannot be stored in nanosecond "
                           "resolution"})
        if nanos > 9223372036854775807:  # int64 max = 2262-04-11
            raise MapperParsingError(
                f"failed to parse field [{self.name}]",
                caused_by={"reason": f"date[{value}] is after "
                           "2262-04-11T23:47:16.854775807 and cannot be "
                           "stored in nanosecond resolution"})
        return nanos


class IpFieldMapper(FieldMapper):
    type_name = "ip"

    @staticmethod
    def parse_ip(value) -> int:
        """IPs order/store in the 16-byte IPv6 space; IPv4 maps to
        ::ffff:a.b.c.d (the reference stores InetAddressPoint's 16-byte
        form), so '::1' and '0.0.0.1' remain distinct values. Every
        consumer (doc values, query bounds, agg ranges) MUST use this one
        transform or comparisons cross number spaces."""
        ip = ipaddress.ip_address(str(value))
        if isinstance(ip, ipaddress.IPv4Address):
            ip = ipaddress.IPv6Address(b"\x00" * 10 + b"\xff\xff" + ip.packed)
        return int(ip)

    def coerce(self, value) -> int:
        try:
            return self.parse_ip(value)
        except ValueError:
            raise MapperParsingError(f"failed to parse IP [{value}] for field [{self.name}]")

    @staticmethod
    def format_value(stored: int) -> str:
        addr = ipaddress.IPv6Address(int(stored))
        return str(addr.ipv4_mapped or addr)

    def index_terms(self, value):
        return [str(self.coerce(value))]

    def doc_value(self, value):
        return self.coerce(value)


def _geohash_decode(gh: str):
    """Geohash -> (lat, lon) cell center (Lucene GeoHashUtils)."""
    bits = "0123456789bcdefghjkmnpqrstuvwxyz"
    lat_lo, lat_hi = -90.0, 90.0
    lon_lo, lon_hi = -180.0, 180.0
    even = True
    for ch in gh:
        cd = bits.index(ch)
        for mask in (16, 8, 4, 2, 1):
            if even:
                mid = (lon_lo + lon_hi) / 2
                if cd & mask:
                    lon_lo = mid
                else:
                    lon_hi = mid
            else:
                mid = (lat_lo + lat_hi) / 2
                if cd & mask:
                    lat_lo = mid
                else:
                    lat_hi = mid
            even = not even
    return ((lat_lo + lat_hi) / 2, (lon_lo + lon_hi) / 2)


class GeoPointFieldMapper(FieldMapper):
    type_name = "geo_point"

    def coerce(self, value) -> Tuple[float, float]:
        """Returns (lat, lon)."""
        if isinstance(value, dict):
            try:
                return float(value["lat"]), float(value["lon"])
            except (KeyError, TypeError, ValueError):
                raise MapperParsingError(f"failed to parse geo_point [{value}]")
        if isinstance(value, (list, tuple)) and len(value) == 2:
            return float(value[1]), float(value[0])  # [lon, lat] GeoJSON order
        if isinstance(value, str):
            parts = value.split(",")
            if len(parts) == 2:
                return float(parts[0]), float(parts[1])
            import re as _re
            if _re.fullmatch(r"[0123456789bcdefghjkmnpqrstuvwxyz]{1,12}",
                             value.lower()):
                return _geohash_decode(value.lower())
        raise MapperParsingError(f"failed to parse geo_point [{value}]")

    def doc_value(self, value):
        return self.coerce(value)


class DenseVectorFieldMapper(FieldMapper):
    """`dense_vector` (reference: DenseVectorFieldMapper.java:45).

    params: dims (required), similarity (cosine|dot_product|l2_norm,
    default cosine), index_options.type — the quantization-ladder rung
    (flat|int8_flat|int4_flat|binary_flat: storage encoding of the
    device matrix, see `elasticsearch_tpu/quant/codec.py`;
    ivf|int8_ivf|int4_ivf|binary_ivf: same rung on the partitioned
    `tpu_ivf` engine, overriding `index.knn.engine`),
    index_options.nlist / nprobe (per-field IVF overrides),
    index_options.rescore / rescore_oversample (two-phase exact rescore:
    packed rungs default rescore on; oversample sizes the coarse
    window — k·oversample candidates re-ranked exactly).
    """

    type_name = "dense_vector"

    INDEX_OPTIONS_TYPES = ("flat", "int8_flat", "int4_flat", "binary_flat",
                           "ivf", "int8_ivf", "int4_ivf", "binary_ivf")

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.dims = self.params.get("dims")
        if self.dims is None:
            raise MapperParsingError(f"[{name}] dense_vector requires [dims]")
        self.dims = int(self.dims)
        self.similarity = self.params.get("similarity", "cosine")
        if self.similarity not in ("cosine", "dot_product", "l2_norm", "max_inner_product"):
            raise MapperParsingError(f"[{name}] unknown similarity [{self.similarity}]")
        opts = self.params.get("index_options") or {}
        otype = opts.get("type")
        if otype is not None and otype not in self.INDEX_OPTIONS_TYPES:
            raise MapperParsingError(
                f"[{name}] unknown index_options type [{otype}]; expected "
                f"one of {list(self.INDEX_OPTIONS_TYPES)}")
        self.index_options_type = otype
        # packed rungs constrain dims by their bit layout; reject at
        # mapping time, not at first refresh
        if otype in ("int4_flat", "int4_ivf") and self.dims % 2:
            raise MapperParsingError(
                f"[{name}] index_options type [{otype}] requires even "
                f"[dims], got [{self.dims}]")
        if otype in ("binary_flat", "binary_ivf"):
            if self.dims % 32:
                raise MapperParsingError(
                    f"[{name}] index_options type [{otype}] requires "
                    f"[dims] divisible by 32, got [{self.dims}]")
            if self.similarity in ("l2_norm", "max_inner_product"):
                # the sign-bit coarse phase discards magnitudes, which
                # l2 and MIP rankings depend on — the true top-k would
                # never enter the rescore window
                raise MapperParsingError(
                    f"[{name}] index_options type [{otype}] scores "
                    "sign-bit Hamming — incompatible with "
                    f"[{self.similarity}] similarity (use cosine or "
                    "unit-normalized dot_product)")
        for opt_key in ("nlist", "nprobe", "rescore_oversample"):
            v = opts.get(opt_key)
            if v is None or (opt_key == "nprobe" and v == "auto"):
                continue  # "auto" is meaningful only for nprobe
            try:
                ok = int(v) >= 1
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise MapperParsingError(
                    f"[{name}] index_options [{opt_key}] must be an "
                    f"integer >= 1, got [{v}]")

    def coerce(self, value) -> np.ndarray:
        if not isinstance(value, (list, tuple)):
            raise MapperParsingError(f"[{self.name}] dense_vector value must be an array")
        arr = np.asarray(value, dtype=np.float32)
        if arr.ndim != 1 or arr.shape[0] != self.dims:
            raise MapperParsingError(
                f"[{self.name}] vector has [{arr.shape[0] if arr.ndim == 1 else '?'}] "
                f"dimensions, mapping requires [{self.dims}]")
        if not np.isfinite(arr).all():
            raise MapperParsingError(f"[{self.name}] vector contains non-finite values")
        return arr


class ObjectMapper(FieldMapper):
    type_name = "object"


class NestedMapper(FieldMapper):
    type_name = "nested"


class RankFeatureFieldMapper(FieldMapper):
    """`rank_feature` (reference: modules/mapper-extras
    RankFeatureFieldMapper) — positive float consumed by rank_feature
    queries."""

    type_name = "rank_feature"

    def coerce(self, value) -> float:
        v = float(value)
        if v <= 0 and not self.params.get("positive_score_impact", True) is False:
            if v < 0:
                raise MapperParsingError(
                    f"[{self.name}] rank_feature fields only support positive "
                    f"values, got [{value}]")
        return v

    def index_terms(self, value):
        return []

    def doc_value(self, value):
        return self.coerce(value)


class RankFeaturesFieldMapper(FieldMapper):
    """`rank_features`: a sparse map feature→weight."""

    type_name = "rank_features"

    def coerce(self, value) -> dict:
        if not isinstance(value, dict):
            raise MapperParsingError(
                f"[{self.name}] rank_features value must be an object")
        return {str(k): float(v) for k, v in value.items()}

    def index_terms(self, value):
        return []

    def doc_value(self, value):
        return self.coerce(value)


class RankVectorsFieldMapper(FieldMapper):
    """`rank_vectors` (reference: x-pack rank-vectors
    RankVectorsFieldMapper, the late-interaction field): each doc holds
    a ragged LIST of token vectors, scored by MaxSim against a multi-
    token query.

    params: dims (required), similarity (cosine|dot_product, default
    cosine — l2/MIP have no max-sum decomposition on the dot kernel),
    index_options.encoding — the token-block storage rung in the device
    columnar store (f32|bf16|int8|int4, default int8; binary has no
    MaxSim kernel), index_options.oversample — the coarse pooled-
    centroid window multiplier (k·oversample candidates rescored by the
    fused MaxSim kernel, default 4), index_options.coarse — storage
    rung of the pooled centroid matrix (any `dense_vector` flat rung,
    default f32)."""

    type_name = "rank_vectors"

    ENCODINGS = ("f32", "bf16", "int8", "int4")
    COARSE = ("f32", "bf16", "int8", "int4", "binary")

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.dims = self.params.get("dims")
        if self.dims is None:
            raise MapperParsingError(f"[{name}] rank_vectors requires [dims]")
        self.dims = int(self.dims)
        self.similarity = self.params.get("similarity", "cosine")
        if self.similarity not in ("cosine", "dot_product"):
            raise MapperParsingError(
                f"[{name}] unknown similarity [{self.similarity}] for "
                "rank_vectors; expected cosine or dot_product")
        # storage knobs read from index_options with top-level fallback
        # (the REST mapping surface accepts either placement)
        opts = self.params.get("index_options") or {}
        self.encoding = opts.get("encoding",
                                 self.params.get("encoding", "int8"))
        if self.encoding not in self.ENCODINGS:
            raise MapperParsingError(
                f"[{name}] unknown index_options encoding "
                f"[{self.encoding}]; expected one of {list(self.ENCODINGS)}")
        if self.encoding == "int4" and self.dims % 2:
            raise MapperParsingError(
                f"[{name}] index_options encoding [int4] requires even "
                f"[dims], got [{self.dims}]")
        self.coarse = opts.get("coarse", self.params.get("coarse", "f32"))
        if self.coarse not in self.COARSE:
            raise MapperParsingError(
                f"[{name}] unknown index_options coarse [{self.coarse}]; "
                f"expected one of {list(self.COARSE)}")
        oversample = opts.get("oversample",
                              self.params.get("oversample", 4))
        try:
            ok = int(oversample) >= 1
        except (TypeError, ValueError):
            ok = False
        if not ok:
            raise MapperParsingError(
                f"[{name}] index_options [oversample] must be an integer "
                f">= 1, got [{oversample}]")
        self.oversample = int(oversample)

    def coerce(self, value) -> np.ndarray:
        if not isinstance(value, (list, tuple)) or not value:
            raise MapperParsingError(
                f"[{self.name}] rank_vectors value must be a non-empty "
                "array of vectors")
        arr = np.asarray(value, dtype=np.float32)
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        if arr.ndim != 2 or arr.shape[1] != self.dims:
            raise MapperParsingError(
                f"[{self.name}] rank_vectors rows must have [{self.dims}] "
                "dimensions")
        if not np.isfinite(arr).all():
            raise MapperParsingError(
                f"[{self.name}] rank_vectors contains non-finite values")
        return arr

    def index_terms(self, value):
        return []

    def doc_value(self, value):
        return self.coerce(value)


class JoinFieldMapper(FieldMapper):
    """`join` (reference: modules/parent-join ParentJoinFieldMapper):
    relations define parent→children; doc value keeps {name, parent}."""

    type_name = "join"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.relations: Dict[str, List[str]] = {}
        for parent, children in (self.params.get("relations") or {}).items():
            self.relations[parent] = (children if isinstance(children, list)
                                      else [children])

    def coerce(self, value):
        if isinstance(value, str):
            return {"name": value}
        if isinstance(value, dict) and "name" in value:
            return value
        raise MapperParsingError(f"[{self.name}] join value must be a "
                                 f"relation name or {{name, parent}}")

    def index_terms(self, value):
        return [self.coerce(value)["name"]]

    def doc_value(self, value):
        return self.coerce(value)


class PercolatorFieldMapper(FieldMapper):
    """`percolator` (reference: modules/percolator PercolatorFieldMapper):
    stores a query to run in reverse against candidate documents."""

    type_name = "percolator"

    def coerce(self, value):
        if not isinstance(value, dict):
            raise MapperParsingError(
                f"[{self.name}] percolator field must hold a query object")
        # validate eagerly like the reference (parse at index time)
        from elasticsearch_tpu.search.queries import parse_query
        parse_query(value)
        return value

    def index_terms(self, value):
        return []

    def doc_value(self, value):
        return self.coerce(value)


class BinaryFieldMapper(FieldMapper):
    """`binary` (reference: index/mapper/BinaryFieldMapper.java): base64
    value, stored but not searchable."""

    type_name = "binary"

    def coerce(self, value) -> str:
        import base64
        s = str(value)
        try:
            base64.b64decode(s, validate=True)
        except Exception:
            raise MapperParsingError(
                f"[{self.name}] failed to parse base64 binary value")
        return s

    def doc_value(self, value):
        return self.coerce(value) if self.params.get("doc_values", True) else None


class RangeFieldMapperBase(FieldMapper):
    """Range family (reference: index/mapper/RangeFieldMapper.java —
    integer/long/float/double/date/ip ranges). A value is an object of
    gt/gte/lt/lte bounds; stored normalized to inclusive numeric [lo, hi]
    so membership (term) and overlap (range query relations) are interval
    arithmetic over doc values."""

    discrete = True  # exclusive bounds shift by 1; floats use nextafter

    def _bound(self, value) -> float:
        return float(value)

    def coerce(self, value) -> dict:
        if not isinstance(value, dict):
            raise MapperParsingError(
                f"[{self.name}] range field value must be an object of bounds")
        lo, hi = -math.inf, math.inf
        for k, v in value.items():
            if k == "gte":
                lo = self._bound(v)
            elif k == "gt":
                b = self._bound(v)
                lo = b + 1 if self.discrete else float(np.nextafter(b, math.inf))
            elif k == "lte":
                hi = self._bound(v)
            elif k == "lt":
                b = self._bound(v)
                hi = b - 1 if self.discrete else float(np.nextafter(b, -math.inf))
            else:
                raise MapperParsingError(
                    f"[{self.name}] unknown range bound [{k}]")
        if lo > hi:
            raise MapperParsingError(
                f"[{self.name}] range lower bound greater than upper bound")
        return {"gte": lo, "lte": hi}

    def doc_value(self, value):
        return self.coerce(value)

    def query_bound(self, value, round_up: bool = False) -> float:
        """Bound coercion for query-side values (same units as storage);
        `round_up` only matters for date ranges (date-math rounding)."""
        return self._bound(value)


class IntegerRangeFieldMapper(RangeFieldMapperBase):
    type_name = "integer_range"

    def _bound(self, value):
        return float(int(value))


class LongRangeFieldMapper(IntegerRangeFieldMapper):
    type_name = "long_range"


class FloatRangeFieldMapper(RangeFieldMapperBase):
    type_name = "float_range"
    discrete = False


class DoubleRangeFieldMapper(FloatRangeFieldMapper):
    type_name = "double_range"


class DateRangeFieldMapper(RangeFieldMapperBase):
    type_name = "date_range"

    def _bound(self, value):
        return float(parse_date_millis(value))

    def query_bound(self, value, round_up: bool = False) -> float:
        return float(parse_date_millis(value, round_up=round_up))


class IpRangeFieldMapper(RangeFieldMapperBase):
    type_name = "ip_range"

    def _bound(self, value):
        return float(int(ipaddress.ip_address(str(value))))

    def coerce(self, value):
        if isinstance(value, str):  # CIDR form "10.0.0.0/8"
            try:
                net = ipaddress.ip_network(value, strict=False)
            except ValueError:
                raise MapperParsingError(
                    f"[{self.name}] failed to parse ip range [{value}]")
            return {"gte": float(int(net.network_address)),
                    "lte": float(int(net.broadcast_address))}
        return super().coerce(value)


class CompletionFieldMapper(FieldMapper):
    """`completion` (reference: index/mapper/CompletionFieldMapper.java —
    FST-backed suggester field). Inputs index as exact terms; the completion
    suggester prefix-scans them (search/extras.py)."""

    type_name = "completion"

    def _inputs(self, value) -> Tuple[List[str], int, dict]:
        if isinstance(value, str):
            return [value], 1, {}
        if isinstance(value, list):
            return [str(v) for v in value], 1, {}
        if isinstance(value, dict):
            inp = value.get("input", [])
            inputs = [inp] if isinstance(inp, str) else [str(v) for v in inp]
            return (inputs, int(value.get("weight", 1)),
                    value.get("contexts") or {})
        raise MapperParsingError(
            f"[{self.name}] completion value must be string, array or object")

    def index_terms(self, value):
        return self._inputs(value)[0]

    def doc_value(self, value):
        inputs, weight, contexts = self._inputs(value)
        # context-enabled fields REQUIRE contexts at index time unless the
        # context resolves from a document path (ContextMappings.addField)
        defs = self.params.get("contexts") or []
        needs = [d for d in defs if not d.get("path")]
        if needs and not contexts:
            raise MapperParsingError(
                f"Contexts are mandatory in context enabled completion "
                f"field [{self.name}]")
        return {"input": inputs, "weight": weight, "contexts": contexts}


class _ShingleAnalyzer:
    """Analyzer adapter producing word shingles of size N over a base
    analyzer (both index- and search-side for the SAYT subfields)."""

    def __init__(self, base, n: int):
        self.base = base
        self.n = n

    def terms(self, text: str) -> List[str]:
        base = self.base.terms(text)
        return [" ".join(base[i:i + self.n])
                for i in range(len(base) - self.n + 1)]

    def analyze(self, text: str):
        from elasticsearch_tpu.index.analysis import Token
        return [Token(t, i, 0, 0) for i, t in enumerate(self.terms(text))]


class _ShingleTextMapper(TextFieldMapper):
    """Auto subfield of search_as_you_type: word shingles of size N."""

    type_name = "text"

    def __init__(self, name, params=None, shingle_size=2):
        super().__init__(name, params)
        self.shingle_size = shingle_size
        self.analyzer = _ShingleAnalyzer(self.analyzer, shingle_size)
        self.search_analyzer = _ShingleAnalyzer(self.search_analyzer,
                                                shingle_size)


class _PrefixTextMapper(TextFieldMapper):
    """Auto subfield of search_as_you_type: edge n-grams over 1..3-shingles
    (reference's `._index_prefix`)."""

    type_name = "text"

    def analyze(self, value):
        base = super().analyze(value)
        out = []
        for n in (1, 2, 3):
            for i in range(max(0, len(base) - n + 1)):
                shingle = " ".join(base[i:i + n])
                out.extend(shingle[:j] for j in range(1, min(len(shingle), 19) + 1))
        return sorted(set(out))

    def analyze_positions(self, value):
        from elasticsearch_tpu.index.analysis import Token
        return [Token(t, i, 0, 0) for i, t in enumerate(self.analyze(value))]


class SearchAsYouTypeFieldMapper(TextFieldMapper):
    """`search_as_you_type` (reference: modules/mapper-extras
    SearchAsYouTypeFieldMapper.java): a text field with auto `._2gram`,
    `._3gram` shingle subfields and an `._index_prefix` edge-ngram subfield,
    targeted by multi_match bool_prefix queries."""

    type_name = "search_as_you_type"


class TokenCountFieldMapper(FieldMapper):
    """`token_count` (reference: modules/mapper-extras
    TokenCountFieldMapper.java): indexes the number of analyzed tokens."""

    type_name = "token_count"

    def __init__(self, name, params=None,
                 registry: AnalysisRegistry = DEFAULT_REGISTRY):
        super().__init__(name, params)
        self.analyzer = registry.get(self.params.get("analyzer", "standard"))

    def count(self, value) -> int:
        # numeric input IS the count (query-side values, pre-counted docs);
        # strings get analyzed (index-side text values)
        if isinstance(value, int) and not isinstance(value, bool):
            return value
        return len(self.analyzer.terms(str(value)))

    def index_terms(self, value):
        return [repr(self.count(value))]

    def doc_value(self, value):
        return self.count(value)


class WildcardFieldMapper(KeywordFieldMapper):
    """`wildcard` (reference: x-pack/plugin/wildcard): keyword-like field
    optimized for leading-wildcard matching. The term-scan execution here
    already handles arbitrary patterns, so this shares keyword indexing."""

    type_name = "wildcard"


class ConstantKeywordFieldMapper(FieldMapper):
    """`constant_keyword` (reference: x-pack/plugin/mapper-constant-keyword):
    one value for every document in the index; documents may omit it, and a
    conflicting value is a parse error. First seen value fixes it if the
    mapping didn't."""

    type_name = "constant_keyword"

    def coerce(self, value, fix: bool = False) -> str:
        s = str(value)
        const = self.params.get("value")
        if const is None:
            if fix:  # only the write path fixes the constant
                self.params["value"] = s
            return s
        if s != const:
            raise MapperParsingError(
                f"[{self.name}] constant_keyword field is already set to "
                f"[{const}], cannot index [{s}]")
        return s

    def index_terms(self, value):
        # query-side coercion must not mutate the mapping
        return [self.coerce(value)]

    def doc_value(self, value):
        return self.coerce(value, fix=True)


class Murmur3FieldMapper(FieldMapper):
    """`murmur3` (reference: plugins/mapper-murmur3): stores the murmur3
    hash of the value for cheap cardinality estimation."""

    type_name = "murmur3"

    def doc_value(self, value):
        from elasticsearch_tpu.cluster.routing import murmur3_x86_32
        h = murmur3_x86_32(str(value).encode("utf-8"))
        return h - (1 << 32) if h >= (1 << 31) else h  # signed like the ref


class HistogramFieldMapper(FieldMapper):
    """`histogram` (reference: x-pack/plugin/analytics histogram field):
    pre-aggregated {values[], counts[]} consumed by percentile aggs."""

    type_name = "histogram"

    def coerce(self, value) -> dict:
        if not isinstance(value, dict) or "values" not in value \
                or "counts" not in value:
            raise MapperParsingError(
                f"[{self.name}] histogram must be {{values, counts}}")
        values = [float(v) for v in value["values"]]
        counts = [int(c) for c in value["counts"]]
        if len(values) != len(counts):
            raise MapperParsingError(
                f"[{self.name}] expected same length for values and counts")
        if any(c < 0 for c in counts):
            raise MapperParsingError(f"[{self.name}] counts must be >= 0")
        if values != sorted(values):
            raise MapperParsingError(
                f"[{self.name}] values must be in increasing order")
        return {"values": values, "counts": counts}

    def doc_value(self, value):
        return self.coerce(value)


class FlattenedFieldMapper(FieldMapper):
    """`flattened` (reference: x-pack/plugin/mapper-flattened
    FlatObjectFieldMapper.java): an entire JSON object indexed as keywords —
    root-field queries match any leaf value, `field.key` queries match that
    key's value. Keyed terms are materialized in MapperService._index_one."""

    type_name = "flattened"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.depth_limit = int(self.params.get("depth_limit", 20))

    def coerce(self, value) -> dict:
        if not isinstance(value, dict):
            raise MapperParsingError(
                f"[{self.name}] flattened field value must be an object")
        return value

    def index_terms(self, value):
        # query-side coercion: scalar query values look up leaf terms
        # (document dicts never reach here — _index_one intercepts them)
        if isinstance(value, dict):
            return []
        return [_flat_str(value)]

    def leaves(self, value, prefix: str = "", depth: int = 0):
        """Yields (key_path, leaf_string)."""
        if depth > self.depth_limit:
            raise MapperParsingError(
                f"[{self.name}] object depth exceeds depth_limit "
                f"[{self.depth_limit}]")
        for k, v in value.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                yield from self.leaves(v, path + ".", depth + 1)
            elif isinstance(v, list):
                for item in v:
                    if isinstance(item, dict):
                        yield from self.leaves(item, path + ".", depth + 1)
                    elif item is not None:
                        yield path, _flat_str(item)
            elif v is not None:
                yield path, _flat_str(v)

    def doc_value(self, value):
        return self.coerce(value)


def _flat_str(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


class AnnotatedTextFieldMapper(TextFieldMapper):
    """`annotated_text` (reference: plugins/mapper-annotated-text):
    markdown-style `[text](annotation)` spans index both the visible text
    and the annotation value as terms."""

    type_name = "annotated_text"

    _ANNOTATION = re.compile(r"\[([^\]]+)\]\(([^)]+)\)")

    def _expand(self, value: str) -> str:
        annotations = []

        def sub(m):
            for part in m.group(2).split("&"):
                import urllib.parse
                annotations.append(urllib.parse.unquote(part))
            return m.group(1)

        text = self._ANNOTATION.sub(sub, str(value))
        return text + ("\n" + "\n".join(annotations) if annotations else "")

    def analyze(self, value):
        return super().analyze(self._expand(str(value)))

    def analyze_positions(self, value):
        return super().analyze_positions(self._expand(str(value)))


class SparseVectorFieldMapper(FieldMapper):
    """`sparse_vector` (reference: x-pack/plugin/vectors
    SparseVectorFieldMapper.java, deprecated in the snapshot): map of
    dimension→weight, stored for script access."""

    type_name = "sparse_vector"

    def coerce(self, value) -> dict:
        if not isinstance(value, dict):
            raise MapperParsingError(
                f"[{self.name}] sparse_vector value must be an object")
        return {str(k): float(v) for k, v in value.items()}

    def doc_value(self, value):
        return self.coerce(value)


class GeoShapeFieldMapper(FieldMapper):
    """`geo_shape` (reference: index/mapper/GeoShapeFieldMapper.java +
    libs/geo): GeoJSON (or WKT envelope/point) geometries. Indexed as the
    shape's bounding envelope; geo_shape queries run envelope relations —
    a documented approximation of the reference's triangulated BKD index."""

    type_name = "geo_shape"

    def coerce(self, value) -> dict:
        shape = self._parse_shape(value)
        env = shape_envelope(shape)
        return {"shape": shape, "envelope": env}

    def _parse_shape(self, value) -> dict:
        if isinstance(value, dict) and "type" in value:
            t = str(value["type"]).lower()
            if t == "geometrycollection":
                geoms = [self._parse_shape(g)
                         for g in value.get("geometries", [])]
                return {"type": "geometrycollection", "geometries": geoms}
            if "coordinates" not in value:
                raise MapperParsingError(
                    f"[{self.name}] geo_shape requires [coordinates]")
            return {"type": t, "coordinates": value["coordinates"]}
        if isinstance(value, str):
            return parse_wkt(value, self.name)
        raise MapperParsingError(
            f"[{self.name}] failed to parse geo_shape value [{value}]")

    def doc_value(self, value):
        return self.coerce(value)


def shape_envelope(shape: dict) -> Tuple[float, float, float, float]:
    """(min_lon, min_lat, max_lon, max_lat) of a normalized shape dict."""
    if shape["type"] == "geometrycollection":
        envs = [shape_envelope(g) for g in shape["geometries"]]
        return (min(e[0] for e in envs), min(e[1] for e in envs),
                max(e[2] for e in envs), max(e[3] for e in envs))
    coords = shape["coordinates"]
    if shape["type"] == "envelope":
        # [[min_lon, max_lat], [max_lon, min_lat]] — ES envelope order
        (min_lon, max_lat), (max_lon, min_lat) = coords
        return (float(min_lon), float(min_lat), float(max_lon), float(max_lat))
    pts = list(_iter_positions(coords))
    if not pts:
        raise MapperParsingError("geo_shape has no coordinates")
    lons = [p[0] for p in pts]
    lats = [p[1] for p in pts]
    return (min(lons), min(lats), max(lons), max(lats))


def _iter_positions(coords):
    if isinstance(coords, (list, tuple)):
        if len(coords) >= 2 and all(
                isinstance(c, (int, float)) for c in coords[:2]):
            yield float(coords[0]), float(coords[1])
        else:
            for c in coords:
                yield from _iter_positions(c)


def parse_wkt(s: str, field: str = "") -> dict:
    """Minimal WKT: POINT, ENVELOPE (ES extension), POLYGON, LINESTRING."""
    m = re.match(r"\s*(\w+)\s*\((.*)\)\s*$", s, re.DOTALL)
    if not m:
        raise MapperParsingError(f"[{field}] failed to parse WKT [{s}]")
    kind = m.group(1).upper()
    body = m.group(2)

    def pts(text):
        out = []
        for pair in text.split(","):
            xy = pair.split()
            out.append([float(xy[0]), float(xy[1])])
        return out

    if kind == "POINT":
        return {"type": "point", "coordinates": pts(body)[0]}
    if kind == "ENVELOPE":
        # ENVELOPE(min_lon, max_lon, max_lat, min_lat) — WKT/ES order
        v = [float(x) for x in body.split(",")]
        return {"type": "envelope",
                "coordinates": [[v[0], v[2]], [v[1], v[3]]]}
    if kind == "LINESTRING":
        return {"type": "linestring", "coordinates": pts(body)}
    if kind == "POLYGON":
        rings = re.findall(r"\(([^()]*)\)", body)
        return {"type": "polygon", "coordinates": [pts(r) for r in rings]}
    raise MapperParsingError(f"[{field}] unsupported WKT type [{kind}]")


class AliasFieldMapper(FieldMapper):
    """`alias` (reference: index/mapper/FieldAliasMapper.java): query-time
    alternate name for a concrete field. Resolved in MapperService.get /
    resolve_field; writes through an alias are rejected."""

    type_name = "alias"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        if not self.params.get("path"):
            raise MapperParsingError(f"[{name}] alias requires [path]")
        self.path = self.params["path"]


FIELD_TYPES = {
    m.type_name: m
    for m in (KeywordFieldMapper, TextFieldMapper, LongFieldMapper, IntegerFieldMapper,
              ShortFieldMapper, ByteFieldMapper, DoubleFieldMapper, FloatFieldMapper,
              HalfFloatFieldMapper, ScaledFloatFieldMapper, BooleanFieldMapper,
              DateFieldMapper, DateNanosFieldMapper, IpFieldMapper,
              GeoPointFieldMapper,
              DenseVectorFieldMapper, ObjectMapper, NestedMapper,
              RankFeatureFieldMapper, RankFeaturesFieldMapper,
              RankVectorsFieldMapper,
              JoinFieldMapper, PercolatorFieldMapper,
              BinaryFieldMapper, IntegerRangeFieldMapper, LongRangeFieldMapper,
              FloatRangeFieldMapper, DoubleRangeFieldMapper,
              DateRangeFieldMapper, IpRangeFieldMapper,
              CompletionFieldMapper, SearchAsYouTypeFieldMapper,
              TokenCountFieldMapper, WildcardFieldMapper,
              ConstantKeywordFieldMapper, Murmur3FieldMapper,
              HistogramFieldMapper, FlattenedFieldMapper,
              AnnotatedTextFieldMapper, SparseVectorFieldMapper,
              GeoShapeFieldMapper, AliasFieldMapper)
}


def build_mapper(name: str, definition: dict,
                 registry: Optional[AnalysisRegistry] = None) -> FieldMapper:
    leaf = name.rpartition(".")[2]
    if leaf == "":
        # ObjectMapper.Builder rejects empty field names
        raise IllegalArgumentError("field name cannot be an empty string")
    t = definition.get("type", "object" if "properties" in definition else None)
    if t is None:
        raise MapperParsingError(f"no type specified for field [{name}]")
    cls = FIELD_TYPES.get(t)
    if cls is None:
        raise MapperParsingError(f"No handler for type [{t}] declared on field [{name}]")
    params = {k: v for k, v in definition.items() if k not in ("type", "properties", "fields")}
    if registry is not None and issubclass(cls, (TextFieldMapper,
                                                 TokenCountFieldMapper)):
        return cls(name, params, registry=registry)
    return cls(name, params)


# ---------------------------------------------------------------------------
# MapperService / DocumentMapper
# ---------------------------------------------------------------------------

class MapperService:
    """Holds the (mutable, additive-only) mapping for one index.

    Reference: MapperService.java — mappings merge additively; changing an
    existing field's type is rejected.
    """

    def __init__(self, mapping: Optional[dict] = None, dynamic: bool = True,
                 registry: Optional[AnalysisRegistry] = None):
        # flat map "a.b.c" -> FieldMapper
        self._mappers: Dict[str, FieldMapper] = {}
        # fields with subfields (multi-fields), e.g. text with .keyword
        self._multi_fields: Dict[str, Dict[str, FieldMapper]] = {}
        self.registry = registry or DEFAULT_REGISTRY
        # fields whose fielddata/global-ordinals were materialized by a
        # search (stats report bytes only for loaded fields)
        self.loaded_fielddata: set = set()
        self.dynamic = dynamic
        self._meta: dict = {}
        # set on any mapping mutation; cleared by whoever persists the mapping
        self.dirty = False
        self.source_enabled = True
        if mapping:
            self.merge(mapping)

    # -- mapping CRUD --------------------------------------------------------
    def merge(self, mapping: dict) -> None:
        if "properties" in mapping:
            props = mapping["properties"]
        else:
            # a bare props dict; strip metadata sections (_source/_meta/
            # dynamic/_routing) which are NOT field definitions
            props = {k: v for k, v in mapping.items()
                     if not k.startswith("_") and k != "dynamic"}
        if "dynamic" in mapping:
            dyn = mapping["dynamic"]
            self.dynamic = dyn if isinstance(dyn, bool) else dyn == "true"
        if "_meta" in mapping:
            self._meta = mapping["_meta"]
        if isinstance(mapping.get("_source"), dict) \
                and mapping["_source"].get("enabled") is False:
            # _source disabled: stored internally (the engine needs it),
            # but never rendered and GET /_source 404s
            self.source_enabled = False
        self._merge_props(props, prefix="")

    def _merge_props(self, props: dict, prefix: str) -> None:
        for name, definition in props.items():
            if not isinstance(definition, dict):
                raise MapperParsingError(f"invalid mapping definition for [{prefix}{name}]")
            path = f"{prefix}{name}"
            if "properties" in definition:
                self._merge_props(definition["properties"], prefix=path + ".")
                if definition.get("type") == "nested":
                    self._put(path, NestedMapper(path, {}))
                continue
            mapper = build_mapper(path, definition, self.registry)
            self._put(path, mapper)
            for sub_name, sub_def in (definition.get("fields") or {}).items():
                sub_path = f"{path}.{sub_name}"
                sub = build_mapper(sub_path, sub_def, self.registry)
                self._multi_fields.setdefault(path, {})[sub_name] = sub
                self._put(sub_path, sub)
            if isinstance(mapper, SearchAsYouTypeFieldMapper):
                # auto shingle/prefix subfields (reference:
                # SearchAsYouTypeFieldMapper.java builds them in the builder)
                analyzer_params = {k: v for k, v in mapper.params.items()
                                   if k in ("analyzer", "search_analyzer")}
                subs = {
                    "_2gram": _ShingleTextMapper(f"{path}._2gram",
                                                 analyzer_params, 2),
                    "_3gram": _ShingleTextMapper(f"{path}._3gram",
                                                 analyzer_params, 3),
                    "_index_prefix": _PrefixTextMapper(f"{path}._index_prefix",
                                                       analyzer_params),
                }
                for sub_name, sub in subs.items():
                    self._multi_fields.setdefault(path, {})[sub_name] = sub
                    self._mappers[f"{path}.{sub_name}"] = sub

    def _put(self, path: str, mapper: FieldMapper) -> None:
        existing = self._mappers.get(path)
        if existing is not None and existing.type_name != mapper.type_name:
            raise IllegalArgumentError(
                f"mapper [{path}] cannot be changed from type [{existing.type_name}] "
                f"to [{mapper.type_name}]")
        if existing is None:
            self.dirty = True
        self._mappers[path] = mapper

    def get(self, path: str) -> Optional[FieldMapper]:
        """Mapper for a path; `alias` fields resolve to their target
        (reference: FieldAliasMapper — aliases are query-time only)."""
        mapper = self._mappers.get(path)
        if isinstance(mapper, AliasFieldMapper):
            target = self._mappers.get(mapper.path)
            return target if not isinstance(target, AliasFieldMapper) else None
        return mapper

    def get_raw(self, path: str) -> Optional[FieldMapper]:
        return self._mappers.get(path)

    def mark_fielddata_loaded(self, field: str) -> None:
        self.loaded_fielddata.add(field)

    def resolve_field(self, path: str) -> str:
        """Follow an alias to its concrete field name (one hop)."""
        mapper = self._mappers.get(path)
        if isinstance(mapper, AliasFieldMapper):
            return mapper.path
        return path

    def all_mappers(self):
        return list(self._mappers.items())

    def field_names(self) -> List[str]:
        return sorted(self._mappers)

    def vector_fields(self) -> Dict[str, DenseVectorFieldMapper]:
        return {p: m for p, m in self._mappers.items()
                if isinstance(m, DenseVectorFieldMapper)}

    def to_dict(self) -> dict:
        """Render back to the API mapping shape (GET /index/_mapping)."""
        root: dict = {}
        for path in sorted(self._mappers):
            mapper = self._mappers[path]
            if isinstance(mapper, (ObjectMapper,)):
                continue
            parts = path.split(".")
            # multi-fields render under "fields", not "properties"
            parent = ".".join(parts[:-1])
            if parent in self._multi_fields and parts[-1] in self._multi_fields[parent]:
                continue
            node = root
            for p in parts[:-1]:
                node = node.setdefault("properties", {}).setdefault(p, {})
            leaf = node.setdefault("properties", {}).setdefault(parts[-1], {})
            leaf.update(mapper.to_def())
            if path in self._multi_fields:
                leaf["fields"] = {sub: m.to_def()
                                  for sub, m in self._multi_fields[path].items()}
        out = {"properties": root.get("properties", {})}
        if self._meta:
            out["_meta"] = self._meta
        return out

    # -- document parsing ----------------------------------------------------
    def parse_document(self, doc_id: str, source: dict) -> ParsedDocument:
        """Parse a source document (reference: DocumentParser.parseDocument)."""
        if not isinstance(source, dict):
            raise MapperParsingError("document source must be an object")
        limit = getattr(self, "nested_objects_limit", None)
        if limit is not None:
            n_nested = self._count_nested_docs(source, "")
            if n_nested > limit:
                raise IllegalArgumentError(
                    f"The number of nested documents has exceeded the "
                    f"allowed limit of [{limit}]. This limit can be set by "
                    f"changing the [index.mapping.nested_objects.limit] "
                    f"index level setting.")
        parsed = ParsedDocument(doc_id, source)
        self._parse_object(source, "", parsed)
        return parsed

    def _count_nested_docs(self, obj: dict, prefix: str) -> int:
        """Count the Lucene sub-documents nested arrays expand into
        (DocumentParser nested-doc accounting)."""
        total = 0
        for k, v in obj.items():
            path = prefix + k
            mapper = self.get(path)
            is_nested = mapper is not None and \
                getattr(mapper, "type_name", "") == "nested"
            if isinstance(v, list):
                dict_items = [i for i in v if isinstance(i, dict)]
                if is_nested:
                    total += len(dict_items)
                for item in dict_items:
                    total += self._count_nested_docs(item, path + ".")
            elif isinstance(v, dict):
                if is_nested:
                    total += 1
                total += self._count_nested_docs(v, path + ".")
        return total

    def _parse_object(self, obj: dict, prefix: str, parsed: ParsedDocument) -> None:
        for key, value in obj.items():
            path = f"{prefix}{key}"
            if isinstance(value, dict) and self.get(path) is None or (
                    isinstance(value, dict) and isinstance(self.get(path), (ObjectMapper, NestedMapper))):
                self._parse_object(value, path + ".", parsed)
                continue
            if isinstance(value, dict) and isinstance(self.get(path), (
                    GeoPointFieldMapper, FlattenedFieldMapper,
                    HistogramFieldMapper, GeoShapeFieldMapper,
                    SparseVectorFieldMapper, RangeFieldMapperBase,
                    CompletionFieldMapper, JoinFieldMapper,
                    PercolatorFieldMapper, RankFeaturesFieldMapper)):
                self._parse_field(path, value, parsed)
                continue
            if isinstance(value, list) and value and isinstance(value[0], dict) \
                    and isinstance(self.get(path), (
                        GeoPointFieldMapper, FlattenedFieldMapper,
                        HistogramFieldMapper, GeoShapeFieldMapper,
                        SparseVectorFieldMapper, RangeFieldMapperBase,
                        CompletionFieldMapper, RankFeaturesFieldMapper)):
                # arrays of dict-valued field values (multi-valued ranges,
                # shapes, …) — each element is one field value, not an object
                self._parse_field(path, value, parsed)
                continue
            if isinstance(value, list) and value and isinstance(value[0], dict):
                # array of objects (nested docs stored flattened)
                for item in value:
                    if isinstance(item, dict):
                        self._parse_object(item, path + ".", parsed)
                continue
            self._parse_field(path, value, parsed)

    def _parse_field(self, path: str, value: Any, parsed: ParsedDocument) -> None:
        if isinstance(self.get_raw(path), AliasFieldMapper):
            raise MapperParsingError(f"Cannot write to a field alias [{path}].")
        mapper = self.get(path)
        if mapper is None:
            if value is None:
                return
            if not self.dynamic:
                return  # dynamic:false — unmapped fields not indexed, still in _source
            mapper = self._dynamic_mapper(path, value)
            if mapper is None:
                return
            self._put(path, mapper)
            parsed.dynamic_updates[path] = mapper.to_def()
            # dynamic strings get the reference's default text + .keyword multi-field
            if isinstance(mapper, TextFieldMapper):
                kw = KeywordFieldMapper(f"{path}.keyword", {"ignore_above": 256})
                self._multi_fields.setdefault(path, {})["keyword"] = kw
                self._put(f"{path}.keyword", kw)

        # dense_vector: the array IS the single value, not multi-values;
        # geo_point [lon, lat] numeric pairs too (GeoJSON order —
        # GeoPointFieldMapper parse() array form)
        if isinstance(mapper, DenseVectorFieldMapper):
            values = [value]
        elif (isinstance(mapper, GeoPointFieldMapper)
              and isinstance(value, (list, tuple)) and len(value) == 2
              and all(isinstance(v, (int, float)) and
                      not isinstance(v, bool) for v in value)):
            values = [value]
        else:
            values = value if isinstance(value, list) else [value]
        for v in values:
            if v is None:
                continue
            try:
                self._index_one(path, mapper, v, parsed)
            except MapperParsingError:
                # ignore_malformed: drop the unparseable VALUE, keep the doc
                # (IgnoreMalformedStoredValues; the field lands in _ignored)
                if not mapper.params.get("ignore_malformed"):
                    raise
                parsed.doc_values.setdefault("_ignored", [])
                if path not in parsed.doc_values["_ignored"]:
                    parsed.doc_values["_ignored"].append(path)
                    # _ignored is searchable (term/terms/exists) like the
                    # reference's IgnoredFieldMapper metadata field
                    parsed.terms.setdefault("_ignored", []).append(path)
                continue
            for sub_name, sub in self._multi_fields.get(path, {}).items():
                self._index_one(f"{path}.{sub_name}", sub, v, parsed)

    def _index_one(self, path: str, mapper: FieldMapper, v: Any, parsed: ParsedDocument) -> None:
        if isinstance(mapper, AliasFieldMapper):
            raise MapperParsingError(
                f"Cannot write to a field alias [{path}].")
        if isinstance(mapper, FlattenedFieldMapper):
            obj = mapper.coerce(v)
            root_terms = parsed.terms.setdefault(path, [])
            for key_path, leaf in mapper.leaves(obj):
                root_terms.append(leaf)
                parsed.terms.setdefault(f"{path}.{key_path}", []).append(leaf)
            parsed.doc_values[path] = obj
            return
        if isinstance(mapper, DenseVectorFieldMapper):
            if path in parsed.vectors:
                raise MapperParsingError(f"[{path}] only one vector per document")
            parsed.vectors[path] = mapper.coerce(v)
            return
        if isinstance(mapper, TextFieldMapper):
            tokens = mapper.analyze_positions(v)
            bucket = parsed.terms.setdefault(path, [])
            pos_map = parsed.term_positions.setdefault(path, {})
            base = parsed.field_lengths.get(path, 0)
            for t in tokens:
                bucket.append(t.term)
                pos_map.setdefault(t.term, []).append(base + t.position)
            parsed.field_lengths[path] = base + len(tokens)
            return
        terms = mapper.index_terms(v)
        if terms:
            parsed.terms.setdefault(path, []).extend(terms)
        dv = mapper.doc_value(v)
        if dv is not None:
            existing = parsed.doc_values.get(path)
            if existing is None:
                parsed.doc_values[path] = dv
            elif isinstance(existing, list):
                existing.append(dv)
            else:
                parsed.doc_values[path] = [existing, dv]

    def _dynamic_mapper(self, path: str, value: Any) -> Optional[FieldMapper]:
        probe = value[0] if isinstance(value, list) and value else value
        if isinstance(probe, bool):
            return BooleanFieldMapper(path, {})
        if isinstance(probe, int):
            return LongFieldMapper(path, {})
        if isinstance(probe, float):
            return FloatFieldMapper(path, {})
        if isinstance(probe, str):
            try:
                parse_date_millis(probe) if re.match(r"\d{4}-\d{2}-\d{2}", probe) else None
            except MapperParsingError:
                pass
            else:
                if re.match(r"\d{4}-\d{2}-\d{2}", probe):
                    return DateFieldMapper(path, {})
            return TextFieldMapper(path, {}, registry=self.registry)
        return None

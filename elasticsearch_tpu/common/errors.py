"""Exception hierarchy.

Parallels the reference's ElasticsearchException tree
(`server/src/main/java/org/elasticsearch/ElasticsearchException.java`) with the
subset of status-carrying exceptions the REST layer needs. Each exception maps
to an HTTP status so RestController can render structured error bodies.
"""

from __future__ import annotations


class SearchEngineError(Exception):
    """Base of all framework errors. Carries an HTTP status for the REST layer."""

    status = 500

    def __init__(self, message: str = "", **metadata):
        super().__init__(message)
        self.message = message
        self.metadata = metadata

    @property
    def error_type(self) -> str:
        # e.g. IndexNotFoundError -> index_not_found_exception, matching the
        # reference's snake_cased exception names in REST error bodies.
        name = type(self).__name__
        if name.endswith("Error"):
            name = name[: -len("Error")]
        out = []
        for i, ch in enumerate(name):
            if ch.isupper() and i > 0:
                out.append("_")
            out.append(ch.lower())
        return "".join(out) + "_exception"

    def to_dict(self) -> dict:
        d = {"type": self.error_type, "reason": self.message}
        d.update(self.metadata)
        return d

    def to_wrapped_dict(self) -> dict:
        """Top-level error shape with the root_cause chain (the REST layer
        and per-response msearch errors use this; per-ITEM bulk/mget errors
        stay bare, matching the reference)."""
        inner = self.to_dict()
        return {**inner, "root_cause": [dict(inner)]}


class IllegalArgumentError(SearchEngineError):
    status = 400


class SnapshotMissingError(SearchEngineError):
    status = 404


class ActionRequestValidationError(SearchEngineError):
    status = 400


class InvalidIndexNameError(SearchEngineError):
    status = 400


class IllegalStateError(SearchEngineError):
    status = 500


class ParseError(SearchEngineError):
    status = 400


class ParsingError(SearchEngineError):
    status = 400


class MapperParsingError(SearchEngineError):
    status = 400


class ValidationError(SearchEngineError):
    status = 400


class ActionRequestValidationError(SearchEngineError):
    """Aggregated request validation failures (reference:
    ActionRequestValidationException — "Validation Failed: 1: ...;")."""
    status = 400

    @classmethod
    def of(cls, failures) -> "ActionRequestValidationError":
        msg = "Validation Failed: " + " ".join(
            f"{i + 1}: {m};" for i, m in enumerate(failures))
        return cls(msg)


class ResourceNotFoundError(SearchEngineError):
    status = 404


class SearchContextMissingError(SearchEngineError):
    """Expired/unknown scroll or PIT context
    (SearchContextMissingException)."""
    status = 404


class IndexNotFoundError(ResourceNotFoundError):
    status = 404

    def __init__(self, index: str):
        super().__init__(f"no such index [{index}]", index=index)
        self.index = index


class DocumentMissingError(ResourceNotFoundError):
    status = 404


class ResourceAlreadyExistsError(SearchEngineError):
    status = 400


class VersionConflictError(SearchEngineError):
    """Optimistic concurrency failure (seq_no/primary_term or version mismatch).

    Reference: `index/engine/VersionConflictEngineException.java`.
    """

    @property
    def error_type(self) -> str:
        # the engine-layer name the REST layer exposes
        return "version_conflict_engine_exception"

    status = 409


class TooManyBucketsError(SearchEngineError):
    """search.max_buckets exceeded (MultiBucketConsumerService)."""
    status = 503


class CircuitBreakingError(SearchEngineError):
    status = 429


class NodeNotConnectedError(SearchEngineError):
    status = 503


class MasterNotDiscoveredError(SearchEngineError):
    status = 503


class ClusterBlockError(SearchEngineError):
    status = 503


class IndexClosedError(SearchEngineError):
    """Operation against a closed index (IndexClosedException)."""

    status = 400


class TaskCancelledError(SearchEngineError):
    status = 400


class QueryShardError(SearchEngineError):
    """Query cannot execute against this shard's mappings (reference:
    QueryShardException — e.g. `exists` on [_source])."""

    status = 400


class ArrayIndexOutOfBoundsError(SearchEngineError):
    """Shard-level execution failure inside an aggregator — notably HDR
    percentiles collecting a negative value (the reference's DoubleHistogram
    throws ArrayIndexOutOfBoundsException and fails the shard, Ref
    `HDRPercentilesAggregator`). Execution-class: coordinators record it as
    a per-shard failure instead of failing the whole request."""

    status = 500


class SearchPhaseExecutionError(SearchEngineError):
    status = 503

    def __init__(self, phase: str, message: str, shard_failures=()):
        super().__init__(message, phase=phase)
        self.phase = phase
        self.shard_failures = list(shard_failures)

    def to_dict(self) -> dict:
        d = super().to_dict()
        if self.shard_failures:
            d["failed_shards"] = [dict(f) for f in self.shard_failures]
        return d

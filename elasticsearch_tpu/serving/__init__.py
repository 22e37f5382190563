"""Query serving layer: micro-batching dispatch."""

from elasticsearch_tpu.serving.batcher import (
    BoundedBatcher, CombiningBatcher,
)

__all__ = ["BoundedBatcher", "CombiningBatcher"]

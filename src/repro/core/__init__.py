"""PageANN core: the paper's contribution as composable JAX modules."""
from repro.core.config import (
    AdaptiveParams,
    DeltaParams,
    FilterParams,
    MemoryBudget,
    MemoryMode,
    PageANNConfig,
    SearchParams,
)
from repro.core.delta import DeltaTier, MutableIndex
from repro.core.filter import Bag, FilterExpr, MetadataSchema, Num, Tag
from repro.core.index import PageANNIndex, recall_at_k
from repro.core.persist import IndexFormatError, load_index
from repro.core.protocol import MutableVectorIndex, VectorIndex

__all__ = [
    "AdaptiveParams",
    "Bag",
    "DeltaParams",
    "DeltaTier",
    "FilterExpr",
    "FilterParams",
    "IndexFormatError",
    "MemoryBudget",
    "MemoryMode",
    "MetadataSchema",
    "MutableIndex",
    "MutableVectorIndex",
    "Num",
    "PageANNConfig",
    "PageANNIndex",
    "SearchParams",
    "Tag",
    "VectorIndex",
    "load_index",
    "recall_at_k",
]

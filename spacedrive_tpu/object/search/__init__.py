"""Semantic search — per-library vector index + query plane.

The first *query-time* device workload: embeddings computed by the
media pipeline (ops/embed_jax) land in `object_embedding`, replicate
through the CRDT plane, and are scored here as one batched cosine
matmul per query (index.py).
"""

from .index import (  # noqa: F401
    LibraryIndex,
    drop_index,
    get_index,
    on_embeddings_applied,
    probe_for,
    query,
    refresh,
)

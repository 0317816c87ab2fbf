"""Retriever API v1 — the stable serving surface of the reproduction.

:class:`LemurRetriever` owns the index lifecycle (build / search / add /
with_backend / save / load); :class:`SearchParams` is the typed, hashable,
jit-static query-time knob object.  Per-backend build knobs live in the
``LemurConfig`` namespaces (``cfg.ivf``, ``cfg.muvera``, …) defined in
:mod:`repro.anns.params` and registered next to each backend in
:mod:`repro.anns.registry`.
"""
from repro.anns.params import (
    BruteforceBackendConfig,
    DessertBackendConfig,
    IVFBackendConfig,
    IVFSearchParams,
    MuveraBackendConfig,
    NoSearchParams,
    TokenPruningBackendConfig,
    TokenPruningSearchParams,
)
from repro.retriever.facade import (
    CorruptIndexError,
    LemurRetriever,
    xla_compile_count,
)
from repro.retriever.params import SearchParams
from repro.retriever.sharded import (
    MeshLemurRetriever,
    ShardedLemurRetriever,
    TrainedLemur,
)

__all__ = [
    "CorruptIndexError",
    "LemurRetriever",
    "MeshLemurRetriever",
    "ShardedLemurRetriever",
    "TrainedLemur",
    "SearchParams",
    "IVFSearchParams",
    "NoSearchParams",
    "TokenPruningSearchParams",
    "BruteforceBackendConfig",
    "IVFBackendConfig",
    "MuveraBackendConfig",
    "DessertBackendConfig",
    "TokenPruningBackendConfig",
    "xla_compile_count",
]

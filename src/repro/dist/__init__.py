"""Multi-device layer: sharding rule tables + the LEMUR corpus-sharded
serving/indexing steps.

* :mod:`repro.dist.sharding` — regex rule tables mapping parameter names to
  PartitionSpecs (``LM_RULES`` / ``LM_RULES_FFSLICE`` / ``RECSYS_RULES`` /
  ``GNN_RULES``), consumed by ``launch/cells.py``.
* :mod:`repro.dist.serve` — ``ShardedRetrievalState`` + the per-shard
  latent-scan/rerank/merge serve step and the zero-comms OLS index step;
  the user-facing wrapper is :meth:`repro.retriever.LemurRetriever.shard`.
"""
from repro.dist.serve import (
    ShardedRetrievalState,
    auto_axes,
    corpus_axes,
    default_k_prime_local,
    make_index_step,
    make_serve_step,
    n_corpus_shards,
    state_shardings,
)
from repro.dist.sharding import (
    GNN_RULES,
    LM_RULES,
    LM_RULES_FFSLICE,
    RECSYS_RULES,
    ShardingRules,
)

__all__ = [
    "GNN_RULES",
    "LM_RULES",
    "LM_RULES_FFSLICE",
    "RECSYS_RULES",
    "ShardedRetrievalState",
    "ShardingRules",
    "auto_axes",
    "corpus_axes",
    "default_k_prime_local",
    "make_index_step",
    "make_serve_step",
    "n_corpus_shards",
    "state_shardings",
]

"""Multi-device layer: sharding rule tables + the LEMUR corpus-sharded
serving/indexing steps.

* :mod:`repro.dist.sharding` — regex rule tables mapping parameter names to
  PartitionSpecs (``LM_RULES`` / ``LM_RULES_FFSLICE`` / ``RECSYS_RULES`` /
  ``GNN_RULES``), consumed by ``launch/cells.py``.
* :mod:`repro.dist.serve` — the serve steps over a doc-sharded corpus:
  ``ShardedIndexState`` (one one-chip index per shard, built by
  ``LemurRetriever.build(mesh=)``) with the one-chip pipeline per shard,
  and ``ShardedRetrievalState`` (the slot pool of
  ``LemurRetriever.shard``) with its latent scan and gather rerank; both
  end in the same all-gather merge.  Also the zero-comms OLS index step.
"""
from repro.dist.serve import (
    ShardedIndexState,
    ShardedRetrievalState,
    auto_axes,
    corpus_axes,
    default_k_prime_local,
    join_shards,
    make_index_step,
    make_serve_step,
    make_shard_candidates_step,
    make_shard_search_step,
    merge_topk,
    n_corpus_shards,
    shard_devices,
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
    "ShardedIndexState",
    "ShardedRetrievalState",
    "ShardingRules",
    "auto_axes",
    "corpus_axes",
    "default_k_prime_local",
    "join_shards",
    "make_index_step",
    "make_serve_step",
    "make_shard_candidates_step",
    "make_shard_search_step",
    "merge_topk",
    "n_corpus_shards",
    "shard_devices",
    "state_shardings",
]

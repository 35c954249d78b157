"""repro.data — data pipeline: synthetic skewed relations + tokenized LM batches."""
from .synthetic import (zipf_column, skewed_relation, skewed_join_dataset,
                        drifting_join_batch, pinned_skew_batch, chain_query,
                        mixed_workload)
from .pipeline import TokenPipeline, PipelineConfig

__all__ = ["zipf_column", "skewed_relation", "skewed_join_dataset",
           "drifting_join_batch", "pinned_skew_batch", "chain_query", "mixed_workload",
           "TokenPipeline", "PipelineConfig"]

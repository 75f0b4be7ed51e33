# Pallas TPU kernels (compiled on TPU, interpret mode on CPU) + jnp oracles.
# fused_scan / agg_scan: the served zone-mapped filter and aggregates;
# opd_filter / packed_filter / bitpack: the paper's SIMD filter pipeline;
# multi_filter: K predicates in one pass over packed words; merge_remap:
# compaction-time <src, ev> -> ev' XLA gather (+ bitpack re-pack for the
# 'jax_packed' compaction backend); bloom_probe: batched lookups;
# ssm_scan: serving recurrence.
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]

"""repro — a production-grade JAX framework reproducing and extending
"Beyond Similarity Search: A Unified Data Layer for Production RAG Systems".

Layers:
  repro.core        the paper's unified data layer (store/query/transactions/tenancy)
  repro.kernels     Pallas TPU kernels (arena_scan, decode_attention)
  repro.models      model zoo (LM dense/MoE, GNN, recsys)
  repro.training    optimizers, train loop, checkpointing, fault tolerance
  repro.serving     batched RAG serving engine
  repro.distributed sharding rules, collectives, gradient compression
  repro.configs     assigned architecture registry
  repro.launch      production mesh, multi-pod dry-run, train/serve drivers
"""

__version__ = "1.0.0"

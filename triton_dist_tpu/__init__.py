"""triton_dist_tpu — a TPU-native framework for computation–communication
overlapping kernels.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
ByteDance-Seed/Triton-distributed (see SURVEY.md at the repo root):

- one-sided tile-granular communication primitives over ICI/DCN remote DMA
  (``triton_dist_tpu.lang``) — the analogue of the reference's Distributed
  dialect + libshmem_device (reference: python/triton_dist/language/),
- a symmetric-workspace runtime over a ``shard_map`` mesh
  (``triton_dist_tpu.shmem``, reference: shmem/ + triton_dist/utils.py),
- fused overlapped operators: AllGather+GEMM, GEMM+ReduceScatter,
  GEMM+AllReduce, EP dispatch/combine, Ulysses and KV-allgather sequence
  parallelism, distributed flash-decode (``triton_dist_tpu.ops``,
  reference: python/triton_dist/kernels/),
- nn-style TP/EP/SP/PP layers (``triton_dist_tpu.layers``),
- Qwen3 dense/MoE models + an inference Engine (``triton_dist_tpu.models``),
- a distributed-aware autotuner with a persistent cache
  (``triton_dist_tpu.autotuner`` / ``triton_dist_tpu.tune``),
- an intra-kernel profiler with Perfetto export
  (``triton_dist_tpu.profiler``),
- a megakernel runtime executing a whole decode step as one persistent
  per-core Pallas kernel (``triton_dist_tpu.megakernel``).
"""

__version__ = "0.1.0"

from triton_dist_tpu.parallel.mesh import MeshContext, make_mesh  # noqa: F401
from triton_dist_tpu.utils.distributed import (  # noqa: F401
    dist_print,
    initialize_distributed,
    on_tpu,
    use_interpret,
)

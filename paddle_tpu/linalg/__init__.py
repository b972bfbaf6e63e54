"""paddle_tpu.linalg — distributed dense linear algebra at pod scale
(ROADMAP item 4; PAPERS "Large Scale Distributed Linear Algebra With
Tensor Processing Units").

The non-NN workload tier: SUMMA blocked matmul, blocked Cholesky,
blocked Householder QR, and power iteration, all expressed as Program
IR ops (``ops/linalg_ops.py``) over the existing dp x tp mesh — the
same NamedSharding/GSPMD machinery, executor compile cache, autotuner
(``tuning.decide_summa_panel`` / ``decide_linalg_block``), and static
verifier (the ``linalg`` blocked-layout pass) that serve training and
decoding. No shard ever materializes a full matrix: per-shard peak
memory stays O(N^2/P), modeled by :func:`per_shard_peak_bytes` and
enforced by :func:`assert_memory_contract`.

See docs/linalg.md for the panel schedule diagrams, the memory
contract, the autotuner key family, and the quantized-reduction
ablation (``tests/test_linalg.py``).
"""

from .api import (MemoryContractError, assert_memory_contract,  # noqa: F401
                  build_cholesky_program, build_matmul_program,
                  build_power_iter_program, build_qr_program, cholesky,
                  matmul, power_iteration, qr)
from .kernels import (axis_sizes_of, blocked_cholesky,  # noqa: F401
                      blocked_qr, default_block, default_panel,
                      legal_blocks, legal_panels, per_shard_peak_bytes,
                      power_iter_step, summa_matmul)

__all__ = ['matmul', 'cholesky', 'qr', 'power_iteration',
           'build_matmul_program', 'build_cholesky_program',
           'build_qr_program', 'build_power_iter_program',
           'summa_matmul', 'blocked_cholesky', 'blocked_qr',
           'power_iter_step', 'legal_panels', 'default_panel',
           'legal_blocks', 'default_block', 'axis_sizes_of',
           'per_shard_peak_bytes', 'assert_memory_contract',
           'MemoryContractError']

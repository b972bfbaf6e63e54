"""paddle_tpu.tuning — autotuned kernel selection (ROADMAP item 2).

Two pieces:

- a persisted **tuning table** (`table.py`): versioned JSON, keyed by
  device kind, holding the measured winner for every (op, shape, dtype)
  key — atomic writes, corrupted/stale tables ignored with a flight
  event, inspectable offline via ``tools/tuning_inspect.py``;
- the **autotuner** (`autotune.py`): on first sight of a key (with
  ``PADDLE_TPU_AUTOTUNE=on``) microbenchmarks the candidate variants —
  XLA vs Pallas, and the Pallas block-size grids — records the winner,
  and serves it to the kernel dispatch sites from then on. Explicit env
  gates (``PADDLE_TPU_USE_PALLAS``, ``PADDLE_TPU_BN_PALLAS``,
  ``PADDLE_TPU_PALLAS_BLOCK_K``) always override the table.
"""

from .autotune import (autotune_mode, current_table, decide,  # noqa: F401
                       decide_attention, decide_batch_norm,
                       decide_layer_norm, decide_linalg_block,
                       decide_matmul_dtype, decide_summa_panel,
                       device_kind, env_gate_set, reset, set_timer,
                       table_path)
from .table import FORMAT_VERSION, TuningTable  # noqa: F401

__all__ = ['autotune_mode', 'decide', 'decide_attention',
           'decide_batch_norm', 'decide_layer_norm',
           'decide_linalg_block', 'decide_matmul_dtype',
           'decide_summa_panel', 'device_kind', 'env_gate_set', 'reset',
           'set_timer', 'table_path', 'current_table', 'TuningTable',
           'FORMAT_VERSION']

"""paddle_tpu.serving.decode — autoregressive decode serving.

Continuous batching + paged KV cache + ragged paged attention over a
decoder-only LM: `DecodeEngine` admits requests into a fixed-shape
decode batch as others finish, KV pages come from a shared HBM pool
(`KVPool`) addressed through per-sequence block tables, and the
attention (ops/pallas/paged_attention.py) reads exactly the
pages each sequence owns at its true length. See docs/serving.md
(decode engine section); its speed is the benchmark's serving cells'
(benchmark/run.py, PERF_LEDGER.jsonl).
"""

from .engine import DecodeEngine  # noqa: F401
from .kv_pool import BlockTable, KVPool  # noqa: F401
from .model import (LMSpec, build_lm_programs,  # noqa: F401
                    kv_page_bytes, random_weights)
from .prefix_cache import PrefixCache  # noqa: F401
from .scheduler import (GenerationStream, Scheduler,  # noqa: F401
                        Sequence)
from .spec import NgramDraft  # noqa: F401

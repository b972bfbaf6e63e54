"""MFU and goodput accounting.

MFU (model FLOPs utilization) = observed FLOPs/s divided by the chip's
peak FLOPs/s — the lingua franca of TPU perf comparisons. FLOPs come
from XLA's own ``compiled.cost_analysis()`` of the step program (the
executor records them per compiled step when observability is on), so
the number reflects the program the hardware actually ran, not an
analytic model.

Goodput = productive training seconds / total run wall seconds. Time
spent compiling, checkpointing, restoring after a restart, or undoing
bad steps counts AGAINST the run: a job that spends 10% of its wall
clock recompiling after preemptions has 0.9 goodput no matter how fast
its steps are.
"""

import time

__all__ = ['PEAK_TFLOPS_BF16', 'device_peak_flops', 'cost_analysis_flops',
           'overlap_fraction', 'GoodputTracker']

# bf16 dense matmul peak per chip (TFLOP/s), keyed by the exact
# ``device_kind`` string jax reports on that chip. An entry is added
# when a chip has printed its string, with the source of the figure.
PEAK_TFLOPS_BF16 = {
    # TPU v5e. Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16.
    # The chip prints this string, not 'v5e' (chip_smoke.py, PR 21).
    'TPU v5 lite': 197.0,
}


def device_peak_flops(device=None):
    """Peak bf16 FLOP/s of `device` (default: jax's first device) from
    the table above; None off-TPU (a CPU has no peak worth a
    utilization). A TPU whose device_kind is not in the table is an
    error, not a default."""
    if device is None:
        import sys
        jax = sys.modules.get('jax')
        if jax is None:
            return None
        device = jax.devices()[0]
    if device.platform != 'tpu':
        return None
    kind = device.device_kind
    if kind not in PEAK_TFLOPS_BF16:
        raise KeyError(
            'no peak FLOP/s recorded for TPU device_kind %r; add it to '
            'observe.mfu.PEAK_TFLOPS_BF16 with its source' % kind)
    return PEAK_TFLOPS_BF16[kind] * 1e12


def overlap_fraction(step_seconds, compute_seconds, comm_seconds):
    """Fraction of the shorter leg hidden behind the longer one, from
    three wall-clock measurements: the combined step, the compute-only
    leg, and the communication-only leg. If nothing overlapped the step
    would take compute + comm; if the shorter leg were fully hidden it
    would take max(compute, comm) — so

        overlap = (compute + comm - step) / min(compute, comm)

    clamped to [0, 1]. Used for the bucketed backward/allreduce overlap
    gauge (``trainer.allreduce_overlap_fraction``); None on degenerate
    inputs (any leg non-positive, or a step faster than both legs can
    explain is still clamped, but a step of 0 is meaningless)."""
    try:
        s = float(step_seconds)
        c = float(compute_seconds)
        m = float(comm_seconds)
    except (TypeError, ValueError):
        return None
    if s <= 0 or c <= 0 or m <= 0:
        return None
    return max(0.0, min(1.0, (c + m - s) / min(c, m)))


def cost_analysis_flops(compiled):
    """FLOPs per execution from a jax Compiled object or its
    cost-analysis dict. None when XLA reports none."""
    ca = compiled
    if hasattr(ca, 'cost_analysis'):
        try:
            ca = ca.cost_analysis()
        except Exception:
            return None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get('flops', 0.0) or 0.0)
    return flops if flops > 0 else None


class GoodputTracker(object):
    """Productive-vs-overhead wall-time ledger for one run.

    begin() anchors the run start; step(seconds) credits productive
    time; overhead(kind, seconds) debits compile/checkpoint/restore/
    bad-step time. publish() writes the derived gauges into a metrics
    registry:

        run.wall_seconds         total wall since begin()
        run.productive_seconds   sum of credited step time
        run.productive_steps     number of credited steps
        run.goodput              productive / wall  (0..1)
        run.overhead_seconds{kind=...}  per-cause debit
    """

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self._productive = 0.0
        self._steps = 0
        self._overhead = {}

    def begin(self):
        if self._t0 is None:
            self._t0 = time.monotonic()

    @property
    def started(self):
        return self._t0 is not None

    def step(self, seconds, steps=1):
        self.begin()
        self._productive += float(seconds)
        self._steps += int(steps)

    def overhead(self, kind, seconds):
        self.begin()
        self._overhead[kind] = self._overhead.get(kind, 0.0) + float(
            seconds)

    def goodput(self):
        if self._t0 is None:
            return None
        wall = time.monotonic() - self._t0
        if wall <= 0:
            return None
        return min(1.0, self._productive / wall)

    def publish(self, registry):
        if self._t0 is None:
            return
        wall = max(time.monotonic() - self._t0, 1e-9)
        registry.gauge('run.wall_seconds').set(wall)
        registry.gauge('run.productive_seconds').set(self._productive)
        registry.gauge('run.productive_steps').set(self._steps)
        registry.gauge('run.goodput').set(min(1.0, self._productive / wall))
        g = registry.gauge('run.overhead_seconds')
        for kind, secs in self._overhead.items():
            g.set(secs, kind=kind)

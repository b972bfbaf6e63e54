#!/usr/bin/env python
"""Replica worker process — one engine, one PID, one port.

Spawned by ``serving.rpc.ProcessReplicaFactory`` (or by hand) with a
JSON config file::

    python tools/replica_worker.py --config /path/to/replica.json

The config describes the engine this process hosts::

    {"name": "r0", "kind": "serving",          # or "decode"
     "model_dir": "/tmp/model",                 # serving: saved model
     "engine": {"max_batch_size": 8, ...},      # engine kwargs
     "compute_delay_ms": 10.0,                  # serving: chaos floor
     "spec": {"vocab_size": 64, ...},           # decode: LMSpec kwargs
     "weights_npz": "/tmp/w.npz",               # decode: params
     "backend": "cpu",                          # cpu -> force_host_cpu
     "port": 0,                                 # 0 = ephemeral
     "port_file": "/tmp/r0.port",               # where to publish url
     "metrics_jsonl": "/tmp/run-r0.jsonl",      # JSONL beside parent's
     "host_label": "r0"}                        # observe record host

Boot sequence: build + warmup + start the engine, start the observe
diagnostics HTTP server (which carries /readyz, /metrics, /statusz AND
— via ``serving.rpc.serve_engine`` — the POST control plane:
submit/generate/drain/shutdown/state/kv), then atomically publish
``{"url", "port", "pid"}`` to ``port_file``. The parent treats that
file appearing as "worker is up"; /readyz flipping 200 as "worker is
serving". The main loop just heartbeats worker.* gauges into the
JSONL until a remote /rpc/shutdown (or SIGTERM) lands, then exits 0.

Env reads live inside functions only (tools/repo_lint.py enforces the
same env-scoped rule here as for serving/rpc.py); the one env WRITE —
``PADDLE_TPU_OBSERVE_HOST`` from ``host_label`` — happens before any
paddle_tpu import so every metrics record this process emits carries
the replica name as its ``host``.
"""

import argparse
import json
import os
import signal
import sys
import threading


def _publish_port_file(path, doc):
    """Atomic write (tmp + rename): the parent polling this file never
    sees a torn JSON."""
    tmp = '%s.tmp.%d' % (path, os.getpid())
    with open(tmp, 'w') as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class _DelayPredictor(object):
    """Fixed per-batch compute floor (same duck-type as
    tests/chaos.py's ChaosPredictor) so a worker's capacity does not
    depend on the machine."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def predict(self, feed):
        import time
        out = self._inner.predict(feed)
        if self._delay_s:
            time.sleep(self._delay_s)
        return out


def _build_engine(cfg):
    kind = cfg.get('kind', 'serving')
    name = cfg.get('name') or 'worker-%d' % os.getpid()
    engine_kw = dict(cfg.get('engine') or {})
    if kind == 'decode':
        import numpy as np

        from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,
                                               random_weights)
        spec = LMSpec(**(cfg.get('spec') or {}))
        if cfg.get('weights_seed') is not None:
            # deterministic init: every process seeding the same way
            # holds bit-identical params (a subprocess replica's
            # bit identity with an in-process engine rides on this)
            engine_kw.setdefault(
                'weights', random_weights(spec,
                                          seed=int(cfg['weights_seed'])))
        eng = DecodeEngine(spec, name=name, **engine_kw)
        wpath = cfg.get('weights_npz')
        if wpath:
            with np.load(wpath) as npz:
                eng.load_weights({k: npz[k] for k in npz.files})
        return eng
    if kind == 'serving':
        from paddle_tpu.inference import create_predictor
        from paddle_tpu.serving import ServingEngine
        pred = create_predictor(cfg['model_dir'])
        delay_ms = float(cfg.get('compute_delay_ms') or 0.0)
        if delay_ms:
            pred = _DelayPredictor(pred, delay_ms / 1000.0)
        return ServingEngine(pred, name=name, **engine_kw)
    raise ValueError('unknown replica kind %r' % kind)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', required=True,
                    help='path to the replica JSON config')
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    name = cfg.get('name') or 'worker-%d' % os.getpid()

    # stamp BEFORE any paddle_tpu import: every observe record this
    # process writes carries the replica name as its host field
    os.environ['PADDLE_TPU_OBSERVE_HOST'] = str(
        cfg.get('host_label') or name)

    if cfg.get('backend', 'cpu') == 'cpu':
        from paddle_tpu.core.platform_boot import force_host_cpu
        force_host_cpu()
    from paddle_tpu.core.platform_boot import arm_compile_cache
    arm_compile_cache()

    from paddle_tpu import observe
    from paddle_tpu.serving import rpc

    if cfg.get('metrics_jsonl') or cfg.get('trace_json'):
        observe.enable(jsonl=cfg.get('metrics_jsonl'),
                       trace=cfg.get('trace_json'),
                       every_secs=float(cfg.get('flush_every_s', 0.25)))
    # label this process's span track for the merged fleet Perfetto
    # view (tools/fleet_trace.py): pid -> replica name
    observe.spans().set_process_name(name)

    engine = _build_engine(cfg)
    if callable(getattr(engine, 'warmup', None)):
        engine.warmup()
    engine.start()

    stop = threading.Event()
    binding = rpc.serve_engine(engine, on_shutdown=stop.set)
    # order matters: install OUR stop handler first, THEN arm the
    # flight recorder — its SIGTERM handler dumps the postmortem and
    # chains to the previously installed handler (stop.set), so a
    # SIGTERM both leaves the dump AND exits the main loop cleanly
    terminated = threading.Event()

    def _on_sigterm(*_):
        terminated.set()
        stop.set()
    signal.signal(signal.SIGTERM, _on_sigterm)
    if cfg.get('flight_dump'):
        observe.arm_flight(path=cfg['flight_dump'])

    srv = observe.serve(port=int(cfg.get('port', 0)))
    observe.set_gauge('worker.up', 1, replica=name)
    if cfg.get('port_file'):
        _publish_port_file(cfg['port_file'],
                           {'url': srv.url, 'port': srv.port,
                            'pid': os.getpid(), 'name': name})

    # heartbeat loop: worker.* gauges land in the JSONL so the parent's
    # metrics_report --fleet renders a per-process census; on a
    # snapshot cadence the flight ring re-dumps to the controller-known
    # path, so even a SIGKILL (no handler runs) leaves the controller a
    # recent postmortem of this worker's final seconds
    import time as _time
    snap_every = float(cfg.get('postmortem_snapshot_s', 1.0))
    last_snap = _time.monotonic()
    try:
        while not stop.wait(0.25):
            observe.set_gauge('worker.ready', int(bool(engine.ready())),
                              replica=name)
            observe.set_gauge('worker.queue_depth',
                              int(engine.queue_depth()), replica=name)
            observe.maybe_flush()
            if cfg.get('flight_dump') and \
                    _time.monotonic() - last_snap >= snap_every:
                last_snap = _time.monotonic()
                observe.flight_dump('heartbeat_snapshot')
    finally:
        binding.close()
        try:
            engine.shutdown(drain=False)   # idempotent post-/rpc/shutdown
        except Exception:
            pass
        observe.set_gauge('worker.up', 0, replica=name)
        if cfg.get('flight_dump') and not terminated.is_set():
            # a SIGTERM already dumped with reason='sigterm' (via the
            # arm_flight handler) — don't overwrite that with a clean
            # worker_exit dump
            observe.flight_dump('worker_exit')
        observe.stop_serving()
        observe.disable()                  # exports trace_json if set
    return 0


if __name__ == '__main__':
    sys.exit(main())

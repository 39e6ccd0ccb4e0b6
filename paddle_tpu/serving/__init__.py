"""AOT-compiled serving engine (the `paddle/fluid/inference` parity
tentpole): per-bucket zero-compile serve graphs, paged KV-cache with
buffer donation, continuous batching, stdlib HTTP front end.

Quick start::

    from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,
                                    init_params, save_served_model,
                                    load_engine)

    spec = ModelSpec(vocab_size=512, hidden=64, layers=2, heads=4)
    engine = ServingEngine(spec, init_params(spec), ServeConfig.from_env())
    tokens = engine.generate([[5, 9, 2]], max_new_tokens=8)[0]

    # or serve a directory over HTTP:
    save_served_model("/tmp/m", spec, init_params(spec))
    from paddle_tpu.serving.http import ServeHTTPServer
    ServeHTTPServer(load_engine("/tmp/m")).start()

Module map: :mod:`.model` (pure serve-side decoder fns over paged KV),
:mod:`.experts` (routed SwiGLU experts), :mod:`.ssm` (selective
state-space mixer: the scan over a prompt, the one-token update),
:mod:`.kv_cache` (block-pool page allocator, state slots + admission
reservations),
:mod:`.engine` (AOT program ladder, compile sentinel, weight swap),
:mod:`.scheduler` (continuous batching), :mod:`.http` (front end).
"""
from .model import ModelSpec, init_params, prefill_step, decode_step
from .kv_cache import PagePool, KVPoolExhausted, NULL_PAGE
from .engine import (ServeConfig, ServingEngine, save_served_model,
                     load_engine, is_served_model_dir, SERVE_CONFIG_NAME)
from .scheduler import (ContinuousScheduler, GenerationStream,
                        EngineSaturated, RequestShed, RequestCancelled,
                        DeadlineExceeded, WATCHDOG_EXIT_CODE)

__all__ = [
    "ModelSpec", "init_params", "prefill_step", "decode_step",
    "PagePool", "KVPoolExhausted", "NULL_PAGE",
    "ServeConfig", "ServingEngine", "save_served_model", "load_engine",
    "is_served_model_dir", "SERVE_CONFIG_NAME",
    "ContinuousScheduler", "GenerationStream", "EngineSaturated",
    "RequestShed", "RequestCancelled", "DeadlineExceeded",
    "WATCHDOG_EXIT_CODE",
]

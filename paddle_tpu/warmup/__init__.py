"""paddle_tpu.warmup — persistent compile cache + AOT warmup manifests.

Kills cold start on both ends of the lifecycle:

- **Persistent compile cache** (``persistent.py``): one switch points
  JAX's on-disk compilation cache at a framework-version+backend-keyed
  directory, with corruption-tolerant fallback and ``warmup.cache.*``
  hit/miss/bytes telemetry.
- **Warmup manifests** (``manifest.py``): ``capture()`` records every
  distinct compiled signature of a run — serving bucket keys, hapi
  train/eval step signatures, Predictor feed keys — into a JSON manifest.
- **AOT prebuild** (``prebuild.py``): ``prebuild(manifest, ...)`` replays
  the manifest with abstract ``ShapeDtypeStruct`` args ahead of traffic,
  populating the in-process caches and the persistent cache.

Recipe::

    from paddle_tpu import warmup, serving

    warmup.enable_persistent_cache('/var/cache/paddle_tpu')

    # capture run (once, e.g. in staging)
    with warmup.capture() as man:
        engine = serving.InferenceEngine(net, max_batch_size=64)
        ... live or synthetic traffic ...
    man.save('warmup.json')

    # every later process: first request runs an already-built program
    engine = serving.InferenceEngine(net, max_batch_size=64,
                                     warmup='warmup.json')

The serving engines and hapi Model turn the persistent cache on by
themselves (``ensure_persistent_cache``): at ``$JAX_COMPILATION_CACHE_DIR``
when the environment sets it, else at ``<repo>/.jax_cache``.
"""
from .manifest import (Manifest, array_sig, capture, capture_start,  # noqa: F401
                       capture_stop, capturing, eval_step_entry,
                       generation_entry, predictor_entry, record,
                       serving_bucket_entry, train_step_entry)
from .persistent import (DEFAULT_CACHE_DIR, cache_stats,  # noqa: F401
                         disable_persistent_cache,
                         enable_persistent_cache, ensure_persistent_cache,
                         persistent_cache_dir)
from .prebuild import all_buckets_manifest, prebuild  # noqa: F401

__all__ = [
    'Manifest', 'capture', 'capture_start', 'capture_stop', 'capturing',
    'record', 'array_sig', 'serving_bucket_entry', 'train_step_entry',
    'eval_step_entry', 'predictor_entry', 'generation_entry',
    'enable_persistent_cache', 'disable_persistent_cache',
    'ensure_persistent_cache', 'persistent_cache_dir', 'cache_stats',
    'DEFAULT_CACHE_DIR',
    'prebuild', 'all_buckets_manifest',
]

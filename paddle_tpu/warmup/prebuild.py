"""AOT prebuild: replay a warmup manifest ahead of traffic.

Each manifest entry is compiled with ``jit(...).lower(abstract).compile()``
over ``jax.ShapeDtypeStruct`` arguments — no real data, no device math.
Compiling through the *same* jit callables the live path uses means the
executable lands in their in-process tracing caches (a later real call with
matching avals neither retraces nor recompiles), and serving/Predictor
entries go further: the AOT ``Compiled`` object itself is seeded into the
bucket/shape caches, so live traffic reports literally zero compiles. When
the persistent cache (``persistent.py``) is enabled, every prebuilt
executable is also written to disk for the *next* process.

Entry dispatch:

- ``serving_bucket`` → ``engine=``: build + AOT-compile the bucket
  executable and ``put()`` it into the engine's ``BucketCompileCache``.
- ``train_step`` / ``accum_step`` → ``model=``: compile the hapi train step
  (or accum micro-step + apply) against abstract params/opt-state/PRNG-key
  avals — the training RNG stream is never consumed.
- ``eval_step`` → ``model=``: compile the eval/predict step.
- ``predictor`` → ``predictor=``: compile the padded-feed executable and
  seed ``Predictor._compiled``.
- ``gen_prefill`` / ``gen_decode`` → ``generation=``: compile the
  continuous-batching GenerationEngine's executables (the fixed-slot
  decode step, and the padded batch-1 prefill at the entry's ``body``
  width) after verifying the manifest's slot/page geometry matches the
  live engine.

Entries with no matching target are counted ``untargeted`` and skipped;
stale entries (shapes the current network can no longer trace) are warned
about and skipped — a manifest from last week must never crash today's
deploy. Telemetry: ``warmup.prebuild_ms`` histogram,
``warmup.prebuilt_total`` / ``warmup.prebuild_skipped`` counters.
"""
import concurrent.futures
import os
import time
import warnings

import jax
import numpy as np

from .. import observability as _obs
from .manifest import Manifest, _sig_from_json, serving_bucket_entry


COMPILE_THREADS = 4     # a generation engine's executables side by side


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(d) for d in shape),
                                np.dtype(dtype))


def _tree_structs(tree):
    """Abstract (shape, dtype) skeleton of a pytree of arrays. Leaves
    committed to a multi-device mesh keep their NamedSharding (via
    parallel.mesh_engine.sharded_structs): an executable AOT-compiled for
    a mesh-sharded engine must expect exactly the placements the live
    path passes, or the first real call would recompile."""
    from ..parallel.mesh_engine import sharded_structs
    return sharded_structs(tree)


def _key_struct():
    """Aval of a PRNG key WITHOUT consuming the global RNG stream —
    prebuild must not perturb bit-exact training/resume behaviour."""
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _scalar_f32():
    return jax.ShapeDtypeStruct((), np.float32)


def _perf_analyze(label, compiled):
    """Publish the prebuilt executable's cost/memory analysis under the SAME
    label the live path uses, so perf.* series exist before first traffic
    and a later live ``note_step`` joins them into an MFU."""
    if _obs.enabled() and _obs.perf.analyzed(label) is None:
        _obs.perf.analyze_compiled(label, compiled)


# ---- per-kind prebuilders --------------------------------------------------

def _prebuild_bucket(engine, entry):
    bucket = int(entry['bucket'])
    sig = _sig_from_json(entry['inputs'])
    # The live path only ever queries at the engine's own precision; a
    # manifest captured at another precision still warms this engine's key.
    precision = engine._precision
    if engine._cache.peek(bucket, sig, precision) is not None:
        return False
    if bucket > engine.max_batch_size:
        raise ValueError(f'bucket {bucket} exceeds engine max_batch_size '
                         f'{engine.max_batch_size}')
    fn = engine._build(bucket, sig, precision)
    params = _tree_structs(engine._params)
    buffers = _tree_structs(engine._buffers)
    xs = [_struct((bucket,) + shape, dtype) for shape, dtype in sig]
    compiled = fn.lower(params, buffers, *xs).compile()
    _perf_analyze(f'serving.bucket{bucket}', compiled)
    return engine._cache.put(bucket, sig, precision, compiled)


def _opt_state_structs(model, param_structs):
    if getattr(model, '_opt_state', None) is not None:
        return _tree_structs(model._opt_state)
    if getattr(model, '_tstate', None) is not None:
        return _tree_structs(model._tstate.opt_state)
    return jax.eval_shape(model._optimizer.functional_init, param_structs)


def _prebuild_train(model, entry):
    if model._optimizer is None or model._loss is None:
        raise RuntimeError('prepare(optimizer, loss) must run before '
                           'train-step warmup')
    model._enter_mode(True)
    mode_key = (model._mode_sig(), model._amp_sig())
    fns = model._train_steps.get(mode_key)
    if fns is None:
        model._asp_sig = model._asp_signature()
        fns = model._build_train_step()
        model._train_steps[mode_key] = fns
    step, accum_step, apply_accum = fns
    params = _tree_structs(model._params_dict())
    buffers = _tree_structs(model._buffers_dict())
    inputs = tuple(_struct(s, d)
                   for s, d in _sig_from_json(entry.get('inputs') or []))
    labels = tuple(_struct(s, d)
                   for s, d in _sig_from_json(entry.get('labels') or []))
    key = _key_struct()
    opt_state = _opt_state_structs(model, params)
    if entry['kind'] == 'accum_step':
        _perf_analyze('hapi.accum_step',
                      accum_step.lower(params, buffers, params, key, inputs,
                                       labels).compile())
        _perf_analyze('hapi.apply_accum',
                      apply_accum.lower(params, opt_state, params,
                                        _scalar_f32(),
                                        _scalar_f32()).compile())
    else:
        _perf_analyze('hapi.train_step',
                      step.lower(params, buffers, opt_state, key,
                                 _scalar_f32(), inputs, labels).compile())
    return True


def _prebuild_eval(model, entry):
    model._enter_mode(False)
    in_sig = _sig_from_json(entry.get('inputs') or [])
    lab_sig = _sig_from_json(entry.get('labels') or [])
    cache_key = (model._mode_sig(), model._amp_sig(), in_sig, lab_sig)
    step = model._eval_steps.get(cache_key)
    if step is None:
        step = model._build_eval_step()
        model._eval_steps[cache_key] = step
    params = _tree_structs(model._params_dict())
    buffers = _tree_structs(model._buffers_dict())
    inputs = tuple(_struct(s, d) for s, d in in_sig)
    labels = tuple(_struct(s, d) for s, d in lab_sig)
    _perf_analyze('hapi.eval_step',
                  step.lower(params, buffers, _key_struct(), inputs,
                             labels).compile())
    return True


def _prebuild_generation(engine, entry):
    """AOT-compile one GenerationEngine executable (gen_prefill/gen_decode).
    The manifest's geometry must match the live engine — a mismatched
    entry is stale (caught by the strict/skip machinery), never silently
    compiled at the wrong shapes. -> False where the engine holds it
    already, else its compile, still to run, as a callable."""
    kind = entry['kind']
    geom = {'slots': engine.num_slots, 'page_size': engine.page_size,
            'num_pages': engine.num_pages,
            'prefill_width': engine.prefill_width,
            'table_width': engine.p_max}
    for k, v in geom.items():
        got = int(entry.get(k, v))
        if got != v:
            raise ValueError(
                f'generation entry {k}={got} does not match the live '
                f'engine ({k}={v})')
    pf, st = engine._fns_pair()
    if kind == 'gen_prefill':
        # the width this entry's prompts are padded to (an entry written
        # before the engine chose among widths names none: the widest)
        body = int(entry.get('body', engine.prefill_width))
        if body not in engine.prefill_widths:
            raise ValueError(
                f'generation entry body={body} is none of the live '
                f"engine's prefill widths {engine.prefill_widths}")
        name, label, fn = f'gen_prefill.{body}', f'gen.prefill.{body}', pf
        shapes = (
            _struct((1, body), np.int32),
            _struct((1,), np.int32),    # start (prefix-cache tail offset)
            _struct((1,), np.int32),    # valid
            _tree_structs(engine._tables(1)),
            _struct((1,), np.uint32))
    else:
        s = engine.num_slots
        name, label, fn = kind, 'gen.decode', st
        shapes = (
            _tree_structs(engine._no_prev),     # the previous step's tokens
            _struct((s,), np.int32),    # the host's tokens ...
            _struct((s,), np.bool_),    # ... and which rows take them
            _struct((s,), np.int32),
            _tree_structs(engine._tables(s)),
            _struct((s,), np.uint32))
    if name in engine._aot:
        return False
    # traced and lowered here; the compiles (or the reads from the
    # persistent cache) of an engine's executables, a prefill a width
    # among them, run side by side once all are lowered (``prebuild``)
    lowered = fn.lower(_tree_structs(engine._params),
                       _tree_structs(engine._pool), *shapes)

    def compile_it():
        compiled = lowered.compile()
        _perf_analyze(label, compiled)
        # hand the AOT executable to the engine's live path: jit's own call
        # cache would rebuild the executable on the first real invocation
        # even with the trace warm, costing one full XLA compile per fn
        engine._aot[name] = compiled
        return True
    return compile_it


def _prebuild_predictor(predictor, entry):
    key = _sig_from_json(entry['inputs'])
    fn = predictor._compiled.get(key)
    if fn is not None and not hasattr(fn, 'lower'):
        return False  # already an AOT executable
    fn = predictor._get_compiled(key)
    structs = [_struct(shape, dtype) for shape, dtype in key]
    compiled = fn.lower(*structs).compile()
    predictor._compiled[key] = compiled
    label = 'predictor.' + ';'.join(
        'x'.join(map(str, shape)) or 'scalar' for shape, _ in key)
    _perf_analyze(label, compiled)
    return True


# ---- driver ----------------------------------------------------------------

def prebuild(manifest, *, engine=None, model=None, predictor=None,
             generation=None, strict=False):
    """Replay ``manifest`` (a Manifest or a path to one) against the given
    targets. Returns a report dict: entries / prebuilt / already_cached /
    skipped / untargeted / total_ms (+ ``skips`` reasons).

    With ``strict=False`` (default) a stale entry — a signature the current
    network can no longer build — is warned about and skipped; with
    ``strict=True`` it raises."""
    if isinstance(manifest, (str, os.PathLike)):
        manifest = Manifest.load(manifest)
    handlers = {}
    if engine is not None:
        handlers['serving_bucket'] = lambda e: _prebuild_bucket(engine, e)
    if model is not None:
        handlers['train_step'] = lambda e: _prebuild_train(model, e)
        handlers['accum_step'] = lambda e: _prebuild_train(model, e)
        handlers['eval_step'] = lambda e: _prebuild_eval(model, e)
    if predictor is not None:
        handlers['predictor'] = lambda e: _prebuild_predictor(predictor, e)
    if generation is not None:
        handlers['gen_prefill'] = \
            lambda e: _prebuild_generation(generation, e)
        handlers['gen_decode'] = \
            lambda e: _prebuild_generation(generation, e)

    # Prebuild flips the network's train/eval mode to trace each step kind;
    # put it back so a live fit/eval after warmup starts where it left off.
    orig_mode = model._net_mode if model is not None else None

    report = {'entries': len(manifest), 'prebuilt': 0, 'already_cached': 0,
              'skipped': 0, 'untargeted': 0, 'skips': []}
    t_start = time.perf_counter()

    def settle(kind, t0, build):
        """One entry's outcome into the report. ``build()`` -> whether it
        built anything, or the rest of its work as a callable, which is
        handed back for a worker thread."""
        try:
            built = build()
        except Exception as e:
            if strict:
                raise
            warnings.warn(
                f'paddle_tpu.warmup: skipping stale manifest entry '
                f'({kind}): {e!r}', RuntimeWarning, stacklevel=3)
            _obs.counter('warmup.prebuild_skipped',
                         {'kind': str(kind)}).inc()
            report['skipped'] += 1
            report['skips'].append(f'{kind}: {e}')
            return None
        if callable(built):
            return built
        if built:
            elapsed_ms = 1e3 * (time.perf_counter() - t0)
            _obs.histogram('warmup.prebuild_ms').observe(elapsed_ms)
            _obs.counter('warmup.prebuilt_total', {'kind': str(kind)}).inc()
            report['prebuilt'] += 1
        else:
            report['already_cached'] += 1
        return None

    try:
        lowered = []
        for entry in manifest:
            kind = entry.get('kind')
            handler = handlers.get(kind)
            if handler is None:
                report['untargeted'] += 1
                continue
            t0 = time.perf_counter()
            rest = settle(kind, t0, lambda: handler(entry))
            if rest is not None:
                lowered.append((kind, t0, rest))
        # every trace and lowering first, on this thread alone (Python's:
        # a worker beside it slows both, measured); then the compiles, or
        # the reads from the persistent cache, side by side
        with concurrent.futures.ThreadPoolExecutor(COMPILE_THREADS) as pool:
            for kind, t0, future in [(kind, t0, pool.submit(rest))
                                     for kind, t0, rest in lowered]:
                settle(kind, t0, future.result)
    finally:
        if model is not None and orig_mode is not None:
            model._enter_mode(orig_mode)
    # mark the targets warm for the telemetry plane's /readyz probes
    for target in (engine, generation):
        if target is not None and hasattr(target, '_warmed'):
            target._warmed = True
    report['total_ms'] = round(1e3 * (time.perf_counter() - t_start), 3)
    return report


# ---- manifest synthesis ----------------------------------------------------

def _normalize_example_spec(spec):
    """Normalize a per-example input spec into ((shape, dtype), ...).

    Accepts: ``(shape, dtype)`` pairs (per-example, no batch dim),
    ``static.InputSpec`` objects or ``{'shape': .., 'dtype': ..}`` dicts
    (batched — the leading dim is stripped). Any remaining dynamic dim is
    an error: warmup needs concrete per-example shapes."""
    if spec is None:
        return None
    out = []
    for s in spec:
        if isinstance(s, dict):
            shape, dtype = tuple(s['shape'])[1:], s.get('dtype', 'float32')
        elif hasattr(s, 'shape') and hasattr(s, 'dtype') and \
                not isinstance(s, (tuple, list)):
            shape, dtype = tuple(s.shape)[1:], s.dtype
        else:
            shape, dtype = s
            shape = tuple(shape)
        if any(d is None or int(d) < 0 for d in shape):
            raise ValueError(
                f'input spec {s!r} has dynamic non-batch dims; warmup '
                'needs concrete per-example shapes')
        out.append((tuple(int(d) for d in shape), np.dtype(dtype).name))
    return tuple(out)


def all_buckets_manifest(engine, input_spec=None):
    """Synthesize a manifest covering the engine's whole bucket ladder for
    one input signature — warmup without a prior capture run. The spec
    comes from ``input_spec`` or from what the engine inferred from its
    backend (hapi ``Model._inputs`` / ``Predictor`` metadata)."""
    from ..serving.bucketing import bucket_sizes
    sig = _normalize_example_spec(
        input_spec if input_spec is not None
        else getattr(engine, '_example_spec', None))
    if sig is None:
        raise ValueError(
            "warmup='all_buckets' needs an input signature: pass "
            "input_spec= (e.g. [((8,), 'float32')] per example) or build "
            'the engine from a hapi Model / Predictor with input specs')
    manifest = Manifest()
    for bucket in bucket_sizes(engine.max_batch_size):
        manifest.add(serving_bucket_entry(bucket, sig, engine._precision,
                                          max_batch=engine.max_batch_size))
    return manifest

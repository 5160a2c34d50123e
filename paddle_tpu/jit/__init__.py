"""paddle.jit — dy2static. Reference: python/paddle/jit/ + fluid/dygraph/jit.py.

TPU-native: ``to_static`` doesn't rewrite Python AST into ProgramDesc like the
reference (python/paddle/fluid/dygraph/dygraph_to_static); it traces the
function through jax.jit — the jaxpr IS the static program, and XLA compiles
it for TPU. Differentiable: the compiled callable is registered on the eager
tape via jax.vjp, so ``loss.backward()`` crosses the jit boundary.
``jit.save``/``jit.load`` export params + StableHLO; the inference engine
(paddle_tpu.inference) AOT-compiles the loaded program.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..nn.layer_base import Layer, functional_call, param_arrays, buffer_arrays
from ..static.input_spec import InputSpec
from ..tensor.random import rng_scope, next_key


class TracedLayer:
    pass


def _trace_state_clean():
    """True when no jax trace (jit/grad/vmap/export) is active (private
    API, checked against jax 0.9.0)."""
    from jax._src.core import trace_state_clean
    return trace_state_clean()


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    try:
        hash(v)
        return v
    except TypeError:
        return repr(v)


class StaticFunction:
    """Compiled wrapper around a Python function / Layer.forward."""

    def __init__(self, function, input_spec=None):
        if not getattr(function, '_not_to_static', False):
            # dy2static pass: rewrite tensor-conditioned if/while into
            # lax.cond / lax.while_loop (no-op for control-flow-free fns)
            from .dy2static import convert_control_flow
            function = convert_control_flow(function)
        self._fn = function
        self._input_spec = input_spec
        self._layer = getattr(function, '__self__', None)
        self._cache = {}       # cache_key -> (jitted_pure, holder)

    def __get__(self, obj, objtype=None):
        # descriptor protocol: `@to_static` in a CLASS BODY (the reference
        # idiom) must bind `self` like a method — Layer.__call__ then
        # reaches __call__ with the instance first, and _bound_layer
        # routes through the layer path (r4b). A plain closure (not
        # functools.partial) so jit.save can still read the decoration
        # metadata off layer.forward.
        if obj is None:
            return self
        sf = self

        def bound(*args, **kwargs):
            return sf(obj, *args, **kwargs)
        bound.__self__ = obj
        bound._input_spec = self._input_spec
        bound._static_function = sf
        return bound

    def _cache_for(self, layer):
        # one class-level StaticFunction serves EVERY instance under
        # class-body decoration, and _build bakes the instance into the
        # compiled closure — so compiled entries must be per-instance
        # (review r4b: instance B silently ran A's trace). WeakKey: a
        # dropped instance must not pin its compiled programs.
        if layer is None:
            return self._cache
        import weakref
        if not hasattr(self, '_inst_caches'):
            self._inst_caches = weakref.WeakKeyDictionary()
        cache = self._inst_caches.get(layer)
        if cache is None:
            cache = self._inst_caches[layer] = {}
        return cache

    def _bound_layer(self, args):
        if self._layer is not None:
            return self._layer, args
        if args and isinstance(args[0], Layer):
            return args[0], args[1:]
        return None, args

    def _build(self, layer, training, tensor_like, static_ctx, kwargs):
        fn = self._fn
        if layer is not None and self._layer is None:
            fn = functools.partial(self._fn, layer)
        pnames = static_ctx['pnames']
        bnames = static_ctx['bnames']
        static_args = static_ctx['static_args']   # {pos: value}
        nargs = static_ctx['nargs']
        holder = {'treedef': None, 'n_out': 0}

        def pure(rng_key, buf_vals, *dyn):
            dyn_args = dyn[:len(tensor_like)]
            p_vals = dyn[len(tensor_like):]
            full_args = [None] * nargs
            for pos, v in static_args.items():
                full_args[pos] = v
            for i, idx in enumerate(tensor_like):
                full_args[idx] = dyn_args[i]
            with rng_scope(rng_key):
                if layer is not None:
                    pd = dict(zip(pnames, p_vals))
                    bd = dict(zip(bnames, buf_vals))
                    was = layer.training
                    for l in layer.sublayers(include_self=True):
                        l.training = training
                    try:
                        from ..nn.layer_base import functional_call_method
                        out, new_buf = functional_call_method(
                            layer, fn, pd, bd, *full_args, **kwargs)
                    finally:
                        for l in layer.sublayers(include_self=True):
                            l.training = was
                    new_buf_vals = [new_buf[n] for n in bnames]
                else:
                    targs = [Tensor(a) if isinstance(a, (jax.Array, jax.core.Tracer,
                                                         np.ndarray)) else a
                             for a in full_args]
                    from ..core.tensor import no_grad_ctx
                    with no_grad_ctx():
                        res = fn(*targs, **kwargs)
                    out = jax.tree_util.tree_map(
                        lambda x: x._value if isinstance(x, Tensor) else x, res,
                        is_leaf=lambda x: isinstance(x, Tensor))
                    new_buf_vals = []
            leaves, treedef = jax.tree_util.tree_flatten(out)
            holder['treedef'] = treedef
            holder['n_out'] = len(leaves)
            return tuple(leaves) + tuple(new_buf_vals)

        return jax.jit(pure), holder

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator.enabled:
            # reference semantics: ProgramTranslator.enable(False) makes
            # @to_static functions run in plain dygraph (the converted fn
            # preserves eager behaviour exactly)
            return self._fn(*args, **kwargs)
        # Already inside an outer jax trace (jit.save export, a fused hapi
        # train step, dryrun pjit...): the inner jit+cache machinery is void
        # — everything is being traced anyway — and re-reading
        # layer.named_parameters() here would capture the outer trace's
        # substituted tracers into a cached closure (leaf-count corruption
        # at export). Run the converted function directly.
        if not _trace_state_clean():
            return self._fn(*args, **kwargs)
        layer, call_args = self._bound_layer(args)
        arg_arrays = [a._value if isinstance(a, Tensor) else a for a in call_args]
        tensor_like = tuple(i for i, a in enumerate(arg_arrays)
                            if isinstance(a, (jax.Array, np.ndarray, jax.core.Tracer)))
        static_args = {i: a for i, a in enumerate(arg_arrays) if i not in tensor_like}
        training = layer.training if layer is not None else False

        if layer is not None:
            named_p = list(layer.named_parameters())
            named_b = list(layer.named_buffers())
            pnames = [n for n, _ in named_p]
            bnames = [n for n, _ in named_b]
            params = [p for _, p in named_p]
            buffers = [b._value for _, b in named_b]
        else:
            pnames, bnames, params, buffers = [], [], [], []

        cache_key = (training, tensor_like, len(arg_arrays),
                     _hashable(static_args), _hashable(kwargs), tuple(pnames))
        cache = self._cache_for(layer)
        entry = cache.get(cache_key)
        if entry is None:
            static_ctx = {'pnames': pnames, 'bnames': bnames,
                          'static_args': static_args, 'nargs': len(arg_arrays)}
            entry = self._build(layer, training, tensor_like, static_ctx, kwargs)
            cache[cache_key] = entry
        jitted, holder = entry

        dyn_tensors = [call_args[i] if isinstance(call_args[i], Tensor)
                       else Tensor(jnp.asarray(arg_arrays[i])) for i in tensor_like]
        key = next_key()
        results = apply_op(jitted, Tensor(key), [Tensor(b) for b in buffers],
                           *dyn_tensors, *params)
        if not isinstance(results, (list, tuple)):
            results = (results,)
        n_out = holder['n_out']
        out_leaves = list(results[:n_out])
        new_bufs = results[n_out:]
        if layer is not None and training:
            for (n, b), nb in zip(layer.named_buffers(), new_bufs):
                b._replace_value(nb._value)
        return jax.tree_util.tree_unflatten(holder['treedef'], out_leaves)


def to_static(function=None, input_spec=None, build_strategy=None, **kwargs):
    def decorate(fn):
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec)
            return fn
        sf = StaticFunction(fn, input_spec)
        functools.update_wrapper(sf, fn) if not isinstance(fn, functools.partial) else None
        return sf
    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def _spec_to_example(spec):
    shape = [1 if (s is None or s == -1) else int(s) for s in spec.shape]
    return jnp.zeros(shape, spec.dtype)


def save(layer, path, input_spec=None, **configs):
    """Persist params + buffers + StableHLO of the traced forward.

    Mirrors the reference's jit.save (__model__ ProgramDesc + params,
    python/paddle/fluid/dygraph/jit.py:save); here the portable program
    format is StableHLO text, consumed by paddle_tpu.inference.Predictor.
    """
    os.makedirs(os.path.dirname(path) or '.', exist_ok=True)
    from ..framework_io import save as fsave
    if not isinstance(layer, Layer):
        # reference jit.save also accepts a @to_static FUNCTION: persist it
        # as a param-less program (StaticFunction or plain callable)
        return _save_function(layer, path, input_spec)
    fwd = layer.forward
    state = {'params': {n: np.asarray(p._value) for n, p in layer.named_parameters()},
             'buffers': {n: np.asarray(b._value) for n, b in layer.named_buffers()}}
    fsave(state, path + '.pdparams')
    if input_spec is None:
        input_spec = (getattr(fwd, '_input_spec', None) or
                      getattr(layer, '_input_spec', None))
    meta = {'class': type(layer).__name__}
    if input_spec is not None:
        specs = [s if isinstance(s, InputSpec) else InputSpec.from_tensor(s)
                 for s in input_spec]
        meta['input_spec'] = [{'shape': [(-1 if d is None else int(d)) for d in s.shape],
                               'dtype': str(np.dtype(s.dtype).name)} for s in specs]
        examples = [_spec_to_example(s) for s in specs]
        pd = {n: p._value for n, p in layer.named_parameters()}
        bd = {n: b._value for n, b in layer.named_buffers()}
        was_training = layer.training
        layer.eval()
        # a RAW layer with tensor control flow must trace through the
        # dy2static conversion exactly like the @to_static call path
        # (reference jit.save converts the forward too). The converted
        # forward is installed as an INSTANCE attribute for the trace so
        # layer.__call__ still runs forward pre/post hooks (weight_norm/
        # spectral_norm recompute weights in a pre-hook — bypassing
        # __call__ would bake stale weights into the export).
        import contextlib

        from .dy2static import convert_control_flow
        fwd_conv = convert_control_flow(layer.forward)

        @contextlib.contextmanager
        def converted_forward():
            had = 'forward' in layer.__dict__
            prev = layer.__dict__.get('forward')
            object.__setattr__(layer, 'forward', fwd_conv)
            try:
                yield
            finally:
                if had:
                    object.__setattr__(layer, 'forward', prev)
                else:
                    layer.__dict__.pop('forward', None)

        def infer_fn(*xs):
            with converted_forward():
                out, _ = functional_call(layer, pd, bd, *xs)
            return out

        def infer_fn_functional(params, buffers, *xs):
            with converted_forward():
                out, _ = functional_call(layer, params, buffers, *xs)
            return out
        try:
            _export_artifacts(infer_fn, infer_fn_functional, pd, bd, specs,
                              examples, path, meta)
        finally:
            if was_training:
                layer.train()
    import json
    with open(path + '.pdmodel', 'w') as f:
        json.dump(meta, f)


def _export_artifacts(infer_fn, infer_fn_functional, pd, bd, specs, examples,
                      path, meta):
    """Shared export machinery for Layer and function saves: StableHLO dump
    plus the standalone serialized program (jax.export) — the portable
    analogue of the reference's __model__ ProgramDesc, which the Predictor
    runs WITHOUT the Python object. Dims marked -1/None become symbolic so
    one artifact serves any size along those axes. Tried in order: one
    symbol per dynamic dim (fully independent), one shared symbol (programs
    that require equal dynamic dims, e.g. two inputs added together), then
    fully concrete example shapes. On total failure the cause lands in
    meta['export_error'] and any stale .pdexec from a prior save is removed.
    """
    lowered = jax.jit(infer_fn).lower(*examples)
    with open(path + '.stablehlo', 'w') as f:
        f.write(lowered.as_text())
    meta['exported'] = False
    meta['poly_batch'] = False
    from jax import export as jax_export
    p_struct = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pd)
    b_struct = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bd)

    def _sym_specs(shared):
        n_dyn = sum(1 for s in specs for d in s.shape
                    if d is None or d == -1)
        if n_dyn == 0:
            return None, False
        names = 'b' if shared else ', '.join(f'b{i}' for i in range(n_dyn))
        syms = list(jax_export.symbolic_shape(names))
        it = iter(syms * n_dyn if shared else syms)
        out = []
        for s in specs:
            dims = [next(it) if (d is None or d == -1) else int(d)
                    for d in s.shape]
            out.append(jax.ShapeDtypeStruct(tuple(dims), s.dtype))
        return out, True

    n_dyn_total = sum(1 for s in specs for d in s.shape
                      if d is None or d == -1)
    attempts = []
    for shared in ((False, True) if n_dyn_total > 1 else (False,)):
        ss, poly = _sym_specs(shared)
        if ss is not None:
            attempts.append((ss, poly))
        if not poly:
            break
    attempts.append(([jax.ShapeDtypeStruct(e.shape, e.dtype)
                      for e in examples], False))
    # vjp_order=1 bundles the backward program so jit.load's TranslatedLayer
    # is FINE-TUNABLE (reference TranslatedLayer is a trainable Layer). VJP
    # serialization can fail where the forward succeeds (symbolic-shape vjp
    # gaps), so a LATER shape mode with a working vjp beats an earlier one
    # without: keep the first inference-only success as fallback and keep
    # trying shape modes for a trainable artifact (review r4b).
    fallback = None   # (blob, poly, vjp_error)
    chosen = None
    for in_specs, poly in attempts:
        try:
            exported = jax_export.export(jax.jit(infer_fn_functional))(
                p_struct, b_struct, *in_specs)
        except Exception as e:   # noqa: BLE001 — try next shape mode
            # keep the cause: a silent exported=False cost a round-3
            # debugging session (to_static leaf-count corruption)
            meta['export_error'] = f'{e.__class__.__name__}: {e}'[:300]
            continue
        try:
            chosen = (exported.serialize(vjp_order=1), poly, None)
            break
        except Exception as e:   # noqa: BLE001 — inference-only candidate
            if fallback is None:
                try:
                    fallback = (exported.serialize(), poly,
                                f'{e.__class__.__name__}: {e}'[:300])
                except Exception as e2:   # noqa: BLE001
                    meta['export_error'] = \
                        f'{e2.__class__.__name__}: {e2}'[:300]
    if chosen is None and fallback is not None:
        chosen = fallback
    if chosen is not None:
        blob, poly, vjp_err = chosen
        with open(path + '.pdexec', 'wb') as f:
            f.write(blob)
        meta['exported'] = True
        meta['poly_batch'] = poly
        meta['vjp_exported'] = vjp_err is None
        if vjp_err is not None:
            # tells the user WHY their finetune loop will refuse
            meta['vjp_export_error'] = vjp_err
        meta.pop('export_error', None)
    if not meta['exported'] and os.path.exists(path + '.pdexec'):
        os.unlink(path + '.pdexec')   # drop stale program from a prior save


def _save_function(fn, path, input_spec):
    """jit.save for a function: .pdparams carries empty state; the .pdexec
    program takes only the inputs."""
    import json
    from ..framework_io import save as fsave
    raw = fn._fn if isinstance(fn, StaticFunction) else fn
    spec = input_spec or getattr(fn, '_input_spec', None)
    if spec is None:
        raise ValueError('jit.save of a function requires input_spec')
    specs = [s if isinstance(s, InputSpec) else InputSpec.from_tensor(s)
             for s in spec]
    fsave({'params': {}, 'buffers': {}}, path + '.pdparams')
    meta = {'class': getattr(raw, '__name__', 'function'), 'function': True,
            'input_spec': [{'shape': [(-1 if d is None else int(d))
                                      for d in s.shape],
                            'dtype': str(np.dtype(s.dtype).name)}
                           for s in specs]}

    def infer_fn_functional(params, buffers, *xs):
        from ..core.tensor import no_grad_ctx
        targs = [Tensor(x) for x in xs]
        with no_grad_ctx():
            res = raw(*targs)
        return jax.tree_util.tree_map(
            lambda t: t._value if isinstance(t, Tensor) else t, res,
            is_leaf=lambda t: isinstance(t, Tensor))

    def infer_fn(*xs):
        return infer_fn_functional({}, {}, *xs)

    examples = [_spec_to_example(s) for s in specs]
    _export_artifacts(infer_fn, infer_fn_functional, {}, {}, specs, examples,
                      path, meta)
    with open(path + '.pdmodel', 'w') as f:
        json.dump(meta, f)


def load_saved_artifacts(path):
    """Load a jit.save'd prefix: (params, buffers, meta, exec_or_None).

    The serialized program is only deserialized when meta says the export
    succeeded — a stale .pdexec from an earlier save of a different model is
    ignored. Shared by jit.load and inference.Predictor.
    """
    import json
    from ..framework_io import load as fload
    state = fload(path + '.pdparams')

    def _arr(v):
        return jnp.asarray(getattr(v, '_value', v))
    params = {k: _arr(v) for k, v in state['params'].items()}
    buffers = {k: _arr(v) for k, v in state['buffers'].items()}
    with open(path + '.pdmodel') as f:
        meta = json.load(f)
    executable = None
    if meta.get('exported') and os.path.exists(path + '.pdexec'):
        from jax import export as jax_export
        with open(path + '.pdexec', 'rb') as f:
            executable = jax_export.deserialize(f.read())
    return params, buffers, meta, executable


def _flat_name(n):
    """Injective flattening of dotted program names into single-level
    attribute names ('_' escaped first, so 'a__weight' and 'a.weight'
    cannot collide — review r4b)."""
    return n.replace('_', '_u').replace('.', '_d')


class TranslatedLayer(Layer):
    """A jit.save'd program reloaded WITHOUT its Python class.

    Reference: fluid/dygraph/io.py TranslatedLayer (rebuilds a Layer from the
    __model__ ProgramDesc). Here the program is a serialized jax.export
    artifact (.pdexec): deserialization gives a callable XLA program; params
    and buffers come from the .pdparams archive and are passed as the leading
    pytree arguments.

    Like the reference, the result is a real Layer: its parameters are
    trainable when the artifact was serialized with its backward program
    (meta['vjp_exported'], the jit.save default) — the deploy-then-finetune
    workflow. Caveat: the program is traced in eval mode at save time, so
    dropout stays off and norm running stats stay frozen while fine-tuning
    (feature-extractor semantics).
    """

    def __init__(self, path):
        super().__init__()
        params, buffers, self._meta, self._exec = load_saved_artifacts(path)
        if self._exec is None:
            raise RuntimeError(
                f'{path}.pdexec missing or export failed at save time; '
                f'reconstruct the Layer and set_state_dict(jit.load raw dict)')
        from ..nn.layer_base import Parameter
        # registered under sanitized names ('.' nests in state_dict keys);
        # _tl_pnames keeps the original program-side names in order
        self._tl_pnames = list(params)
        self._tl_bnames = list(buffers)
        trainable = bool(self._meta.get('vjp_exported'))
        for n, v in params.items():
            p = Parameter(v)
            if not trainable:
                # no serialized backward program: advertising trainable
                # params would let a finetune loop run with grads silently
                # frozen (review r4b)
                p.stop_gradient = True
            self.add_parameter(_flat_name(n), p)
        for n, v in buffers.items():
            self.register_buffer(_flat_name(n), Tensor(v))
        self.eval()

    def train(self):
        if not self._meta.get('vjp_exported'):
            raise RuntimeError(
                'this artifact was serialized without its backward program '
                '(vjp_exported=false) — TranslatedLayer is inference-only; '
                're-save with the current jit.save to fine-tune')
        return super().train()

    def forward(self, *inputs):
        from ..core.dispatch import apply_op
        xs = [a if isinstance(a, Tensor) else Tensor(jnp.asarray(np.asarray(a)))
              for a in inputs]
        pts = [self._parameters[_flat_name(n)] for n in self._tl_pnames]
        bvals = {n: self._buffers[_flat_name(n)]._value
                 for n in self._tl_bnames}
        pnames, np_ = self._tl_pnames, len(self._tl_pnames)

        treedef_box = []

        def pure(*leaves):
            pvals = dict(zip(pnames, leaves[:np_]))
            out = self._exec.call(pvals, bvals, *leaves[np_:])
            # arbitrary output pytrees (dict returns etc.) ride through the
            # dispatch layer as flat leaves and are rebuilt below
            flat, td = jax.tree_util.tree_flatten(out)
            treedef_box.append(td)
            return tuple(flat) if len(flat) != 1 else flat[0]

        if self._meta.get('vjp_exported'):
            # through the dispatch layer: taped, so loss.backward() reaches
            # the registered Parameters via the serialized VJP program
            res = apply_op(pure, *pts, *xs)
        else:
            out = pure(*[t._value for t in pts], *[t._value for t in xs])
            res = jax.tree_util.tree_map(Tensor, out,
                                         is_leaf=lambda x: not isinstance(
                                             x, (list, tuple)))
        flat = list(res) if isinstance(res, (list, tuple)) else [res]
        return jax.tree_util.tree_unflatten(treedef_box[-1], flat)

    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix=''):
        # original program-side (dotted) names, as the reference
        # TranslatedLayer; honors the Layer API's destination/prefix
        d = destination if destination is not None else {}
        for n in self._tl_pnames:
            d[structured_name_prefix + n] = self._parameters[_flat_name(n)]
        for n in self._tl_bnames:
            d[structured_name_prefix + n] = self._buffers[_flat_name(n)]
        return d


def load(path, **configs):
    """Reload a jit.save'd model. Returns a callable TranslatedLayer when the
    standalone program (.pdexec) exists; otherwise the raw state dict
    {params, buffers} for manual ``set_state_dict``."""
    if os.path.exists(path + '.pdexec') and os.path.exists(path + '.pdmodel'):
        try:
            # load_saved_artifacts makes the exported/stale decision itself
            return TranslatedLayer(path)
        except Exception as e:   # noqa: BLE001 — any deserialization failure
            # (RuntimeError, OSError, ValueError, jax.export version skew...)
            # degrades to the raw state dict rather than aborting the load
            import warnings
            warnings.warn(f'jit.load: standalone program at {path}.pdexec '
                          f'unusable ({e.__class__.__name__}: {e}); '
                          f'returning raw state dict')
    from ..framework_io import load as fload
    return fload(path + '.pdparams')


# ---- parity shims (reference: python/paddle/jit/__init__.py) -------------
declarative = to_static          # old alias


class ProgramTranslator:
    """Reference: jit/dy2static/program_translator.py. Tracing-based backend
    has no AST translator state; enable flag toggles to_static pass-through."""
    _instance = None
    enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static):
        ProgramTranslator.enabled = bool(enable_to_static)


def enable_to_static(flag):
    ProgramTranslator.get_instance().enable(flag)


def set_code_level(level=100):
    pass


def set_verbosity(level=0, also_to_stdout=False):
    pass


class dy2static:
    ProgramTranslator = ProgramTranslator

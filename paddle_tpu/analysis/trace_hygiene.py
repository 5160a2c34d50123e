"""Pass 1 — trace hygiene: host-sync, nondeterminism, closure capture and
donation hazards inside functions that reach ``jax.jit``.

A *traced function* is found statically, per module: anything decorated
with / passed to a trace entry point (``jax.jit``, ``jax.grad``,
``jax.lax.scan``/``cond``/``while_loop``, ``custom_vjp`` pairs,
``shard_map``, …) plus everything those functions call, resolved through
module-local names and ``self.<method>`` (cross-module propagation is out
of scope — every in-tree traced step lives in the module that jits it).

Rules:

  trace-host-sync       ``.item()``/``.tolist()``, ``np.asarray``/``np.array``,
                        and ``float()``/``int()``/``bool()`` on values that flow
                        from traced params — each one a device round-trip that
                        stalls the async dispatch pipeline (or a tracer error).
  trace-host-branch     Python ``if``/``while`` on a value produced by a
                        jnp/jax op — a TracerBoolConversionError at best, a
                        silent per-value retrace at worst.
  trace-nondeterminism  ``time.time()``, stdlib/np ``random``, ``uuid4`` in a
                        trace: baked in as a compile-time constant, NOT fresh
                        per step — almost never what the author meant.
  trace-closure-capture a jitted closure captures an array-ish value from an
                        enclosing function scope: the array is hashed into the
                        compile cache key (silent retrace per object) and
                        pinned in HBM for the executable's lifetime.
  trace-missing-donate  a jit of a state-threading step (params/opt-state in,
                        updated state out) without ``donate_argnums`` — XLA
                        must double-buffer the whole optimizer state.

Heuristics are deliberately conservative where static/traced cannot be
decided (e.g. ``float()`` on arguments is only flagged when no static
marker like ``.shape``/``len()`` is involved); deliberate exceptions are
acknowledged with ``# pt-lint: disable=...`` pragmas at the site.
"""
import ast

from .core import Finding, register_rule

R_HOST_SYNC = register_rule(
    'trace-host-sync',
    'host synchronisation inside a traced function', 'trace')
R_HOST_BRANCH = register_rule(
    'trace-host-branch',
    'Python control flow on a traced value', 'trace')
R_NONDET = register_rule(
    'trace-nondeterminism',
    'host-side nondeterminism captured into a trace', 'trace')
R_CLOSURE = register_rule(
    'trace-closure-capture',
    'jitted closure captures an array from an enclosing scope', 'trace')
R_DONATE = register_rule(
    'trace-missing-donate',
    'state-threading jit without donate_argnums', 'trace')

# dotted suffixes that make a function argument / decorated function traced
_TRACE_WRAPPERS = {
    'jax.jit', 'jit', 'pjit', 'jax.pjit',
    'jax.grad', 'jax.value_and_grad', 'jax.jacfwd', 'jax.jacrev',
    'jax.vmap', 'jax.pmap', 'jax.eval_shape',
    'jax.checkpoint', 'jax.remat', 'checkpoint', 'remat',
    'jax.custom_vjp', 'jax.custom_jvp', 'custom_vjp', 'custom_jvp',
    'jax.lax.scan', 'jax.lax.cond', 'jax.lax.while_loop',
    'jax.lax.fori_loop', 'jax.lax.map', 'jax.lax.switch',
    'jax.lax.associative_scan', 'lax.scan', 'lax.cond', 'lax.while_loop',
    'lax.fori_loop', 'lax.map', 'lax.switch',
    'shard_map', 'jax.shard_map',
    'jit_train_step',      # parallel/train_jit.py: jit that donates its state
}
_JIT_NAMES = {'jax.jit', 'jit', 'pjit', 'jax.pjit'}

# jnp/jax producers whose results are STATIC python values, not tracers
_STATIC_PRODUCERS = {'shape', 'ndim', 'size', 'result_type', 'dtype',
                     'finfo', 'iinfo', 'issubdtype'}

# free-variable names treated as array state when captured by a jitted
# closure (inverse — config/treedef/callable captures are the normal,
# harmless pattern, so only known array-ish names are flagged)
_ARRAYISH = {
    'params', 'param', 'state', 'opt_state', 'opt_s', 'cache', 'caches',
    'weights', 'grads', 'gradients', 'toks', 'tokens', 'batch', 'arr',
    'array', 'buffers', 'inputs', 'labels', 'leaves', 'xs', 'ys',
}
_ARRAYISH_SUFFIX = ('_params', '_state', '_cache', '_weights', '_arrays')

# parameter-name sets that mark a jitted function as state-threading
_STATE_PARAMS = {'opt_state', 'opt_s', 'optimizer_state', 'fp8_state'}

_NONDET_CALLS = {
    'time.time', 'time.perf_counter', 'time.monotonic', 'time.time_ns',
    'time.perf_counter_ns', 'datetime.now', 'datetime.utcnow',
    'datetime.datetime.now', 'datetime.datetime.utcnow',
    'uuid.uuid4', 'uuid.uuid1', 'os.urandom',
}
_NONDET_MODULES = {'random', 'secrets'}     # any call into these
_NONDET_NP_RANDOM = 'random'                # np.random.* via numpy aliases


def _dotted(node):
    """'jax.lax.scan' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return '.'.join(reversed(parts))
    return None


def walk_scope(node):
    """ast.walk that does not descend into nested function/class scopes."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))


class _FnInfo:
    __slots__ = ('node', 'qualname', 'parent', 'cls', 'params', 'assigned',
                 'defs', 'is_lambda')

    def __init__(self, node, qualname, parent, cls):
        self.node = node
        self.qualname = qualname
        self.parent = parent          # enclosing _FnInfo or None (module)
        self.cls = cls                # enclosing class name or None
        self.is_lambda = isinstance(node, ast.Lambda)
        a = node.args
        self.params = {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)}
        for extra in (a.vararg, a.kwarg):
            if extra is not None:
                self.params.add(extra.arg)
        self.assigned = set()
        self.defs = {}                # name -> _FnInfo (immediate children)


class _ModuleIndex(ast.NodeVisitor):
    """One walk collecting scopes, aliases, class methods and call sites."""

    def __init__(self, src):
        self.src = src
        self.fns = {}                 # ast node -> _FnInfo
        self.module_names = set()     # module-level bindings
        self.module_fns = {}          # module-level def name -> _FnInfo
        self.np_aliases = set()       # names bound to numpy
        self.jnp_aliases = set()      # names bound to jax.numpy / jax.*
        self.module_aliases = {}      # asname -> dotted module
        self.class_methods = {}       # class name -> {method: _FnInfo}
        self.calls = []               # (call node, enclosing _FnInfo|None)
        self._scope = []              # stack of _FnInfo
        self._cls = []                # stack of class names
        self.visit(src.tree)

    # -- imports ---------------------------------------------------------
    def visit_Import(self, node):
        for al in node.names:
            name = al.asname or al.name.split('.')[0]
            if not self._scope:
                self.module_names.add(name)
            self.module_aliases[name] = al.name
            if al.name in ('numpy', 'numpy.ma'):
                self.np_aliases.add(name)
            if al.name in ('jax.numpy', 'jax', 'jax.lax', 'jax.random',
                           'jax.nn'):
                self.jnp_aliases.add(name)

    def visit_ImportFrom(self, node):
        for al in node.names:
            name = al.asname or al.name
            if not self._scope:
                self.module_names.add(name)
            if node.module == 'jax' and al.name in ('numpy', 'lax',
                                                    'random', 'nn'):
                self.jnp_aliases.add(name)
            if node.module in ('time', 'datetime', 'random', 'uuid',
                               'secrets'):
                self.module_aliases[name] = f'{node.module}.{al.name}'

    # -- scopes ----------------------------------------------------------
    def _enter_fn(self, node, name):
        parent = self._scope[-1] if self._scope else None
        cls = self._cls[-1] if (parent is None and self._cls) else \
            (parent.cls if parent is not None else None)
        prefix = []
        if parent is None and cls:
            prefix = [cls]
        prefix += [(f.node.name if not f.is_lambda else '<lambda>')
                   for f in self._scope]
        info = _FnInfo(node, '.'.join(prefix + [name]) if prefix else name,
                       parent, cls)
        self.fns[node] = info
        if parent is not None:
            parent.defs[name] = info
        elif cls:
            self.class_methods.setdefault(cls, {})[name] = info
        else:
            self.module_names.add(name)
            self.module_fns.setdefault(name, info)
        return info

    def visit_FunctionDef(self, node):
        info = self._enter_fn(node, node.name)
        if self._scope:
            self._scope[-1].assigned.add(node.name)
        for dec in node.decorator_list:    # decorators run in outer scope
            self.visit(dec)
        self._scope.append(info)
        for child in node.body:
            self.visit(child)
        self._scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        info = self._enter_fn(node, '<lambda>')
        self._scope.append(info)
        self.visit(node.body)
        self._scope.pop()

    def visit_ClassDef(self, node):
        if not self._scope:
            self.module_names.add(node.name)
        self._cls.append(node.name)
        saved, self._scope = self._scope, []   # methods don't see class body
        for child in node.body:
            self.visit(child)
        self._scope = saved
        self._cls.pop()

    # -- bindings and calls ---------------------------------------------
    def visit_Name(self, node):
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            if self._scope:
                self._scope[-1].assigned.add(node.id)
            elif not self._cls:
                self.module_names.add(node.id)
        self.generic_visit(node)

    def visit_Call(self, node):
        self.calls.append((node, self._scope[-1] if self._scope else None))
        self.generic_visit(node)


def _resolve(name, scope, index):
    """A Name in ``scope`` -> _FnInfo if it names a visible local def."""
    s = scope
    while s is not None:
        if name in s.defs:
            return s.defs[name]
        s = s.parent
    return index.module_fns.get(name)


def _wrapper_name(node, index):
    """Dotted name of a call/decorator target if it is a trace wrapper."""
    d = _dotted(node)
    if d is None:
        return None
    if d in _TRACE_WRAPPERS or d.split('.', 1)[-1] in _TRACE_WRAPPERS:
        return d
    return None


def _is_partial(node):
    d = _dotted(node)
    return d is not None and d.split('.')[-1] == 'partial'


def _trace_roots(index):
    """(traced fn infos, jit sites). A jit site is (call-ish node, wrapped
    _FnInfo or None, has_donate, scope)."""
    traced = set()
    jit_sites = []

    def mark_arg(arg, scope):
        if isinstance(arg, ast.Lambda):
            traced.add(index.fns[arg])
            return index.fns[arg]
        if isinstance(arg, ast.Name):
            info = _resolve(arg.id, scope, index)
            if info is not None:
                traced.add(info)
                return info
        return None

    # call sites: jax.jit(f, ...), lax.scan(body, ...), partial(jax.jit,...)
    for call, scope in index.calls:
        wrapper = _wrapper_name(call.func, index)
        inner_jit = None
        if wrapper is None and _is_partial(call.func):
            for a in call.args:
                w = _wrapper_name(a, index)
                if w is not None:
                    inner_jit = w
                    break
            wrapper = inner_jit
        if wrapper is None:
            continue
        wrapped = [mark_arg(a, scope) for a in call.args]
        wrapped = [w for w in wrapped if w is not None]
        if {wrapper.split('.', 1)[-1], wrapper} & _JIT_NAMES:
            has_donate = any(kw.arg in ('donate_argnums', 'donate_argnames')
                             for kw in call.keywords if kw.arg)
            for info in wrapped:
                jit_sites.append((call, info, has_donate))

    # decorators: @jax.jit / @partial(jax.jit, ...) / @jax.custom_vjp ...
    for node, info in index.fns.items():
        if info.is_lambda:
            continue
        for dec in node.decorator_list:
            wrapper = _wrapper_name(dec, index)
            has_donate = False
            if wrapper is None and isinstance(dec, ast.Call):
                wrapper = _wrapper_name(dec.func, index)
                kws = dec.keywords
                if wrapper is None and _is_partial(dec.func):
                    for a in dec.args:
                        w = _wrapper_name(a, index)
                        if w is not None:
                            wrapper = w
                            break
                has_donate = any(
                    kw.arg in ('donate_argnums', 'donate_argnames')
                    for kw in kws if kw.arg) if isinstance(dec, ast.Call) \
                    else False
            if wrapper is None:
                continue
            traced.add(info)
            if {wrapper.split('.', 1)[-1], wrapper} & _JIT_NAMES:
                jit_sites.append((dec, info, has_donate))
    return traced, jit_sites


def _propagate(traced, index):
    """Callees of traced functions are traced (module-local fixpoint)."""
    work = list(traced)
    while work:
        fn = work.pop()
        for call, scope in index.calls:
            if scope is not fn:
                continue
            target = None
            if isinstance(call.func, ast.Name):
                target = _resolve(call.func.id, fn, index)
            elif isinstance(call.func, ast.Attribute) and \
                    isinstance(call.func.value, ast.Name) and \
                    call.func.value.id == 'self' and fn.cls:
                target = index.class_methods.get(fn.cls, {}).get(
                    call.func.attr)
            if target is not None and target not in traced:
                traced.add(target)
                work.append(target)
    return traced


# ---------------------------------------------------------------------------
# per-function checks
# ---------------------------------------------------------------------------

def _device_locals(fn, index):
    """Names assigned from jnp/jax calls in fn's own scope (two passes so
    simple forwarding assignments propagate)."""
    jnp = index.jnp_aliases | {'jnp', 'jax', 'lax'}
    device = set()

    def produces_device(expr):
        # A jnp/jax call (other than a static producer) is a device value.
        # Any OTHER call poisons name-based propagation: helpers routinely
        # distil device args down to static facts (``is_weight_only(cache)``
        # returns a bool, ``jnp.dtype(x)`` a dtype), so an expression with a
        # foreign call is only device-valued if a jnp call appears in it.
        foreign = False
        for n in ast.walk(expr):
            if isinstance(n, ast.Call):
                d = _dotted(n.func)
                if d and d.split('.')[0] in jnp and \
                        d.split('.')[-1] not in _STATIC_PRODUCERS:
                    return True
                foreign = True
        if foreign:
            return False
        return any(isinstance(n, ast.Name) and n.id in device
                   for n in ast.walk(expr))

    def bind(t):
        # only REBOUND names become device locals — ``cache[k] = jnp...``
        # mutates a container (and must not mark the index ``k``)
        if isinstance(t, ast.Name):
            device.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for elt in t.elts:
                bind(elt)
        elif isinstance(t, ast.Starred):
            bind(t.value)

    for _ in range(2):
        for n in walk_scope(fn.node):
            if isinstance(n, ast.Assign) and produces_device(n.value):
                for t in n.targets:
                    bind(t)
    return device


def _refs(expr, names):
    return [n for n in ast.walk(expr)
            if isinstance(n, ast.Name) and n.id in names]


def _has_static_marker(expr):
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr in (
                'shape', 'ndim', 'size', 'dtype'):
            return True
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if d and d.split('.')[-1] in ('len', 'shape', 'ndim', 'size'):
                return True
    return False


def _hazard_refs(test, names):
    """References to ``names`` in a branch test that actually force a
    tracer->bool conversion. Discards references that are

      - inside ``is`` / ``is not`` comparisons (static None checks),
      - under a static attribute (``x.shape``/``.ndim``/``.size``/``.dtype``),
      - arguments of ANY call — host predicates over device values
        (``flash_decode_available(q, k)``) return static facts; calls that
        produce device values are caught by the direct-jnp check instead.
    """
    hazard = set(id(r) for r in _refs(test, names))
    if not hazard:
        return False
    for n in ast.walk(test):
        if isinstance(n, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
            for sub in [n.left] + n.comparators:
                for r in _refs(sub, names):
                    hazard.discard(id(r))
        elif isinstance(n, ast.Attribute) and n.attr in _STATIC_PRODUCERS | \
                {'shape', 'ndim', 'size', 'dtype'}:
            for r in _refs(n.value, names):
                hazard.discard(id(r))
        elif isinstance(n, ast.Call):
            for sub in list(n.args) + [kw.value for kw in n.keywords]:
                for r in _refs(sub, names):
                    hazard.discard(id(r))
    return bool(hazard)


def _check_traced_fn(fn, index, src, findings):
    jnp = index.jnp_aliases | {'jnp', 'jax', 'lax'}
    device = _device_locals(fn, index)
    traced_names = device | fn.params

    def add(rule, node, msg):
        findings.append(Finding(rule.id, src.relpath, node.lineno,
                                node.col_offset, msg, fn.qualname))

    for n in walk_scope(fn.node):
        # --- host sync ---------------------------------------------------
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ('item', 'tolist', 'to_py') and \
                    not n.args and not n.keywords:
                add(R_HOST_SYNC, n,
                    f'.{n.func.attr}() forces a device->host readback '
                    'inside a traced function')
            elif d and d.split('.')[0] in index.np_aliases and \
                    d.split('.')[-1] in ('asarray', 'array'):
                add(R_HOST_SYNC, n,
                    f'{d}() materializes a traced value on host '
                    '(use jnp.asarray)')
            elif isinstance(n.func, ast.Name) and \
                    n.func.id in ('float', 'int', 'bool') and \
                    len(n.args) == 1 and not n.keywords:
                # only a bare name / indexed name that is a traced param or
                # a jnp-produced local: float(config.n) etc. stays silent
                arg = n.args[0]
                base = arg.value if isinstance(arg, ast.Subscript) else arg
                if isinstance(base, ast.Name) and base.id in traced_names \
                        and not _has_static_marker(arg):
                    add(R_HOST_SYNC, n,
                        f'{n.func.id}() on a traced value syncs the host '
                        '(use jnp casts / keep it on device)')
            # --- nondeterminism ------------------------------------------
            if d is not None:
                root = d.split('.')[0]
                if d in _NONDET_CALLS or root in _NONDET_MODULES or (
                        root in index.np_aliases and
                        d.split('.')[1:2] == [_NONDET_NP_RANDOM]):
                    if index.module_aliases.get(root, root) in (
                            'time', 'datetime', 'uuid', 'os', 'random',
                            'secrets') or root in index.np_aliases:
                        add(R_NONDET, n,
                            f'{d}() is evaluated once at trace time — the '
                            'compiled step will replay a constant')
        # --- host control flow on device values --------------------------
        if isinstance(n, (ast.If, ast.While)):
            test = n.test
            direct_jnp = any(
                isinstance(c, ast.Call) and (_dotted(c.func) or '').split(
                    '.')[0] in jnp and (_dotted(c.func) or '.').split(
                    '.')[-1] not in _STATIC_PRODUCERS
                for c in ast.walk(test))
            if direct_jnp or _hazard_refs(test, device):
                kw = 'while' if isinstance(n, ast.While) else 'if'
                add(R_HOST_BRANCH, n,
                    f'python `{kw}` on a traced value — use lax.cond/'
                    'lax.while_loop or jnp.where')


def _check_closures(traced, jit_fns, index, src, findings):
    import builtins as _b
    builtins_ = set(dir(_b))
    # only jit/pjit-wrapped closures: constants are baked (and pinned in
    # HBM, and hashed into the compile cache) at JIT boundaries — scan /
    # vmap / grad bodies trace within whatever trace encloses them
    for fn in jit_fns:
        if fn.parent is None:        # module-level def: no closure
            continue
        called_names = set()
        for call, scope in index.calls:
            if scope is fn and isinstance(call.func, ast.Name):
                called_names.add(call.func.id)
        bound = fn.params | fn.assigned | set(fn.defs) | builtins_ | \
            index.module_names
        for n in walk_scope(fn.node):
            if not (isinstance(n, ast.Name) and
                    isinstance(n.ctx, ast.Load)):
                continue
            name = n.id
            if name in bound or name in called_names:
                continue
            # bound in SOME enclosing function scope?
            s = fn.parent
            binder = None
            while s is not None:
                if name in s.params or name in s.assigned:
                    binder = s
                    break
                s = s.parent
            if binder is None:
                continue
            if binder in traced:
                # the binding scope is itself inside the trace, so the
                # captured value is a tracer of the SAME trace — closing
                # over it is the canonical jax idiom (grad loss_fn, scan
                # bodies), not a baked-in constant
                continue
            if name in _ARRAYISH or name.endswith(_ARRAYISH_SUFFIX):
                findings.append(Finding(
                    R_CLOSURE.id, src.relpath, n.lineno, n.col_offset,
                    f'jitted closure captures {name!r} from an enclosing '
                    'scope — pass it as an argument (captured arrays are '
                    'baked into the compile cache and pinned in HBM)',
                    fn.qualname))
                bound.add(name)      # one finding per name per function


def _check_donation(jit_sites, src, findings):
    for site, info, has_donate in jit_sites:
        if info is None or has_donate or info.is_lambda:
            continue
        params = [p.arg for p in (info.node.args.posonlyargs
                                  + info.node.args.args)]
        pset = set(params)
        statey = bool(pset & _STATE_PARAMS) or (
            'params' in pset and bool(pset & {'opt', 'state', 'fp8'}))
        if statey:
            findings.append(Finding(
                R_DONATE.id, src.relpath, site.lineno, site.col_offset,
                f'jit of state-threading step {info.qualname}'
                f'({", ".join(params)}) without donate_argnums — the '
                'old state stays live and doubles the HBM footprint',
                info.qualname))


# ---------------------------------------------------------------------------

def run_pass(sources):
    findings = []
    for src in sources:
        try:
            index = _ModuleIndex(src)
        except RecursionError:      # pathological nesting: skip the file
            continue
        traced, jit_sites = _trace_roots(index)
        traced = _propagate(traced, index)
        for fn in traced:
            _check_traced_fn(fn, index, src, findings)
        jit_fns = {info for _, info, _ in jit_sites if info is not None}
        _check_closures(traced, jit_fns, index, src, findings)
        _check_donation(jit_sites, src, findings)
    return findings

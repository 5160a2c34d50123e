"""Mesh-sharded engine execution: one serving replica spanning N chips.

A fleet replica used to be a single-chip engine, so the largest servable
model was whatever fit one chip's HBM. This module supplies the glue that
lets the SAME two GenerationEngine functions (the padded batch-1 prefill,
an executable a width, + the fixed-slot decode step) — and the
InferenceEngine bucket executables —
run as ONE SPMD program over an mp=N device mesh:

 - ``MeshContext`` owns the mesh (a dedicated ``HybridTopology`` over
   exactly N devices, mp innermost) and the logical-axis
   :class:`~.partitioner.Partitioner` whose rules place every tensor:
   params via the model's ``LOGICAL_AXES`` (Megatron column/row layout
   from the 'heads'/'mlp'/'vocab' rules), the paged KV pool along its
   *heads* dim (``kv_heads -> mp``), and page tables / decode state
   replicated. The page allocator never sees the mesh: one logical page
   maps to N physical head-shards, so page accounting, eviction, COW and
   the prefix cache are byte-for-byte the mp=1 code paths.
 - placement is *fallback-safe*: a tensor whose dim does not divide the
   mesh degree is replicated (recorded in ``ctx.fallbacks``) instead of
   raising — forgetting divisibility can cost memory, never correctness.
 - ``sharded_structs`` preserves multi-device shardings when the warmup
   prebuilder lowers ``jax.ShapeDtypeStruct`` skeletons, so an AOT
   executable compiled before traffic expects exactly the placements the
   live engine passes (zero retraces, zero resharding).

The engine executables stay *uniform* across mesh sizes: the trace count
is what it is on one chip (the step and a prefill a width), warmth
cloning/snapshotting copies the same ``_aot`` dict, and the fleet/host
control planes cannot tell mp=4 from mp=1.
"""
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..ops.paged_kv import POOL_LOGICAL_AXES  # noqa: F401  (re-export)
from .partitioner import Partitioner, ShardingRuleError, model_rules


def serving_rules(mp=1):
    """Rules table for the serving path: the model rules, which include
    the paged-KV axes (``kv_heads -> mp``, ``kv_pages`` replicated — the
    +1 trash page makes the page count indivisible by any mp > 1, so the
    table pins it rather than relying on fall-through). On a mesh whose
    'mp' axis has size 1 the kv_heads rule is a no-op, so one table
    serves every mesh shape."""
    return model_rules(mp=mp)


def build_mesh(mp, devices=None):
    """A dedicated mesh over exactly ``mp`` devices with every hybrid axis
    present (sizes 1 except 'mp') so any rules table validates against it.
    Passing ``devices`` pins the replica to a specific chip set; the
    default takes the first ``mp`` local devices."""
    from ..distributed.topology import HybridTopology
    if devices is None:
        devices = jax.devices()
    mp = int(mp)
    if mp < 1:
        raise ValueError(f'mesh size must be >= 1, got {mp}')
    if len(devices) < mp:
        raise ValueError(
            f'mesh of {mp} devices requested but only {len(devices)} '
            f'available (CPU tests: XLA_FLAGS='
            f'--xla_force_host_platform_device_count=N)')
    # exactly mp devices: HybridTopology must not grow dp over the rest
    return HybridTopology(mp=mp, devices=list(devices)[:mp]).mesh


class MeshContext:
    """One replica's mesh + partitioner + placement bookkeeping.

    ``fallbacks`` records every leaf that resolved sharded but was placed
    replicated because its dim does not divide the mesh degree — the
    shard-audit gate (tools/shard_check.py) surfaces these.
    """

    def __init__(self, mesh, rules=None):
        self.mesh = mesh
        self.mp = int(mesh.shape.get('mp', 1))
        self.partitioner = Partitioner(
            rules=rules if rules is not None else serving_rules(self.mp),
            mesh=mesh)
        self.fallbacks = []

    @classmethod
    def build(cls, mp, devices=None, rules=None):
        return cls(build_mesh(mp, devices=devices), rules=rules)

    @property
    def size(self):
        return self.mesh.size

    def describe(self):
        return {'mp': self.mp, 'devices': self.size,
                'axes': dict(self.mesh.shape),
                'fallbacks': list(self.fallbacks)}

    # ---- spec resolution (divisibility falls back to replicated) ---------
    def _spec(self, logical_axes, shape, label=''):
        try:
            return self.partitioner.spec(logical_axes, shape)
        except ShardingRuleError as e:
            self.fallbacks.append({'tensor': label or str(logical_axes),
                                   'reason': str(e)})
            return PartitionSpec()

    def sharding(self, logical_axes, shape=None, label=''):
        return NamedSharding(self.mesh, self._spec(logical_axes, shape,
                                                   label=label))

    def replicated(self):
        return NamedSharding(self.mesh, PartitionSpec())

    # ---- placement --------------------------------------------------------
    def place(self, tree, logical_tree):
        """device_put a pytree per its logical axes (indivisible leaves
        land replicated, recorded in ``fallbacks``)."""
        is_leaf = (lambda x: x is None
                   or isinstance(x, (tuple, PartitionSpec)))
        paths = _tree_paths(logical_tree, is_leaf)

        def put(path, la, x):
            sh = self.sharding(la, getattr(x, 'shape', None), label=path)
            return jax.device_put(x, sh)
        flat_la, treedef = jax.tree_util.tree_flatten(logical_tree,
                                                      is_leaf=is_leaf)
        flat_x = treedef.flatten_up_to(tree)
        placed = [put(p, la, x) for p, la, x in zip(paths, flat_la, flat_x)]
        return jax.tree_util.tree_unflatten(treedef, placed)

    def place_params(self, params, config):
        """Place a causal-LM param pytree by the family's LOGICAL_AXES
        (gpt vs moe_gpt picked off the config type)."""
        return self.place(params, model_logical_axes(config))

    def place_pool(self, pool, logical_axes=POOL_LOGICAL_AXES):
        """Place the page pool's planes by ``logical_axes`` (the model
        family's; the K/V pool shards its heads axis); the page tables and
        the allocator stay host-side and mesh-agnostic. int8 pools
        ({'int8','scale'} banks) place both planes — the per-row scale
        drops the last axis."""
        sh = self.sharding(logical_axes, label='kv_pool')
        scale_sh = self.sharding(logical_axes[:-1], label='kv_scale')

        def put(v):
            if isinstance(v, dict):
                return {'int8': jax.device_put(v['int8'], sh),
                        'scale': jax.device_put(v['scale'], scale_sh)}
            return jax.device_put(v, sh)
        return {k: put(v) for k, v in pool.items()}

    def pool_sharding(self):
        return self.sharding(POOL_LOGICAL_AXES, label='kv_pool')

    def constrain_pool(self, plane):
        """Trace-time sharding constraint pinning one pool plane to the
        heads layout (keeps GSPMD from resharding KV mid-graph)."""
        return jax.lax.with_sharding_constraint(plane, self.pool_sharding())


def model_logical_axes(config):
    """The LOGICAL_AXES tree of a model config's family."""
    from ..models import family
    fam = family.family_of(config)
    if fam.logical_axes is None:
        raise ValueError(f'the {fam.name} family has no logical axes: it '
                         f'serves on one chip')
    return fam.logical_axes


def resolve(mesh, mp=None, devices=None):
    """Normalize an engine's ``mesh=`` argument: an existing MeshContext
    passes through, a Mesh is wrapped, an int builds one (``mp=`` is the
    keyword twin). Returns None when no mesh was requested or the degree
    is 1 — an mp=1 replica takes the single-chip path untouched."""
    if mesh is None and mp is not None:
        mesh = int(mp)
    if mesh is None:
        return None
    if isinstance(mesh, MeshContext):
        ctx = mesh
    elif isinstance(mesh, int):
        if mesh <= 1:
            return None
        ctx = MeshContext.build(mesh, devices=devices)
    else:
        ctx = MeshContext(mesh)
    return ctx if ctx.mp > 1 else None


def sharded_structs(tree):
    """Abstract skeleton of a pytree that PRESERVES multi-device
    placements: ``jax.ShapeDtypeStruct(..., sharding=)`` for leaves
    committed to a >1-device NamedSharding, plain structs otherwise. AOT
    prebuild lowers through these so the compiled executable's input
    shardings match what the live sharded engine passes."""
    def one(a):
        sh = getattr(a, 'sharding', None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype))
    return jax.tree_util.tree_map(one, tree)


def mesh_of(engine):
    """The MeshContext an engine runs under, or None (single chip). The
    ONE accessor the host/fleet/audit planes use — they never reach into
    engine internals for mesh state."""
    return getattr(engine, '_mesh_ctx', None)


def mesh_size(engine):
    """Per-chip divisor for HBM accounting: the number of devices the
    engine's executables span (1 for a single-chip engine)."""
    ctx = mesh_of(engine)
    return ctx.size if ctx is not None else 1


def _tree_paths(tree, is_leaf):
    """Dotted path labels for a pytree's leaves (for fallback records)."""
    out = []

    def walk(node, prefix):
        if is_leaf(node):
            out.append(prefix or 'param')
            return
        if isinstance(node, dict):
            # sorted: must match jax.tree_util's dict flatten order
            for k in sorted(node):
                walk(node[k], f'{prefix}.{k}' if prefix else str(k))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f'{prefix}[{i}]')
        else:
            out.append(prefix or 'param')
    walk(tree, '')
    return out

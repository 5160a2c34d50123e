"""The jit a train step runs under: state keeps its layout, one compile.

``step(*state, *rest) -> (loss, *state)`` — state being params, optimizer
state and whatever else the step carries — is donated and handed back for
the next call. Left to itself the compiler picks the layout of every
output, and jax names that layout its own way, so the state a step returns
never quite matches the state it was given: a NamedSharding spelled
differently, an Adam scalar that went in uncommitted and comes back
committed. The second call then looks new to jit and compiles the whole
step again (a minute on the 337M GPT), and on a real mesh nothing holds a
parameter to the layout it was placed in.

So the jit is built at the first call, with the state's in- AND
out-shardings pinned to what that call passes: a committed leaf keeps its
sharding, an uncommitted one (those scalars) is replicated over the mesh
beside them. What goes in comes out, donation always finds its buffer, and
the step compiles once. State that is not placed at all — every leaf
uncommitted — gets a plain jit and runs where jax puts it.

Kernels inside the step shard over ``mesh`` (ops/mesh_kernel.py).
"""
import jax
from jax.sharding import NamedSharding, PartitionSpec

from .. import observability as _obs
from ..ops import mesh_kernel


def _pinned(x):
    """The sharding a state leaf is held to, or None where jax places it
    (an uncommitted or host array; an abstract value naming no sharding)."""
    return (getattr(x, 'sharding', None)
            if getattr(x, 'committed', True) else None)


class jit_train_step:
    """Callable like the ``jax.jit`` of ``step`` (``__call__``, ``lower``).
    n_state: how many leading arguments — and trailing results after the
    loss — are the training state."""

    def __init__(self, step, mesh, n_state):
        self._step, self._mesh, self._n_state = step, mesh, n_state
        self._jit = self._keep = None
        self._calls = 0

    def _jitted(self, args):
        if self._jit is None:
            state = args[:self._n_state]
            pins = {}
            if any(_pinned(x) is not None
                   for x in jax.tree_util.tree_leaves(state)):
                rep = NamedSharding(self._mesh, PartitionSpec())
                self._keep = keep = jax.tree_util.tree_map(
                    lambda x: _pinned(x) or rep, state)
                pins = dict(
                    in_shardings=keep + (None,) * (len(args) - len(keep)),
                    out_shardings=(rep,) + keep)
            self._jit = mesh_kernel.jit(
                self._step, self._mesh,
                donate_argnums=tuple(range(self._n_state)), **pins)
        return self._jit

    def _settled(self, args):
        """args with the state's uncommitted arrays put where the jit pins
        them: an array's type names its mesh, so the first call must
        already pass what every later call passes."""
        if self._keep is None:
            return args
        return jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(x, sh)
            if isinstance(x, jax.Array) and not x.committed else x,
            args[:self._n_state], self._keep) + args[self._n_state:]

    def __call__(self, *args):
        # the host side of one step, numbered so that it can be laid
        # against that step's run of jit_step in the device trace
        self._calls += 1
        with _obs.span('train.dispatch', step=self._calls):
            jitted = self._jitted(args)
            return jitted(*self._settled(args))

    def lower(self, *args):
        jitted = self._jitted(args)
        return jitted.lower(*self._settled(args))

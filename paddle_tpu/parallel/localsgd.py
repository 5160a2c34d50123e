"""LocalSGD: k local optimizer steps per worker, then a parameter average.
Reference: python/paddle/distributed/fleet/meta_optimizers/localsgd_optimizer.py
(snapshot params, run local steps without grad all-reduce, periodically
all-reduce the param delta).

TPU-native design: instead of per-process replicas synced by NCCL, the
replicas are a LEADING ARRAY AXIS sharded over the mesh's dp axis and the
whole schedule lives inside ONE jit'd shard_map step:
  - each dp shard computes grads from ITS micro-batch only (no psum on the
    backward — that's the entire point of LocalSGD),
  - the inner optimizer update runs per shard,
  - every k-th step `lax.pmean` over the dp axis averages the replicas
    (one ICI all-reduce per k steps instead of per step).
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ['replicate_for_localsgd', 'collapse_replicas',
           'make_localsgd_train_step']


def replicate_for_localsgd(tree, mesh, axis='dp'):
    """Stack n_dp copies of each leaf along a new leading axis sharded over
    ``axis`` — one independent replica per dp group."""
    n = mesh.shape[axis]

    def rep(x):
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        return jax.device_put(
            stacked, NamedSharding(mesh, P(axis, *([None] * x.ndim))))
    return jax.tree_util.tree_map(rep, tree)


def collapse_replicas(tree):
    """Average the replica axis away (e.g. for eval/checkpoint)."""
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), tree)


def make_localsgd_train_step(loss_fn, opt, mesh, k_steps=4, axis='dp',
                             post_update=None):
    """Returns step(params_rep, opt_state_rep, batch, step_idx, lr)
    -> (mean_loss, new_params_rep, new_opt_state_rep).

    ``loss_fn(params, batch) -> scalar``; ``batch`` leading dim must divide
    by the dp degree; params_rep/opt_state_rep from replicate_for_localsgd.
    ``post_update(params) -> params`` runs after every local optimizer
    update (e.g. ASP mask re-application) — traced into the step.
    """
    rep_spec = P(axis)        # leading replica dim on every leaf
    dat_spec = P(axis)        # batch sharded over dp

    def body(params_rep, state_rep, batch, step_idx, lr):
        # inside shard_map every leaf has leading dim 1 (this shard's copy)
        params = jax.tree_util.tree_map(lambda x: x[0], params_rep)
        state = jax.tree_util.tree_map(lambda x: x[0], state_rep)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # NO grad psum here — local step is the point of LocalSGD
        params, state = opt.functional_apply(params, grads, state, lr)
        if post_update is not None:
            params = post_update(params)
        do_avg = (step_idx + 1) % k_steps == 0
        # pvary re-marks the pmean result as device-varying so both cond
        # branches carry the same vma type under shard_map
        params = jax.lax.cond(
            do_avg,
            lambda t: jax.tree_util.tree_map(
                lambda x: jax.lax.pcast(jax.lax.pmean(x, axis),
                                        (axis,), to='varying'), t),
            lambda t: t,
            params)
        loss = jax.lax.pmean(loss, axis)
        exp = jax.tree_util.tree_map(lambda x: x[None], (params, state))
        return loss, exp[0], exp[1]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep_spec, rep_spec, dat_spec, P(), P()),
                       out_specs=(P(), rep_spec, rep_spec))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params_rep, state_rep, batch, step_idx, lr):
        return fn(params_rep, state_rep, batch,
                  jnp.asarray(step_idx, jnp.int32),
                  jnp.asarray(lr, jnp.float32))

    return step

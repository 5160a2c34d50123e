"""paddle.utils.run_check: installation + device smoke test.
Reference: python/paddle/utils/install_check.py (single- and multi-device
fluid smoke run). TPU-native: report ``jax.devices()``, one jit'd
matmul+grad on the default device, and a sharded matmul across all local
devices when there are >1.
"""
__all__ = ['run_check']


def run_check():
    """Verify paddle_tpu works: prints a diagnosis, returns True. A backend
    that cannot start raises from ``jax.devices()``."""
    print('Running verify PaddlePaddle(TPU) program ...')
    import jax
    devs = jax.devices()
    print(f'Found {len(devs)} {devs[0].platform} device(s) '
          f'({devs[0].device_kind}).')

    import jax.numpy as jnp

    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    w = jnp.ones((128, 128), jnp.float32)
    x = jnp.ones((8, 128), jnp.float32)
    loss, grad = jax.jit(jax.value_and_grad(f))(w, x)
    loss.block_until_ready()
    assert grad.shape == w.shape
    print('PaddlePaddle(TPU) single-device check passed.')

    if len(devs) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(devs, ('dp',))
        xs = jax.device_put(jnp.ones((8 * len(devs), 128)),
                            NamedSharding(mesh, P('dp', None)))
        loss = jax.jit(f)(w, xs)
        loss.block_until_ready()
        print(f'PaddlePaddle(TPU) {len(devs)}-device sharded check passed.')

    print('PaddlePaddle(TPU) is installed successfully!')
    return True

"""The two dense operations the served families share: an RMSNorm computed
in float32 and a product that accumulates in float32. Plain ``jax.numpy``,
no kernel; a family rounds the result where its next product needs it."""
import jax
import jax.numpy as jnp


def rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def dot(a, w, cdt):
    """-> float32 (the caller rounds where the next product needs it)."""
    return jnp.dot(a.astype(cdt), w.astype(cdt),
                   preferred_element_type=jnp.float32)

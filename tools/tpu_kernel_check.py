"""On-chip pallas kernel parity validation.

The CI suite covers every kernel shape class in pallas interpret mode on
CPU; this battery re-runs the same parity checks COMPILED BY MOSAIC on the
TPU, in one process, one JSON line out:

  {"tpu_kernel_checks": {"fwd_causal": {"ok": true, "max_diff": ...}, ...},
   "all_ok": true, "platform": "tpu"}

Checks mirror tests/test_flash_attention.py: self-attn fwd+grad (causal /
full), key-padding mask, cross-attention (aligned-ends causal),
non-block-multiple seq (pad + static bound), GQA fwd+grad, flash_decode
(traced position), int8-KV flash_decode, the blockwise LM-head xent
(ops/xent.py) fwd+grad vs the naive logits path, in-kernel attention
dropout fwd+grad, and the engine's paged decode kernel (bf16 and int8 pages
of 128 rows, head width 64 and 128) vs its gather-and-softmax reference.

Run:  python tools/tpu_kernel_check.py        (needs the chip: fails without)
``chip_smoke.py`` runs the same battery in-process (``run_battery()``).
The tool never picks interpret mode by itself: off-chip it fails, and a
test that wants the interpreter calls ``fa.set_interpret(True)`` first.
"""
import importlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_battery():
    """-> {check name: {'ok', 'max_diff', 'tol'} | {'ok': False, 'error'}}.
    Raises without a TPU (unless a test asked for interpret mode)."""
    import jax
    import jax.numpy as jnp

    # the package re-exports shadow the submodule attributes — resolve the
    # real modules (same trick as tests/test_flash_attention.py)
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    pa = importlib.import_module('paddle_tpu.ops.paged_attention')
    xent = importlib.import_module('paddle_tpu.ops.xent')
    from paddle_tpu.ops.weight_only import dequantize_kv, quantize_kv

    if not fa._platform_ok():
        # otherwise flash_attention takes the very XLA path we compare
        # against and the parity checks pass vacuously
        raise RuntimeError(
            f'kernel battery needs a TPU, found '
            f'{jax.devices()[0].platform!r}')

    results = {}

    def check(name, fn, tol):
        try:
            diff = float(fn())
            results[name] = {'ok': bool(diff <= tol), 'max_diff': diff,
                             'tol': tol}
        except Exception as e:  # noqa: BLE001 — record, keep battery going
            results[name] = {'ok': False,
                             'error': f'{type(e).__name__}: {e}'[:300]}

    def rand(key, shape, dtype=jnp.float32):
        return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)

    def maxdiff(a, b):
        """RELATIVE max deviation: on real TPU both sides run their dots on
        the MXU (bf16 multiplicands, f32 accum) but with different tilings,
        so elementwise agreement is bounded by bf16 epsilon × magnitude —
        absolute f32 tolerances only make sense in CPU interpret mode."""
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6)

    # -- self-attention fwd/grad ------------------------------------------
    b, s, h, d = 2, 512, 4, 64
    q, k, v = (rand(i, (b, s, h, d)) for i in range(3))

    def fwd(causal):
        def f():
            got = fa.flash_attention(q, k, v, causal=causal)
            want = fa._jnp_attention(q, k, v, causal, None)
            return maxdiff(got, want)
        return f

    check('fwd_causal', fwd(True), 2e-2)
    check('fwd_full', fwd(False), 2e-2)

    def grad_causal():
        def loss_flash(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(fa._jnp_attention(q, k, v, True, None) ** 2)
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        return max(float(maxdiff(a, c)) for a, c in zip(g1, g2))
    check('grad_causal', grad_causal, 2e-2)

    # -- bf16 fwd ---------------------------------------------------------
    def bf16_fwd():
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        got = fa.flash_attention(qb, kb, vb, causal=True)
        want = fa._jnp_attention(qb, kb, vb, True, None)
        return maxdiff(got, want)
    check('fwd_bf16', bf16_fwd, 5e-2)

    # -- key-padding mask -------------------------------------------------
    def masked():
        mask = (jnp.arange(s)[None, :] < jnp.asarray([s, s // 2])[:, None])
        got = fa.flash_attention(q, k, v, causal=True, mask=mask)
        want = fa._jnp_attention(q, k, v, True, mask)
        return maxdiff(got, want)
    check('key_padding_mask', masked, 2e-2)

    # -- cross-attention (aligned-ends causal) ----------------------------
    def cross():
        qq = rand(7, (b, 256, h, d))
        got = fa.flash_attention(qq, k, v, causal=True)
        want = fa._jnp_attention(qq, k, v, True, None)
        return maxdiff(got, want)
    check('cross_causal', cross, 2e-2)

    # -- non-block-multiple seq -------------------------------------------
    def ragged():
        qq, kk, vv = (rand(i + 11, (b, 300, h, d)) for i in range(3))
        got = fa.flash_attention(qq, kk, vv, causal=True)
        want = fa._jnp_attention(qq, kk, vv, True, None)
        return maxdiff(got, want)
    check('non_block_multiple', ragged, 2e-2)

    # -- GQA fwd + grad ---------------------------------------------------
    kg, vg = (rand(i + 21, (b, s, 1, d)) for i in range(2))

    def gqa_fwd():
        got = fa.flash_attention(q, kg, vg, causal=True)
        want = fa._jnp_attention(q, kg, vg, True, None)
        return maxdiff(got, want)
    check('gqa_mqa_fwd', gqa_fwd, 2e-2)

    def gqa_grad():
        def lf(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) ** 2)

        def lr(q, k, v):
            return jnp.sum(fa._jnp_attention(q, k, v, True, None) ** 2)
        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, kg, vg)
        g2 = jax.grad(lr, argnums=(0, 1, 2))(q, kg, vg)
        return max(float(maxdiff(a, c)) for a, c in zip(g1, g2))
    check('gqa_mqa_grad', gqa_grad, 2e-2)

    # -- flash decode (traced position) -----------------------------------
    s_max, pos = 512, 173
    kc, vc = (rand(i + 31, (b, s_max, h, d)) for i in range(2))
    q1 = rand(33, (b, 1, h, d))

    def decode():
        assert fa.flash_decode_available(q1, kc)
        got = jax.jit(fa.flash_decode)(q1, kc, vc, jnp.int32(pos))
        want = fa._jnp_attention(
            q1, kc[:, :pos + 1], vc[:, :pos + 1], False, None)
        return maxdiff(got, want)
    check('decode_traced_pos', decode, 2e-2)

    # -- int8-KV flash decode ---------------------------------------------
    def decode_int8():
        kq, ks = quantize_kv(kc)
        vq, vs = quantize_kv(vc)
        kbank = {'int8': kq, 'scale': ks}
        vbank = {'int8': vq, 'scale': vs}
        got = jax.jit(fa.flash_decode_int8)(q1, kbank, vbank, jnp.int32(pos))
        kf = dequantize_kv(kq, ks, jnp.float32)
        vf = dequantize_kv(vq, vs, jnp.float32)
        want = fa._jnp_attention(
            q1, kf[:, :pos + 1], vf[:, :pos + 1], False, None)
        return maxdiff(got, want)
    check('decode_int8_kv', decode_int8, 2e-2)

    # -- blockwise LM-head xent vs naive ----------------------------------
    def xent_check():
        nn, hh, vv = 512, 256, 4096
        x = rand(41, (nn, hh)) * 0.1
        w = rand(42, (vv, hh)) * 0.05
        y = jax.random.randint(jax.random.PRNGKey(43), (nn,), 0, vv)

        def blockwise(x, w):
            return xent.softmax_xent_blockwise(x, w, y, 1024)

        def naive(x, w):
            logits = (x @ w.T).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
            return jnp.mean(lse - tgt)
        l1, g1 = jax.value_and_grad(blockwise, argnums=(0, 1))(x, w)
        l2, g2 = jax.value_and_grad(naive, argnums=(0, 1))(x, w)
        return max(float(abs(l1 - l2)),
                   *[float(maxdiff(a, c)) for a, c in zip(g1, g2)])
    check('blockwise_xent', xent_check, 2e-3)

    # -- in-kernel attention dropout (r5): fwd + bwd mask regen -----------
    def dropout_fwd():
        got = fa.flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                                 dropout_seed=42)
        want = fa._jnp_attention(q, k, v, True, None, drop_rate=0.3,
                                 seed=42)
        return maxdiff(got, want)
    check('dropout_fwd', dropout_fwd, 2e-2)

    def dropout_grad():
        def lf(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, dropout_rate=0.25,
                dropout_seed=7) ** 2)

        def lr(q, k, v):
            return jnp.sum(fa._jnp_attention(
                q, k, v, True, None, drop_rate=0.25, seed=7) ** 2)
        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        return max(float(maxdiff(a, c)) for a, c in zip(g1, g2))
    check('dropout_grad', dropout_grad, 2e-2)

    # -- paged decode (the GenerationEngine's decode-step kernel) ----------
    def paged(d, int8):
        nb, hq, ps, p_max = 4, 8, 128, 4
        n_pages = nb * p_max + 1                      # page 0 = trash
        kp, vp = (rand(i + 51, (n_pages, hq, ps, d), jnp.bfloat16)  # head-major
                  for i in range(2))
        qd = rand(53, (nb, 1, hq, d), jnp.bfloat16)
        # every slot owns p_max pages in a scrambled order, at its own depth
        table = jax.random.permutation(
            jax.random.PRNGKey(54), jnp.arange(1, n_pages)).reshape(
                nb, p_max).astype(jnp.int32)
        pos_v = jnp.asarray([0, 127, 128, ps * p_max - 1], jnp.int32)

        def f():
            if int8:
                kq, ks = quantize_kv(kp)
                vq, vs = quantize_kv(vp)
                kb = {'int8': kq, 'scale': ks}
                vb = {'int8': vq, 'scale': vs}
                assert pa.paged_attention_available(qd, kq)
                got = jax.jit(pa.paged_flash_decode_int8)(
                    qd, kb, vb, table, pos_v)
                want = pa.paged_attention_fallback(
                    qd, kb, vb, table, pos_v, jnp.float32)
            else:
                assert pa.paged_attention_available(qd, kp)
                got = jax.jit(pa.paged_flash_decode)(
                    qd, kp, vp, table, pos_v)
                want = pa.paged_attention_fallback(
                    qd.astype(jnp.float32), kp.astype(jnp.float32),
                    vp.astype(jnp.float32), table, pos_v, jnp.float32)
            return maxdiff(got, want)
        return f

    for d in (64, 128):
        check(f'paged_bf16_d{d}', paged(d, False), 5e-2)
        check(f'paged_int8_d{d}', paged(d, True), 5e-2)

    return results


def main():
    import jax
    platform = jax.devices()[0].platform
    try:
        results = run_battery()
    except RuntimeError as e:
        print(json.dumps({'all_ok': False, 'platform': platform,
                          'error': str(e)}))
        return 1
    all_ok = all(r.get('ok') for r in results.values())
    print(json.dumps({'tpu_kernel_checks': results, 'all_ok': all_ok,
                      'platform': platform}))
    return 0 if all_ok else 1


if __name__ == '__main__':
    sys.exit(main())

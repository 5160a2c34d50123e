"""Segment-level step-time breakdown for the bench GPT config, on chip.

Times each piece of the train step separately, each closed by a host read:

  full        jitted train step (grad + optimizer apply)
  grad        value_and_grad only
  fwd         loss forward only
  hidden      transformer stack without the LM-head loss
  opt         optimizer apply alone (precomputed grads)
  flash       flash attention fwd / fwd+bwd at model shapes, x layers
  gemm        sustained bf16 GEMM ceiling (sanity: how close is the chip
              to its datasheet peak — see _detect_peak — on a pure matmul)
  devtime     measured per-category device time for the full step: a
              bounded jax.profiler capture around live steps, attributed
              through the SHARED observability/devtime.py classifier
              (one event-classification table, not a drifting local copy)
              — emits devtime_{matmul,compute,collective,copy,infeed,
              idle}_ms, devtime_overlap_fraction, devtime_mfu_measured

Run in a bounded subprocess:  timeout 900 python tools/tpu_breakdown.py
"""
import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Clean self-exit BEFORE any outer bound SIGKILLs this chip-holding
# process. Partial results were already emitted incrementally.
signal.signal(signal.SIGALRM,
              lambda *_: (_ for _ in ()).throw(
                  SystemExit('breakdown: internal 2100s watchdog')))
signal.alarm(int(os.environ.get('BREAKDOWN_TIMEOUT', '2100')))

import jax
import jax.numpy as jnp
from functools import partial

import paddle_tpu as paddle
from paddle_tpu.models import gpt


def _detect_peak():
    """Per-chip bf16 peak for the MFU denominators, from the attached
    device's device_kind; a kind the table does not know raises."""
    from paddle_tpu.observability import perf
    return perf.peaks()[0]

BATCH, SEQ = 8, 1024
CFG = gpt.GPTConfig(vocab_size=32768, hidden_size=1024, num_layers=24,
                    num_heads=16, max_seq_len=SEQ, dtype='bfloat16',
                    remat=True, use_flash=True, remat_policy='dots')


def fence(*trees):
    leaves = jax.tree_util.tree_leaves(trees)
    return [float(jnp.asarray(l).ravel()[0]) for l in leaves[:1]]


def timeit(fn, *args, iters=10, warmup=1, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
    fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    fence(out)
    return (time.perf_counter() - t0) / iters


def main():
    key = jax.random.PRNGKey(0)
    params = gpt.init_params(CFG, key)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    opt = paddle.optimizer.AdamW(learning_rate=2e-4, weight_decay=0.01)
    opt_state = opt.functional_init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, 32768)
    lr = jnp.asarray(2e-4)
    peak = _detect_peak()
    res = {'n_params': n_params}

    def emit(k, v):
        res[k] = v
        print(json.dumps({k: v}), flush=True)   # incremental: survive timeouts

    # full step (no donation so params survive reuse across segments)
    def step(p, s, k, l, t, y):
        loss, grads = jax.value_and_grad(gpt.loss_fn)(p, t, y, CFG)
        np_, ns = opt.functional_apply(p, grads, s, l)
        return loss, np_, ns
    jstep = jax.jit(step)
    dt = timeit(lambda: jstep(params, opt_state, key, lr, toks, toks))
    emit('full_ms', dt * 1e3)
    emit('tokens_per_sec', BATCH * SEQ / dt)
    emit('mfu', 6.0 * n_params * res['tokens_per_sec'] / peak)

    # measured device-time attribution for the full step: profile a few
    # live steps, classify every event through the shared devtime table
    try:
        import shutil
        import tempfile
        from paddle_tpu.observability import devtime, perf
        perf.analyze('breakdown.full_step', jstep,
                     (params, opt_state, key, lr, toks, toks))
        prof_dir = tempfile.mkdtemp(prefix='pt_breakdown_prof_')
        t0 = time.perf_counter()
        with jax.profiler.trace(prof_dir):
            for _ in range(3):
                fence(jstep(params, opt_state, key, lr, toks, toks))
        prof_ms = 1e3 * (time.perf_counter() - t0)
        att = devtime.attribute(prof_dir, window_ms=prof_ms, publish=False)
        shutil.rmtree(prof_dir, ignore_errors=True)
        for cat, v in att['categories_ms'].items():
            emit(f'devtime_{cat}_ms', v)
        emit('devtime_overlap_fraction', att['overlap']['fraction'])
        emit('devtime_unknown_events', att['unknown_events'])
        emit('devtime_classifier_version', att['classifier_version'])
        mfu_m = (att.get('mfu_measured') or {}).get('total')
        if mfu_m is not None:
            emit('devtime_mfu_measured', mfu_m)
    except Exception as e:                   # noqa: BLE001 — partial data
        emit('devtime_error', f'{type(e).__name__}: {e}'[:300])

    # grad only
    jgrad = jax.jit(lambda p, t, y: jax.value_and_grad(gpt.loss_fn)(p, t, y, CFG))
    emit('grad_ms', timeit(lambda: jgrad(params, toks, toks)) * 1e3)

    # fwd loss only
    jfwd = jax.jit(lambda p, t, y: gpt.loss_fn(p, t, y, CFG))
    emit('fwd_ms', timeit(lambda: jfwd(params, toks, toks)) * 1e3)

    # hidden stack only (no LM head)
    jhid = jax.jit(lambda p, t: gpt.forward_hidden(p, t, CFG))
    emit('hidden_ms', timeit(lambda: jhid(params, toks)) * 1e3)

    # optimizer apply alone
    _, grads = jgrad(params, toks, toks)
    japply = jax.jit(lambda p, g, s, l: opt.functional_apply(p, g, s, l))
    emit('opt_ms', timeit(lambda: japply(params, grads, opt_state, lr)) * 1e3)

    # flash attention at model shapes, x layers (flash_attention wants
    # [B, S, H, D])
    from paddle_tpu.ops.flash_attention import flash_attention
    d = CFG.hidden_size // CFG.num_heads
    q = jax.random.normal(key, (BATCH, SEQ, CFG.num_heads, d), jnp.bfloat16)
    fa = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
    emit('flash_fwd_ms_x24', timeit(lambda: fa(q)) * 1e3 * CFG.num_layers)

    fab = jax.jit(jax.grad(lambda q: flash_attention(q, q, q, causal=True)
                           .astype(jnp.float32).sum()))
    emit('flash_fwdbwd_ms_x24', timeit(lambda: fab(q)) * 1e3 * CFG.num_layers)

    # GEMM ceiling
    a = jax.random.normal(key, (8192, 8192), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    dt = timeit(lambda: mm(a), iters=20)
    emit('gemm_tflops', 2 * 8192**3 / dt / 1e12)

    # ---- 1.3B rung breakdown (r5: the north-star model class) ----------
    # bf16 params + moments + full remat (the bench rung's memory story);
    # failures here must not lose the 337M numbers above
    try:
        del params, opt_state, grads        # free HBM before the big model
        big = gpt.GPTConfig(vocab_size=32768, hidden_size=2048,
                            num_layers=24, num_heads=16, max_seq_len=SEQ,
                            dtype='bfloat16', param_dtype='bfloat16',
                            remat=True, use_flash=True,
                            remat_policy='full')
        bparams = gpt.init_params(big, key)
        bn = sum(int(x.size) for x in jax.tree_util.tree_leaves(bparams))
        bstate = opt.functional_init(bparams)

        def bstep(p, s, l, t):
            loss, grads = jax.value_and_grad(gpt.loss_fn)(p, t, t, big)
            np_, ns = opt.functional_apply(p, grads, s, l)
            return loss, np_, ns
        jb = jax.jit(bstep)
        dt = timeit(lambda: jb(bparams, bstate, lr, toks), iters=5)
        emit('b13_full_ms', dt * 1e3)
        emit('b13_tokens_per_sec', BATCH * SEQ / dt)
        emit('b13_mfu', 6.0 * bn * res['b13_tokens_per_sec'] / peak)
        jbh = jax.jit(lambda p, t: gpt.forward_hidden(p, t, big))
        emit('b13_hidden_ms', timeit(lambda: jbh(bparams, toks),
                                     iters=5) * 1e3)
        jba = jax.jit(lambda p, g, s, l: opt.functional_apply(p, g, s, l))
        _, bg = jax.jit(lambda p, t: jax.value_and_grad(gpt.loss_fn)(
            p, t, t, big))(bparams, toks)
        emit('b13_opt_ms', timeit(lambda: jba(bparams, bg, bstate, lr),
                                  iters=5) * 1e3)
    except Exception as e:                   # noqa: BLE001 — partial data
        emit('b13_error', f'{type(e).__name__}: {e}'[:300])

    print(json.dumps(res))


if __name__ == '__main__':
    main()

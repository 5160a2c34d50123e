"""What ``models/family.py`` promises ``serving.GenerationEngine``, written
once: the cases take a row of tests/served_families.py, and a family's test
file binds them to its row,

    class TestZayaContract(Contract):
        row = FAMILIES['zaya']

so that a family's contract and its own layer's tests share one worker
(``--dist loadfile``), one set of weights and one reference. A helper, not
collected.

``Served`` makes a family's weights once, serves its traffic once and runs
the plain reference once over what was served; each behaviour is its own
test case and ASSERTS on those shared runs instead of paying for a run of
its own. The reference is ONE padded batch of a run's sequences (its
products are eager: every shape it has not met is traced and compiled op
by op in float32 'highest', which was most of what these cases cost when
each ran it a sequence at a time at lengths of its own). Where a case is
about a second engine ("fresh", "alone", "reading first") what must be
fresh is the pool and the slots, not the compile: the second engine is
built anew and borrows the first's jitted pair and executables
(``borrow``), as ``serving/fleet._clone_warmth`` hands a replica its
template's.
So a family compiles three geometries: the standard three slots, one slot
at every width, and the short pool.
"""
import contextlib
import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import family as family_mod
from paddle_tpu.ops.expert_grouped_matmul import expert_grouped_matmul
from paddle_tpu.parallel import routed_experts as rex
from paddle_tpu.serving import GenerationEngine

from served_families import REPO, prompts_of

fa = importlib.import_module('paddle_tpu.ops.flash_attention')

NEW = 20                            # tokens a request of the standard run
ONE_SLOT = (9, 1, 2, 3, 17)         # one after another through ONE slot
ONE_SLOT_NEW = 14
SHORT_POOL, SHORT_POOL_NEW = (7, 6, 5), 18
# every width: w - 1, w and w + 1 rows around every boundary a width of 32
# rows in pages of 4 has (a family of wider pages has some of them)
WIDTH, WIDTH_NEW = 32, 3
BOUNDARIES = (4, 8, 12, 16, 24, 32)
LENGTHS = sorted({n for w in BOUNDARIES for n in (w - 1, w, w + 1)
                  if 1 <= n <= WIDTH})
# every width against the full body: float32's rounding (XLA's CPU products
# split their sums by the operand's shape), not the bit
WIDTH_TOL = 5e-6


# every backend compile of the process, by the name of what was compiled
_compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **kw: _compiles.append(kw.get('fun_name'))
    if event.endswith('backend_compile_duration') else None)


# ---- the two orders, the one body, the traces ------------------------------

@contextlib.contextmanager
def reading_first():
    """Every ``GenerationEngine`` reads a decode step before it plans the
    next one: the order the engine had before its loop ran one step ahead
    of its read-back (PR 36). A test-local patch of ``_plan_step``, which
    gives no step a successor while its tokens are unread; so every row's
    input token is the host's and ``steps_overlapped`` stays 0."""
    plan = GenerationEngine._plan_step
    GenerationEngine._plan_step = (
        lambda self, unread: None if unread is not None
        else plan(self, unread))
    try:
        yield
    finally:
        GenerationEngine._plan_step = plan


@contextlib.contextmanager
def full_body():
    """Every ``GenerationEngine`` built pads every prompt to
    ``prefill_width``: the one body an engine had before it chose among
    ``family.prefill_widths``. A test-local patch of the rule."""
    rule = family_mod.prefill_widths
    family_mod.prefill_widths = (
        lambda width, page_size, pages=1: (int(width),))
    try:
        yield
    finally:
        family_mod.prefill_widths = rule


def traces_for(widths, rows):
    """The traces an engine of these ``prefill_widths`` has made once it
    has served prompts of these (uncached) rows with no ``warmup()`` before
    them: its step, and its prefill at each width one was padded to."""
    return 1 + len({next(w for w in widths if w >= n) for n in rows})


def traces_due(run):
    """What ``run.traces`` is to be: of a borrower none, the lender's
    executables serve it; of an engine with a pair of its own the step and
    a prefill at each width its prompts take."""
    if run.borrowed:
        return 0
    return traces_for(run.stats['prefill_widths'], map(len, run.prompts))


# ---- a family's shared runs ------------------------------------------------

def borrow(engine, like):
    """``engine`` calls ``like``'s jitted pair and the executables
    ``warmup()`` built it (same weights' structure, configuration and
    geometry: what ``serving/fleet._clone_warmth`` hands a replica). The
    pair's closures are ``like``'s: a trace ``engine`` causes is counted on
    ``like`` (its ``_trace_count``), not in ``engine``'s own ``stats()``,
    so a borrower's traces are read there (``traces_of``).
    -> ``engine``."""
    engine._fns = like._fns_pair()
    engine._aot.update(like._aot)
    engine._lender = like
    return engine


def traces_of(engine):
    """-> the traces counted so far where ``engine``'s calls land them:
    its own count and, of a borrower, its lender's."""
    lender = getattr(engine, '_lender', None)
    return engine._trace_count + (lender._trace_count if lender else 0)


class Run:
    """What one engine served: ``served`` [(tokens, rows [new, V])] a
    request, its ``stats()`` at the end, the engine (shut down; what a
    later engine of its geometry borrows the compiled pair from), and
    ``traces``: the traces the run made, on the engine or, of a borrower,
    on its lender (0: it ran the lender's executables and no other)."""

    def __init__(self, prompts, served, stats, engine, notes=None,
                 warmed=None, traces=None):
        self.prompts, self.served, self.stats = prompts, served, stats
        self.engine, self.notes, self.warmed = engine, notes, warmed
        self.traces = traces

    @property
    def tokens(self):
        return [toks for toks, _ in self.served]

    @property
    def borrowed(self):
        return getattr(self.engine, '_lender', None) is not None


def same(got, want, tol):
    """Two runs' tokens equal and their rows to ``tol`` (0: to the last
    bit)."""
    assert len(got.served) == len(want.served)
    for (toks, rows), (want_toks, want_rows) in zip(got.served, want.served):
        assert toks == want_toks
        if tol:
            np.testing.assert_allclose(rows, want_rows, atol=tol, rtol=0)
        else:
            np.testing.assert_array_equal(rows, want_rows)


class Served:
    """One row's weights, runs and reference rows, each made once, on first
    use (``served_of``: one a family a process)."""

    def __init__(self, row):
        self.row = row
        self.shape = row.shape()
        self.config = row.config(self.shape)
        self.family = row.family

    def prompts(self, lens):
        return prompts_of(lens, vocab=self.row.vocab)

    @functools.cached_property
    def weights(self):
        with self.row.patched():
            return self.row.weights(self.shape)

    @property
    def stacked(self):
        return self.weights[1]

    def serve(self, kw, prompts, new, like=None, stacked=None, config=None,
              patches=None, one_at_a_time=False, warm=False):
        """``prompts`` through a NEW engine -> Run. All queued before the
        engine starts (more requests than slots: the later ones are
        admitted while the first decode, into slots and pages others
        left), or ``one_at_a_time``. ``like``: an engine of the same
        geometry, weights' structure and configuration, whose jitted pair
        and built executables this one calls (it traces nothing then: the
        Run's ``traces`` 0, counted where they would land, on the
        lender). ``warm``: ``warmup()`` first; the Run's
        ``warmed`` is then (its report, the traces it made, what the
        traffic compiled after it). ``new``: tokens a request, or one
        count each."""
        news = new if isinstance(new, (list, tuple)) else [new] * len(prompts)
        warmed = None
        with self.row.patched(patches):
            eng = GenerationEngine(
                self.stacked if stacked is None else stacked,
                config or self.config, autostart=False, **kw)
            if like is not None:
                borrow(eng, like)
            before = traces_of(eng)
            if warm:
                warmed = [eng.warmup(), eng._trace_count]
                del _compiles[:]
            try:
                futs = []
                for p, n in zip(prompts, news):
                    futs.append(eng.submit(p, max_new_tokens=n,
                                           want_logits=True))
                    if one_at_a_time:
                        eng.start()
                        futs[-1].result(timeout=600)
                eng.start()
                served = [(f.result(timeout=600), np.stack(f.logits()))
                          for f in futs]
                notes = [f.row_notes() for f in futs]
                stats = eng.stats()
            finally:
                eng.shutdown()
        if warm:
            warmed.append([n for n in _compiles if n in ('prefill', 'step')])
        return Run(prompts, served, stats, eng, notes, warmed,
                   traces_of(eng) - before)

    def reference_rows(self, run, layers=None, shape=None):
        """The plain reference over ``run``'s sequences (prompt and served
        tokens but the last), ONE padded batch -> the rows each request's
        tokens were to be chosen from."""
        seqs = [np.concatenate([p, np.asarray(toks[:-1], np.int32)])
                for p, (toks, _) in zip(run.prompts, run.served)]
        batch = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for i, seq in enumerate(seqs):
            batch[i, :len(seq)] = seq
        out = np.asarray(self.row.reference(
            self.weights[0] if layers is None else layers,
            jnp.asarray(batch), self.shape if shape is None else shape))
        return [out[i, len(p) - 1:len(seq)]
                for i, (p, seq) in enumerate(zip(run.prompts, seqs))]

    def held_to_reference(self, run, new, tol, want=None, **kw):
        """``run``'s rows are the reference's to ``tol``, and its tokens
        the argmax of its rows: ``new`` of them a request (or one count
        each)."""
        want = self.reference_rows(run, **kw) if want is None else want
        news = new if isinstance(new, (list, tuple)) else [new] * len(want)
        assert len(run.served) == len(want) == len(news)
        for (toks, rows), ref_rows, n in zip(run.served, want, news):
            assert len(toks) == n == len(rows)
            np.testing.assert_allclose(rows, ref_rows, atol=tol, rtol=0)
            assert toks == [int(np.argmax(r)) for r in rows]

    # -- the standard three slots --------------------------------------------
    @functools.cached_property
    def standard(self):
        """Seven requests on three slots, ``NEW`` tokens each: the engine
        every other engine of this geometry borrows from."""
        return self.serve(self.row.engine, self.prompts(self.row.prompts),
                          NEW)

    @functools.cached_property
    def standard_reference(self):
        return self.reference_rows(self.standard)

    def like_standard(self, prompts, new, **kw):
        return self.serve(self.row.engine, prompts, new,
                          like=self.standard.engine, **kw)

    @functools.cached_property
    def standard_read_first(self):
        with reading_first():
            return self.like_standard(self.standard.prompts, NEW)

    @functools.cached_property
    def a_request_a_slot(self):
        """A request a slot, queued before the engine starts, so that every
        step holds the same rows in both orders -> (one step ahead,
        reading first)."""
        slots = self.row.engine['num_slots']
        prompts = self.standard.prompts[:slots]
        new = [12 + 4 * i for i in range(slots)]
        ahead = self.like_standard(prompts, new)
        with reading_first():
            return ahead, self.like_standard(prompts, new)

    @functools.cached_property
    def admitted_mid_decode(self):
        """-> (a request admitted while another is five tokens deep and
        still decoding, the same request alone in a fresh engine)."""
        first, late = self.prompts((11, 6))
        alone = self.like_standard([late], 12)
        with self.row.patched():
            eng = borrow(GenerationEngine(self.stacked, self.config,
                                          **self.row.engine),
                         self.standard.engine)
            before = traces_of(eng)
            with eng:
                running = eng.submit(first, max_new_tokens=30)
                stream = running.stream(timeout=300)
                for _ in range(5):          # the first is five tokens deep
                    next(stream)
                fut = eng.submit(late, max_new_tokens=12, want_logits=True)
                served = [(fut.result(timeout=300), np.stack(fut.logits()))]
                assert not running.done()   # and still decoding
                assert len(running.result(timeout=300)) == 30
                stats = eng.stats()
        return Run([late], served, stats, eng,
                   traces=traces_of(eng) - before), alone

    def counted(self, read, lens=(5, 11), new=4):
        """``read() -> {counter: value}`` before and after a run of its own
        on the standard geometry -> (what the run added, its stats)."""
        before = read()
        run = self.like_standard(self.prompts(lens), new)
        after = read()
        return {k: after[k] - before[k] for k in after}, run.stats

    # -- one slot, every width -----------------------------------------------
    @property
    def one_slot_engine(self):
        kw = {k: v for k, v in self.row.engine.items() if k != 'num_pages'}
        return dict(kw, num_slots=1, prefill_width=WIDTH)

    @functools.cached_property
    def widths(self):
        """Every length of ``LENGTHS``, one request at a time on one slot
        (a routed layer groups a step's rows: alone, a request's steps hold
        the same rows in every run) -> (through the narrow bodies, through
        the full-width body). The narrow engine is warmed up first: what
        every other engine of one slot borrows is what ``warmup()``
        built."""
        prompts = prompts_of(LENGTHS, vocab=self.row.vocab, seed=7)
        narrow = self.serve(self.one_slot_engine, prompts, WIDTH_NEW,
                            one_at_a_time=True, warm=True)
        with full_body():
            full = self.serve(self.one_slot_engine, prompts, WIDTH_NEW,
                              one_at_a_time=True, like=narrow.engine)
        return narrow, full

    def like_one_slot(self, prompts, new, **kw):
        return self.serve(self.one_slot_engine, prompts, new,
                          like=self.widths[0].engine, **kw)

    @functools.cached_property
    def one_slot(self):
        """``ONE_SLOT`` one after another through one slot (each starts in
        the row, and the pages, the last occupant left full: prompts of 1,
        2 and 3 rows among them, whose tails reach before row 0) -> (one
        step ahead, reading first, each alone in a fresh engine)."""
        prompts = self.prompts(ONE_SLOT)
        again = self.like_one_slot(prompts, ONE_SLOT_NEW)
        with reading_first():
            first = self.like_one_slot(prompts, ONE_SLOT_NEW)
        fresh = [self.like_one_slot([p], ONE_SLOT_NEW) for p in prompts]
        return again, first, fresh

    # -- the short pool ------------------------------------------------------
    @functools.cached_property
    def short_pool(self):
        """A pool too small for three growing sequences -> (one step
        ahead, reading first, each alone in the same pool: nothing is
        evicted then)."""
        kw = dict(self.row.engine)
        if self.row.short_pool is not None:
            kw['num_pages'] = self.row.short_pool
        prompts = self.prompts(SHORT_POOL)
        ahead = self.serve(kw, prompts, SHORT_POOL_NEW)
        with reading_first():
            first = self.serve(kw, prompts, SHORT_POOL_NEW,
                               like=ahead.engine)
        alone = self.serve(kw, prompts, SHORT_POOL_NEW, like=ahead.engine,
                           one_at_a_time=True)
        return ahead, first, alone

    # -- through the Pallas interpreter --------------------------------------
    @functools.cached_property
    def kernel(self):
        """The kernel-sized shape through the interpreter -> (the run, the
        reference's rows, new tokens, tol)."""
        shape, kw, lens, new, tol, patches = self.row.kernel()
        with self.row.patched(patches):
            layers, stacked = self.row.weights(shape)
        fa.set_interpret(True)
        try:
            run = self.serve(kw, self.prompts(lens), new, stacked=stacked,
                             config=self.row.config(shape), patches=patches)
        finally:
            fa.set_interpret(False)
        return run, self.reference_rows(run, layers, shape), new, tol


_SERVED = {}


def served_of(row):
    if row.name not in _SERVED:
        _SERVED[row.name] = Served(row)
    return _SERVED[row.name]


# ---- parallel/routed_experts as PR 39 had it --------------------------------

def accepted_routed_layer(lp, h, row_ok, *, held, top_k, n_group, topk_group,
                          scale, normalise=True):
    """``parallel/routed_experts.routed_experts`` as PR 39 had it, router
    and layer in one: what the accepted routed families' numbers were made
    by, before ``route`` and ``held_experts`` were split for a family whose
    router is its own."""
    cdt = h.dtype
    t = h.shape[0]
    chosen, w = rex.route(h, lp['router'], lp['router_bias'], top_k=top_k,
                          n_group=n_group, topk_group=topk_group,
                          scale=scale, normalise=normalise)
    tm = rex.tile_rows(t * top_k)
    pl_ = rex.plan(chosen, row_ok, held, tm)
    rows = jnp.take(h, pl_['src'], axis=0)
    gmm = lambda x, wt: expert_grouped_matmul(      # noqa: E731
        x, wt.astype(cdt), pl_['tile_expert'], pl_['n_tiles'], tm=tm)
    ex = lp['experts']
    act = (jax.nn.silu(gmm(rows, ex['gate']).astype(jnp.float32))
           * gmm(rows, ex['up']).astype(jnp.float32)).astype(cdt)
    out = gmm(act, ex['down'])
    y = rex.swiglu(lp['shared'], h, cdt)
    m = out.shape[0]
    picked = jnp.take(out, jnp.minimum(pl_['dest'], m - 1), axis=0)
    w_held = jnp.where(pl_['is_held'], w, 0.0).astype(cdt)
    return y + jnp.einsum('tk,tkh->th', w_held, picked,
                          preferred_element_type=jnp.float32).astype(cdt)


# ---- the contract ----------------------------------------------------------

class Contract:
    """Bound by a family's test file: ``row = FAMILIES[<name>]``."""
    row = None

    @property
    def served(self):
        return served_of(self.row)

    # -- served rows against the plain reference -----------------------------
    def test_engine_serves_the_reference_rows(self):
        """Logits, not tokens: the standard run's seven requests, each
        padded to the narrowest of the widths that holds it (``valid``
        short of it), ``NEW`` tokens each through the family's pool."""
        s = self.served
        s.held_to_reference(s.standard, NEW, self.row.tol,
                            want=s.standard_reference)

    def test_the_standard_run_traces_a_width_once_and_gives_the_pool_back(
            self):
        s, stats = self.served, self.served.standard.stats
        assert stats['evictions'] == 0
        assert len(stats['prefill_widths']) >= 2
        # the step, and four of the widths: what every row's prompts take
        assert stats['traces'] == traces_for(stats['prefill_widths'],
                                             self.row.prompts) == 1 + 4
        # every page is free again but a trash page a paged kind
        paged = len(s.standard.engine._kinds)
        assert stats['free_pages'] == stats['num_pages'] - paged
        assert stats['completed'] == len(self.row.prompts)
        assert stats['tokens'] == NEW * len(self.row.prompts)
        assert stats['state_bytes'] == 0 == stats['page_bytes']
        if not paged:   # no page kind: no page, and no paged step counted
            assert stats['num_pages'] == 0 < stats['steps']
            assert stats['paged_steps_walked'] == 0
            assert stats['paged_steps_dense'] == 0
        else:
            assert 0 < stats['paged_steps_walked'] <= stats[
                'paged_steps_dense']

    def test_engine_serves_the_reference_rows_through_the_kernels(self):
        """The same through the Pallas interpreter at the kernel-sized
        shape: the flash forward in the prefills, the family's paged (or
        state) kernel in the steps."""
        run, want, new, tol = self.served.kernel
        self.served.held_to_reference(run, new, tol, want=want)
        assert run.stats['evictions'] == 0
        assert run.stats['traces'] == traces_for(
            run.stats['prefill_widths'], map(len, run.prompts)) >= 2

    def test_the_whole_forward_is_the_references(self):
        s = self.served
        tokens = jnp.asarray(np.stack(s.prompts((21, 21))))
        with self.row.patched():
            got = self.row.forward(s.stacked, tokens, s.config)
        np.testing.assert_allclose(
            got, self.row.reference(s.weights[0], tokens, s.shape),
            atol=self.row.forward_tol, rtol=0)

    # -- the step in flight --------------------------------------------------
    @pytest.mark.parametrize('case', ['a_request_a_slot', 'refilled',
                                      'alone', 'evicted'])
    def test_one_step_ahead_serves_what_reading_first_serves(self, case):
        """The decode loop dispatches step N+1 before it reads step N (PR
        36). A step updates EVERY slot's row (state, tails) and a window
        kind gives back pages step N still reads, so the step in flight
        when a slot changes hands writes the old occupant's row once more:
        the new occupant's prefill, queued behind it, overwrites it before
        the first step that reads it. Same tokens and rows as a loop that
        reads each step before it dispatches the next, with the same
        executables: to the last bit where every step holds the same rows
        in both orders."""
        s = self.served
        tol = self.row.step_ahead_tol
        if case == 'a_request_a_slot':
            (ahead, first), tol = s.a_request_a_slot, 0.0
        elif case == 'refilled':
            # seven requests on three slots: a slot is filled again while
            # a step computed for its last occupant is still in flight
            ahead, first = s.standard, s.standard_read_first
        elif case == 'alone':
            # one slot: every request starts in the row the last one left
            ahead, first = s.one_slot[:2]
        else:
            # a pool too small: slots evicted with a step in flight
            ahead, first = s.short_pool[:2]
            evicts = self.row.short_pool is not None
            assert (ahead.stats['evictions'] >= 1) is evicts
            assert (first.stats['evictions'] >= 1) is evicts
        assert first.stats['steps_overlapped'] == 0
        assert first.stats['rows_discarded'] == 0
        assert ahead.stats['steps_overlapped'] > 0
        # the same executables in both orders: traced once by the engine
        # that made them and by no engine that borrowed them
        assert ahead.traces == traces_due(ahead)
        assert first.traces == 0
        same(ahead, first, tol)

    # -- slots: filled again, filled while others decode, evicted ------------
    def test_a_slot_filled_a_second_time_serves_what_a_fresh_engine_serves(
            self):
        """One slot, five requests one after another: each starts from
        zero state and tails in a row, and pages, the last occupant left
        full, and serves exactly what an engine that never held another
        serves."""
        again, _, fresh = self.served.one_slot
        assert again.stats['completed'] == len(ONE_SLOT)
        assert again.traces == 0    # a borrower's: counted on the lender
        for i, alone in enumerate(fresh):
            toks, rows = again.served[i]
            assert alone.stats['completed'] == 1
            assert alone.traces == 0
            assert toks == alone.served[0][0]
            np.testing.assert_array_equal(rows, alone.served[0][1])

    def test_a_request_admitted_while_others_decode_serves_what_it_serves_alone(  # noqa: E501
            self):
        admitted, alone = self.served.admitted_mid_decode
        assert admitted.traces == 0 == alone.traces
        assert admitted.tokens == alone.tokens
        np.testing.assert_allclose(admitted.served[0][1], alone.served[0][1],
                                   atol=max(self.row.step_ahead_tol, 1e-6),
                                   rtol=0)

    def test_an_evicted_request_regenerates_its_tokens(self):
        """A pool too small for three growing sequences: the engine
        evicts, the evicted restart from row 0 (their state is rebuilt
        with their pages) and every request's tokens are what it serves
        with the pool to itself. A family that holds no page evicts
        nothing."""
        ahead, _, alone = self.served.short_pool
        assert alone.stats['evictions'] == 0
        # evicted or not, a prompt's width and the step, traced once
        assert ahead.stats['traces'] == ahead.traces == traces_due(ahead)
        assert alone.traces == 0
        if self.row.short_pool is None:
            assert ahead.stats['evictions'] == 0 == ahead.stats['num_pages']
        else:
            assert ahead.stats['evictions'] >= 1
        assert ahead.tokens == alone.tokens
        assert all(len(t) == SHORT_POOL_NEW for t in ahead.tokens)

    # -- every width ---------------------------------------------------------
    @pytest.mark.parametrize('boundary', BOUNDARIES)
    def test_a_family_serves_at_every_width_what_the_full_body_serves(
            self, boundary):
        """Rows past ``valid`` are padding at every width: ``boundary`` - 1,
        ``boundary`` and ``boundary`` + 1 rows through the narrowest body
        that holds them serve the tokens and the rows they serve through
        the full-width body."""
        narrow, full = self.served.widths
        for n in (boundary - 1, boundary, boundary + 1):
            if n not in LENGTHS:
                continue
            at = LENGTHS.index(n)
            tokens, rows = narrow.served[at]
            want_tokens, want_rows = full.served[at]
            assert len(tokens) == WIDTH_NEW
            assert rows.shape == (WIDTH_NEW, self.row.vocab)
            assert tokens == want_tokens, (self.row.name, n)
            np.testing.assert_allclose(
                rows, want_rows, rtol=0, atol=WIDTH_TOL,
                err_msg=f'{self.row.name}, {n} rows')

    def test_the_counters_add_up_over_a_run(self):
        """What the prompts asked and what their bodies computed; a trace
        a width and the step's; the full-width body alone under the one
        rule."""
        s = self.served
        narrow, full = s.widths
        widths = family_mod.prefill_widths(
            WIDTH, s.one_slot_engine['page_size'], s.family.prefill_pages)
        body = lambda n: next(w for w in widths if w >= n)      # noqa: E731
        stats = narrow.stats
        assert stats['prefill_widths'] == widths and len(widths) >= 4
        assert stats['prefills'] == len(LENGTHS)
        assert stats['prefill_rows_asked'] == sum(LENGTHS)
        assert stats['prefill_rows_computed'] == sum(map(body, LENGTHS))
        assert stats['traces'] == 1 + len(widths)
        assert full.stats['prefill_widths'] == (WIDTH,)
        assert full.stats['prefill_rows_computed'] == WIDTH * len(LENGTHS)
        assert full.borrowed and full.traces == 0   # the narrow engine's own

    def test_after_warmup_no_width_traces_or_compiles(self):
        """``warmup()`` builds the step and the prefill at every width (a
        per-slot kind lowered with the slots it is told); live traffic at
        every width then traces and compiles nothing."""
        narrow = self.served.widths[0]
        report, traces, compiled = narrow.warmed
        widths = narrow.stats['prefill_widths']
        assert report['prebuilt'] == 1 + len(widths)
        assert report['skipped'] == 0
        assert set(narrow.engine._aot) == {'gen_decode'} | {
            f'gen_prefill.{w}' for w in widths}
        assert traces == 1 + len(widths) == narrow.stats['traces']
        assert compiled == []
        # every width ran
        assert {next(w for w in widths if w >= n) for n in LENGTHS} == set(
            widths)

    # -- what the engine holds -----------------------------------------------
    def test_the_engine_keeps_a_table_a_paged_kind_and_a_row_a_slot_kind(
            self):
        """An allocator and a table for each paged kind the family names
        (one, 'kv', and the table an array, for a family that names none),
        a row a slot and no page for a per-slot kind, and the bytes of
        both counted apart."""
        s = self.served
        kinds = (s.family.page_kinds(s.config) if s.family.page_kinds
                 else family_mod.ONE_KIND)
        paged = [k.name for k in kinds if not k.per_slot]
        per_slot = [k.name for k in kinds if k.per_slot]
        assert s.family.name == self.row.name
        eng = GenerationEngine(s.stacked, s.config, num_slots=2,
                               page_size=s.row.engine['page_size'],
                               autostart=False)
        try:
            assert [k.name for k in eng._kinds] == paged == list(eng._allocs)
            assert [k.name for k in eng._slot_kinds] == per_slot
            assert (eng._g_bytes is not None) == bool(per_slot)
            assert sorted(eng._num_pages) == sorted(paged)
            assert eng.num_pages == sum(
                2 * eng._held_max[k] + 1 for k in paged)
            tables = eng._tables(2, slots=np.asarray([1, 0], np.int32))
            if s.family.page_kinds is None:
                assert eng._kinds == family_mod.ONE_KIND
                assert list(eng._allocs.values()) == [eng._alloc]
                assert tables.shape == (2, eng.p_max)
                assert tables.dtype == np.int32
            else:
                assert sorted(tables) == sorted(paged + per_slot)
                assert all(tables[k].shape == (2, eng.p_max) for k in paged)
                assert all(list(tables[k]) == [1, 0] for k in per_slot)
                assert all(list(eng._tables(1)[k]) == [0] for k in per_slot)
            for k in kinds:     # a plane's axis 1: pages, or slots
                for plane in k.planes:
                    for leaf in jax.tree_util.tree_leaves(eng._pool[plane]):
                        assert leaf.shape[1] == (
                            2 if k.per_slot else eng._num_pages[k.name])
            stats = eng.stats()
            assert stats['state_bytes'] == 0 == stats['page_bytes']
            assert (stats['state_bytes_per_slot'] > 0) == bool(per_slot)
            assert set(eng._unit_bytes) == set(paged + per_slot)
            assert all(v > 0 for v in eng._unit_bytes.values())
        finally:
            eng.shutdown(drain=False)

    def test_an_engine_holds_matrices_in_the_compute_type_and_the_rest_float32(  # noqa: E501
            self):
        """``serve_params``: every leaf the family names a product's
        operand in the compute type, cast once; every other leaf as given.
        A family without one is held as given, leaf for leaf."""
        s = self.served
        cfg = self.row.config(s.shape, dtype='bfloat16')
        if s.family.serve_params is None:
            assert not self.row.matrices
            eng = GenerationEngine(s.stacked, cfg, num_slots=1,
                                   page_size=s.row.engine['page_size'],
                                   autostart=False)
            given = jax.tree_util.tree_leaves(s.stacked)
            held = jax.tree_util.tree_leaves(eng._params)
            eng.shutdown(drain=False)
            assert all(a is b for a, b in zip(given, held))
            return
        held = s.family.serve_params(s.stacked, cfg)
        leaves = jax.tree_util.tree_flatten_with_path(held)[0]
        given = dict(jax.tree_util.tree_flatten_with_path(s.stacked)[0])
        cast = set()
        for path, leaf in leaves:
            names = [getattr(k, 'key', None) for k in path]
            if (names[-1] in self.row.matrices
                    and not set(names) & set(self.row.kept_float32)):
                assert leaf.dtype == jnp.bfloat16, path
                cast.add(names[-1])
            else:
                assert leaf.dtype == jnp.float32, path
                assert leaf is given[path], path
        assert cast == set(self.row.matrices)
        # leaves already as wanted are handed back themselves
        again = s.family.serve_params(held, cfg)
        assert all(a is b for a, b in zip(
            jax.tree_util.tree_leaves(again),
            jax.tree_util.tree_leaves(held)))

    def test_a_family_that_prefills_from_row_0_is_refused_a_prefix_cache(
            self):
        s = self.served
        kw = dict(num_slots=2, page_size=s.row.engine['page_size'],
                  prefix_cache=True, autostart=False)
        if s.family.tail_prefill and s.family.page_kinds is None:
            eng = GenerationEngine(s.stacked, s.config, **kw)
            assert eng._prefix is not None
            eng.shutdown(drain=False)
        else:
            with pytest.raises(ValueError, match='no prefix cache'):
                GenerationEngine(s.stacked, s.config, **kw)

    # -- the configuration ---------------------------------------------------
    def test_the_published_defaults_are_the_cells_rows(self):
        """``<Config>()`` is the model as published: every key of the
        benchmark's configuration file that the class has reads the same,
        but those the file lists as ``reduced``, which read what it states
        as ``published``. A family no cell serves is named by no file."""
        configs = os.path.join(REPO, 'benchmark', 'configs')
        if self.row.cell is None:
            for name in os.listdir(configs):
                with open(os.path.join(configs, name)) as f:
                    assert f'serve_{self.row.name}' != json.load(f).get(
                        'runner'), name
            return
        with open(os.path.join(configs, self.row.cell + '.json')) as f:
            doc = json.load(f)
        assert doc['runner'] == f'serve_{self.row.name}'
        fields = self.row.config_cls.__dataclass_fields__
        if 'model' in doc:      # gpt's: the paper's table row, not a default
            own = {k: v for k, v in doc['model'].items() if k in fields}
            assert len(own) >= 4
            assert family_mod.family_of(
                self.row.config_cls(**own)).name == self.row.name
            return
        cfg = self.row.config_cls()
        plain = lambda v: tuple(v) if isinstance(v, list) else v  # noqa: E731
        checked = 0
        for key, value in doc.items():
            if (key not in fields or key in doc['reduced']
                    or isinstance(value, dict)):    # 'held': told in words
                continue
            assert plain(getattr(cfg, key)) == plain(value), key
            checked += 1
        assert checked >= 8
        for key in doc['reduced']:
            want = doc['published'].get(key)
            if key in fields and not isinstance(want, (str, type(None))):
                assert getattr(cfg, key) == want != doc[key], key

    def test_a_shape_the_family_does_not_write_is_refused(self, refusal):
        over, match = refusal
        with pytest.raises(ValueError, match=match):
            self.row.config(self.row.shape(**over))

    # -- the routed layer ----------------------------------------------------
    @pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
    def test_the_split_leaves_a_routed_family_its_bits(self, dtype,
                                                       monkeypatch):
        """``routed_experts`` is ``route`` and then ``held_experts`` (PR
        40): for a family that calls it, the layer's output and the whole
        forward's are what the one function of PR 39 gave, to the bit, and
        a family that hands ``held_experts`` the same router's answer
        itself gets the same. Every other family's forward calls no
        ``routed_experts``."""
        row = self.row
        cfg = row.config(row.shape(), dtype=dtype, param_dtype=dtype)
        tokens = jnp.asarray(np.stack(prompts_of((24, 24), vocab=row.vocab)))
        if row.routed is None:
            def refuse(*a, **kw):
                raise AssertionError('routed_experts called')
            monkeypatch.setattr(rex, 'routed_experts', refuse)
            with row.patched():     # traced, not run: a call would show
                params = jax.eval_shape(lambda: row.module.init_params(
                    cfg, jax.random.PRNGKey(4)))
                out = jax.eval_shape(
                    lambda p, t: row.forward(p, t, cfg), params, tokens)
            assert out.shape == (2, 24, row.vocab)
            return
        kw = dict(dict(n_group=1, topk_group=1),
                  held=cfg.held, **{k: getattr(cfg, name)
                                    for k, name in row.routed.items()})
        # any weights tell: the family's shared ones, in ``dtype``
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                        self.served.stacked)
        lp = params['layers'][-1]
        h = jax.random.normal(jax.random.PRNGKey(6),
                              (40, cfg.hidden_size)).astype(dtype)
        row_ok = jnp.arange(40) < 33
        got, _ = rex.routed_experts(lp, h, row_ok, **kw)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(accepted_routed_layer(lp, h, row_ok, **kw),
                       np.float32))
        chosen, w = rex.route(h, lp['router'], lp['router_bias'],
                              **{k: v for k, v in kw.items() if k != 'held'})
        again, _ = rex.held_experts(lp, h, row_ok, chosen, w,
                                    held=kw['held'])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(again, np.float32))
        after = np.asarray(row.forward(params, tokens, cfg), np.float32)
        monkeypatch.setattr(
            rex, 'routed_experts',
            lambda lp, h, row_ok, **kw: (
                accepted_routed_layer(lp, h, row_ok, **kw),
                jnp.zeros((5,), jnp.int32)))
        np.testing.assert_array_equal(
            after, np.asarray(row.forward(params, tokens, cfg), np.float32))

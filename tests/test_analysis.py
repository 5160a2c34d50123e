"""paddle_tpu.analysis — the static-analysis suite and its CI lint gate.

Fixture files under tests/fixtures/analysis/ are scanned as DATA (never
imported): each bad_* file must trigger its rules, clean.py and
pragmas.py must be silent, and the self-lint gate at the bottom runs the
full suite over the real paddle_tpu/ tree exactly as CI does.
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis import Baseline, run

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, 'tests', 'fixtures', 'analysis')


def _rules_for(name):
    findings, _ = run([os.path.join(FIXTURES, name)], root=FIXTURES)
    return findings, {f.rule for f in findings}


# ---- every rule fires on its fixture --------------------------------------

def test_bad_trace_triggers_every_trace_rule():
    findings, rules = _rules_for('bad_trace.py')
    assert rules == {'trace-host-sync', 'trace-host-branch',
                     'trace-nondeterminism', 'trace-closure-capture',
                     'trace-missing-donate'}
    # three distinct host-sync shapes: .item(), np.asarray, float()
    assert sum(f.rule == 'trace-host-sync' for f in findings) == 3


def test_bad_locks_triggers_every_lock_rule():
    findings, rules = _rules_for('bad_locks.py')
    assert rules == {'lock-cycle', 'lock-device-call', 'lock-blocking-call'}
    cycles = [f for f in findings if f.rule == 'lock-cycle']
    # one a->b->a ordering cycle plus one non-reentrant re-acquisition
    assert len(cycles) == 2
    assert any('cycle' in f.message for f in cycles)
    assert any('re-acquisition' in f.message for f in cycles)


def test_bad_sharding_triggers_every_shard_rule():
    findings, rules = _rules_for('bad_sharding.py')
    assert rules == {'shard-unknown-axis', 'shard-shadowed-rule',
                     'shard-mesh-reuse'}
    # both shadow shapes: dead-after-None and identical duplicate
    assert sum(f.rule == 'shard-shadowed-rule' for f in findings) == 2


def test_bad_syntax_reports_parse_error():
    _, rules = _rules_for('bad_syntax.py')
    assert rules == {'parse-error'}


def test_every_registered_rule_covered_by_fixtures():
    covered = set()
    for name in ('bad_trace.py', 'bad_locks.py', 'bad_sharding.py',
                 'bad_syntax.py'):
        covered |= _rules_for(name)[1]
    assert covered == set(analysis.RULES), \
        f'rules without a firing fixture: {set(analysis.RULES) - covered}'


# ---- suppression ----------------------------------------------------------

def test_clean_code_has_zero_findings():
    findings, _ = _rules_for('clean.py')
    assert findings == [], [f.format() for f in findings]


def test_pragmas_suppress_every_finding():
    findings, _ = _rules_for('pragmas.py')
    assert findings == [], [f.format() for f in findings]


def test_pragma_is_rule_specific(tmp_path):
    # a pragma for the WRONG rule must not suppress anything
    p = tmp_path / 'half.py'
    p.write_text(
        'import jax\n'
        '@jax.jit\n'
        'def f(x):\n'
        '    return x.item()  # pt-lint: disable=lock-cycle\n')
    findings, _ = run([str(p)], root=str(tmp_path))
    assert [f.rule for f in findings] == ['trace-host-sync']


# ---- baseline -------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    findings, _ = _rules_for('bad_trace.py')
    bl_path = tmp_path / 'baseline.json'
    Baseline.from_findings(findings, reason='fixture').save(str(bl_path))

    bl = Baseline.load(str(bl_path))
    assert all(bl.match(f) for f in findings)   # every finding grandfathered
    assert bl.stale_keys() == []                # ...and nothing left over

    # a finding disappearing -> its baseline entry reported stale
    bl = Baseline.load(str(bl_path))
    for f in findings[:-1]:
        assert bl.match(f)
    assert len(bl.stale_keys()) == 1


def test_finding_keys_survive_line_shifts(tmp_path):
    """Baseline keys must not churn when unrelated edits move lines."""
    src = open(os.path.join(FIXTURES, 'bad_trace.py')).read()
    a, b = tmp_path / 'a', tmp_path / 'b'
    a.mkdir(), b.mkdir()
    (a / 'mod.py').write_text(src)
    (b / 'mod.py').write_text('# shifted\n\n\n' + src)
    ka = {f.key for f in run([str(a / 'mod.py')], root=str(a))[0]}
    kb = {f.key for f in run([str(b / 'mod.py')], root=str(b))[0]}
    assert ka == kb


# ---- the CLI + the CI gate ------------------------------------------------

def _lint(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'lint.py'), *args],
        capture_output=True, text=True, cwd=REPO, timeout=120)


def test_cli_list_rules():
    r = _lint('--list-rules')
    assert r.returncode == 0
    for rid in analysis.RULES:
        assert rid in r.stdout


def test_cli_exit_codes_and_json():
    bad = os.path.join(FIXTURES, 'bad_locks.py')
    r = _lint(bad, '--json', '--no-baseline')
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload['ok'] is False and payload['total'] == 4
    assert payload['counts']['lock-cycle'] == 2

    r = _lint(os.path.join(FIXTURES, 'clean.py'), '--no-baseline')
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_baseline_workflow(tmp_path):
    bad = os.path.join(FIXTURES, 'bad_sharding.py')
    bl = str(tmp_path / 'bl.json')
    assert _lint(bad, '--baseline', bl, '--write-baseline').returncode == 0
    r = _lint(bad, '--baseline', bl, '--json')
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload['total'] == 0 and payload['baselined'] == 4


def test_repo_self_lint_gate():
    """THE CI GATE: the full suite over paddle_tpu/ (plus the sharding
    audit tool, which carries real trace-hygiene and sharding logic) must
    be clean — fix the finding, acknowledge it with a
    pragma, or baseline it with a reason. New hazards fail this tier-1
    test."""
    r = _lint(os.path.join(REPO, 'paddle_tpu'),
              os.path.join(REPO, 'tools', 'shard_check.py'), '--json')
    assert r.returncode == 0, f'lint gate failed:\n{r.stdout}\n{r.stderr}'
    payload = json.loads(r.stdout)
    assert payload['ok'] is True
    assert payload['files'] > 150            # the whole tree was scanned
    assert payload['stale_baseline'] == []   # baseline only ever shrinks


def test_lint_does_not_import_jax():
    """The linter must stay runnable anywhere: loading the analysis
    package through tools/lint.py must not pull in jax (or paddle_tpu)."""
    lint_path = os.path.join(REPO, 'tools', 'lint.py')
    code = ('import sys, runpy\n'
            'sys.argv = ["lint.py", "--list-rules"]\n'
            'try:\n'
            f'    runpy.run_path({lint_path!r}, run_name="__main__")\n'
            'except SystemExit as e:\n'
            '    assert (e.code or 0) == 0, e.code\n'
            'assert "jax" not in sys.modules, "lint imported jax"\n'
            'assert "paddle_tpu" not in sys.modules\n')
    r = subprocess.run([sys.executable, '-c', code], capture_output=True,
                       text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr

#!/usr/bin/env python
"""Static-analysis lint gate over paddle_tpu — the CI face of
``paddle_tpu.analysis`` (trace hygiene, lock order, sharding rules).

    python tools/lint.py [paths...]            # human output, exit 1 on findings
    python tools/lint.py paddle_tpu --json     # machine output (tests, CI)
    python tools/lint.py --list-rules          # rule catalogue
    python tools/lint.py --write-baseline      # grandfather current findings

Exit codes: 0 clean (every finding fixed, pragma'd, or baselined),
1 unsuppressed findings, 2 internal/usage error.

The baseline (tools/lint_baseline.json) holds explicitly-grandfathered
findings keyed independently of line numbers; stale entries are reported
so it only ever shrinks. Inline ``# pt-lint: disable=<rule>`` pragmas
suppress deliberate patterns at the site. Both paths are visible in
--json output, so the CI gate (tests/test_analysis.py) can refuse NEW
findings while tolerating the acknowledged ones.

The analysis package is loaded directly from its files — importing
``paddle_tpu`` itself would initialize jax, and the linter must run
anywhere in milliseconds with no accelerator stack at all.
"""
import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_DIR = os.path.join(REPO, 'paddle_tpu', 'analysis')
DEFAULT_BASELINE = os.path.join(REPO, 'tools', 'lint_baseline.json')


def _load_analysis():
    """Import paddle_tpu.analysis WITHOUT importing paddle_tpu (no jax)."""
    if 'paddle_tpu.analysis' in sys.modules:
        return sys.modules['paddle_tpu.analysis']
    name = '_pt_lint_analysis'
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_PKG_DIR, '__init__.py'),
        submodule_search_locations=[_PKG_DIR])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='lint.py', description='paddle_tpu static-analysis lint gate')
    ap.add_argument('paths', nargs='*', default=None,
                    help='files/dirs to scan (default: paddle_tpu)')
    ap.add_argument('--json', action='store_true', dest='as_json',
                    help='machine-readable output')
    ap.add_argument('--baseline', default=DEFAULT_BASELINE,
                    help='baseline file (default tools/lint_baseline.json)')
    ap.add_argument('--no-baseline', action='store_true',
                    help='ignore the baseline (report everything)')
    ap.add_argument('--write-baseline', action='store_true',
                    help='grandfather all current findings into --baseline')
    ap.add_argument('--rules', default=None,
                    help='comma-separated rule ids to restrict to')
    ap.add_argument('--root', default=None,
                    help='path root for relative finding paths '
                         '(default: repo root)')
    ap.add_argument('--list-rules', action='store_true')
    args = ap.parse_args(argv)

    try:
        analysis = _load_analysis()
    except Exception as e:     # noqa: BLE001 — surface as exit 2
        print(f'lint: failed to load analysis package: {e!r}',
              file=sys.stderr)
        return 2

    if args.list_rules:
        for rid in sorted(analysis.RULES):
            r = analysis.RULES[rid]
            print(f'{rid:24s} [{r.pass_name}] {r.summary}')
        return 0

    paths = args.paths or [os.path.join(REPO, 'paddle_tpu')]
    root = args.root or REPO
    rules = [r.strip() for r in args.rules.split(',')] if args.rules else None
    try:
        findings, n_files = analysis.run(paths, root=root, rules=rules)
    except Exception as e:     # noqa: BLE001 — surface as exit 2
        print(f'lint: internal error: {e!r}', file=sys.stderr)
        return 2

    if args.write_baseline:
        analysis.Baseline.from_findings(
            findings, reason='grandfathered').save(args.baseline)
        print(f'wrote {len(findings)} entries to {args.baseline}')
        return 0

    baseline = analysis.Baseline() if args.no_baseline else \
        analysis.Baseline.load(args.baseline)
    fresh, grandfathered = [], []
    for f in findings:
        (grandfathered if baseline.match(f) else fresh).append(f)
    stale = baseline.stale_keys()

    counts = {}
    for f in fresh:
        counts[f.rule] = counts.get(f.rule, 0) + 1

    if args.as_json:
        print(json.dumps({
            'ok': not fresh,
            'files': n_files,
            'total': len(fresh),
            'baselined': len(grandfathered),
            'stale_baseline': stale,
            'counts': counts,
            'findings': [f.to_json() for f in fresh],
        }, indent=1))
    else:
        for f in sorted(fresh, key=lambda f: (f.path, f.line, f.col)):
            print(f.format())
        bits = [f'{len(fresh)} finding(s)']
        if grandfathered:
            bits.append(f'{len(grandfathered)} baselined')
        if stale:
            bits.append(f'{len(stale)} STALE baseline entries '
                        '(remove them)')
        print(f'lint: scanned {n_files} files: ' + ', '.join(bits))
        if stale:
            for k in stale:
                print(f'  stale: {k}')
    return 1 if fresh else 0


if __name__ == '__main__':
    sys.exit(main())

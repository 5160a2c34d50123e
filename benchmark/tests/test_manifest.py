"""The loader finds every file BENCHMARK.json names and refuses a missing
one; names and units keep to the allowed characters; a cell, a
configuration, a traffic mix and a per-layer metric are each added by new
files and new entries alone."""
import json
import os
import re

import pytest

import util
from benchmark.harness import manifest


def test_every_file_the_manifest_names_is_there():
    assert manifest.Manifest().check()


def test_names_units_and_whys_keep_to_the_contract():
    doc = manifest.Manifest().doc
    assert set(doc) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
    name = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
    unit = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
    for m in doc['end_to_end'] + doc['per_layer']:
        assert name.match(m['name']) and unit.match(m['unit']), m
        assert m['better'] in ('lower', 'higher')
    for m in doc['end_to_end']:
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'workloads'}
        assert 0.01 <= m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
    for m in doc['per_layer']:
        assert set(m) <= {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert any(m['name'] == 'setup_s' for m in doc['end_to_end'])
    for w in doc['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert len(w['why']) <= 200 and w['chips'] in (1, 4)
    for c in doc['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert c['file'].startswith('benchmark/') and len(c['why']) <= 200
    four = sum(1 for w in doc['workloads'] if w['chips'] == 4)
    assert four <= max(1, len(doc['workloads']) // 4)
    names = [m['name'] for m in doc['end_to_end'] + doc['per_layer']]
    assert len(names) == len(set(names))
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        'BENCHMARK.json')) < 64 * 1024


def test_every_cell_reports_what_the_contract_asks():
    man = manifest.Manifest()
    for cell in man.cells:
        e2e = [m['name'] for m in man.cell_metrics(cell, 'end_to_end')]
        assert 'setup_s' in e2e and len(e2e) >= 2, cell
        assert man.cell_metrics(cell, 'per_layer'), cell
    for m in man.doc['per_layer']:
        movers = {c['name'] for c in man.cell_metrics_cells(m['moves'])}
        assert set(m['workloads']) <= movers, m['name']


def test_a_missing_file_is_refused(tmp_path):
    root = util.make_copy(tmp_path)
    man = manifest.Manifest(root)
    assert man.check()
    os.remove(os.path.join(root, 'benchmark', 'traffic', 'tiny-lm.json'))
    with pytest.raises(manifest.ManifestError, match='tiny-lm'):
        manifest.Manifest(root).check()


def test_a_metric_without_its_file_is_refused(tmp_path):
    root = util.make_copy(tmp_path)
    os.remove(os.path.join(root, 'benchmark', 'metrics',
                           'tiny_step_ms_max.json'))
    with pytest.raises(manifest.ManifestError, match='tiny_step_ms_max'):
        manifest.Manifest(root).check()


def test_one_of_each_is_added_by_files_and_entries_alone(tmp_path):
    """util.make_copy ADDS two configurations, a mix, two cells and a
    per-layer metric; no file that was there differs, bar the manifest's
    new entries."""
    root = util.make_copy(tmp_path)
    man = manifest.Manifest(root)
    assert man.check()
    assert {'tiny-train', 'tiny-train-mesh'} <= set(man.cells)
    assert man.metric_spec('tiny_step_ms_max')['reader'] == 'fact'
    for dirpath, _, files in os.walk(os.path.join(util.REPO, 'benchmark')):
        if '__pycache__' in dirpath or dirpath.endswith('tests') \
                or '/tests/' in dirpath:
            continue
        for f in files:
            src = os.path.join(dirpath, f)
            dst = os.path.join(root, os.path.relpath(src, util.REPO))
            with open(src, 'rb') as a, open(dst, 'rb') as b:
                assert a.read() == b.read(), src
    with open(os.path.join(util.REPO, 'BENCHMARK.json')) as f:
        before = json.load(f)
    for kind in ('configs', 'workloads', 'per_layer'):
        assert man.doc[kind][:len(before[kind])] == before[kind]


def test_an_unknown_workload_is_refused():
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest().cell('no-such-cell')

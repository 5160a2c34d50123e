"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own,
found by name; nothing here lists them."""
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, 'benchmark')
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


class ManifestError(Exception):
    pass


def _json(path):
    if not os.path.isfile(path):
        raise ManifestError(f'missing file: {os.path.relpath(path, ROOT)}')
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, root=ROOT):
    """benchmark/<kind>/<name>.py as a module (no package import, so a
    new file is enough to add one)."""
    path = os.path.join(root, 'benchmark', kind, name + '.py')
    if not os.path.isfile(path):
        raise ManifestError(f'missing file: benchmark/{kind}/{name}.py')
    spec = importlib.util.spec_from_file_location(
        f'benchmark_{kind}_{name}'.replace('-', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.doc = _json(os.path.join(root, 'BENCHMARK.json'))
        self.cells = {w['name']: w for w in self.doc['workloads']}
        self.configs = {c['name']: c for c in self.doc['configs']}
        self.end_to_end = {m['name']: m for m in self.doc['end_to_end']}
        self.per_layer = {m['name']: m for m in self.doc['per_layer']}

    def cell(self, name):
        if name not in self.cells:
            raise ManifestError(f'no workload named {name!r}')
        return self.cells[name]

    def config(self, cell):
        entry = self.configs.get(cell['config'])
        if entry is None:
            raise ManifestError(f"no config named {cell['config']!r}")
        return _json(os.path.join(self.root, entry['file']))

    def traffic(self, cell):
        return _json(os.path.join(self.root, 'benchmark', 'traffic',
                                  cell['traffic'] + '.json'))

    def metric_spec(self, name):
        """benchmark/metrics/<metric>.json: the reader that computes it and
        the reader's parameters."""
        return _json(os.path.join(self.root, 'benchmark', 'metrics',
                                  name + '.json'))

    def cell_metrics(self, cell_name, kind):
        """Metrics of ``kind`` ('end_to_end' | 'per_layer') this cell
        reports: those that list it, and those that list no cells."""
        out = []
        for m in self.doc[kind]:
            if 'workloads' not in m or cell_name in m['workloads']:
                out.append(m)
        return out

    def check(self):
        """Every name, unit and file the manifest depends on. Raises
        ManifestError with the first fault."""
        d = self.doc
        for kind in ('end_to_end', 'per_layer'):
            for m in d[kind]:
                if not NAME.match(m['name']):
                    raise ManifestError(f"bad metric name {m['name']!r}")
                if not UNIT.match(m['unit']):
                    raise ManifestError(f"bad unit {m['unit']!r}")
                if m['better'] not in ('lower', 'higher'):
                    raise ManifestError(f"{m['name']}: better?")
                if m['source'] not in SOURCES:
                    raise ManifestError(f"{m['name']}: source?")
                for w in m.get('workloads', ()):
                    self.cell(w)
        for m in d['per_layer']:
            if m['moves'] not in self.end_to_end:
                raise ManifestError(f"{m['name']} moves an unknown metric")
            spec = self.metric_spec(m['name'])
            load_module('readers', spec['reader'], self.root)
            movers = {c['name'] for c in self.cell_metrics_cells(m['moves'])}
            for w in m.get('workloads', self.cells):
                if w not in movers:
                    raise ManifestError(
                        f"{m['name']} lists {w}, which does not report "
                        f"{m['moves']}")
        for c in d['configs']:
            if not NAME.match(c['name']):
                raise ManifestError(f"bad config name {c['name']!r}")
        for w in d['workloads']:
            for key in ('name', 'config', 'traffic'):
                if not NAME.match(w[key]):
                    raise ManifestError(f'bad {key} {w[key]!r}')
            cfg = self.config(w)
            load_module('runners', cfg['runner'], self.root)
            load_module('reference', cfg['reference'], self.root)
            tr = self.traffic(w)
            load_module('generators', tr['generator'], self.root)
        return True

    def cell_metrics_cells(self, end_to_end_name):
        m = self.end_to_end[end_to_end_name]
        names = m.get('workloads', list(self.cells))
        return [self.cells[n] for n in names]

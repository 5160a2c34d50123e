"""The trace reduction on a small recorded-form fixture with known
answers (benchmark/tests/fixtures/trace_small.json)."""
import json
import os

import pytest

from benchmark.harness import manifest, trace

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ['bench.data', 'bench.dispatch', 'bench.loss_read']


@pytest.fixture()
def small():
    with open(os.path.join(HERE, 'fixtures', 'trace_small.json')) as f:
        return json.load(f)


def test_window_is_the_benchmarks_own_span(small):
    assert trace.window(small) == (1000, 11000)


def test_busy_union_idle_share_and_gaps(small):
    red = trace.reduce(small, SPANS)
    # busy: [1000, 7000) and [9000, 10000) of a 10000 ns window; the
    # operation that began before the window is clipped to it
    assert red['busy_s'] == pytest.approx(7000e-9)
    assert red['window_s'] == pytest.approx(10000e-9)
    reader = manifest.load_module('readers', 'device_idle')
    assert reader.read({}, {}, red) == pytest.approx(30.0)
    gaps = dict(red['idle_gaps'])
    # each gap goes to the SHORTEST host span over its midpoint
    assert gaps['all_gaps_under_bench.loss_read'] == pytest.approx(2000e-9)
    assert gaps['all_gaps_under_bench.data'] == pytest.approx(1000e-9)
    assert 'all_gaps_under_bench.dispatch' not in gaps


def test_self_time_takes_nested_operations_out_of_a_loop(small):
    ops = trace.device_ops(small)[0]
    by = trace.time_by_name(trace.clip(ops, 1000, 11000))
    loop = [k for k in by if k.startswith('%while.1')][0]
    assert by[loop] == pytest.approx(4000 - 1000 - 1500)
    top = dict(trace.reduce(small, SPANS)['device_ops'])
    assert top['fusion.1 fusion f32[8]'] == pytest.approx(1800e-9)
    assert top['fusion.0 fusion f32[8]'] == pytest.approx(200e-9)  # clipped
    assert top['cc.1 custom-call bf16[2,4]'] == pytest.approx(1000e-9)


def test_kernel_time_by_name_pattern_and_roofline(small):
    red = trace.reduce(small, SPANS)
    secs, calls = trace.matching_time(red['events'][0], r' custom-call\(')
    assert (secs, calls) == (pytest.approx(1000e-9), 1)
    share = manifest.load_module('readers', 'kernel_share')
    assert share.read({'pattern': r' custom-call\('}, {}, red) == \
        pytest.approx(100 * 1000 / 7000)
    assert share.read({'pattern': 'no_such_kernel'}, {}, red) is None


def test_module_runs_count_the_part_inside_the_window(small):
    red = trace.reduce(small, SPANS)
    # jit_step runs [1000, 10000) inside the window [1000, 11000)
    assert red['module_runs'] == {'jit_step': pytest.approx(1.0)}
    assert trace.module_runs(small, 1000, 5500) == {
        'jit_step': pytest.approx(0.5)}


def test_roofline_share_counts_calls_by_the_programs_runs(small):
    red = trace.reduce(small, SPANS)
    reader = manifest.load_module('readers', 'kernel_roofline')
    facts = {'shape': {'num_heads': 1, 'hidden_size': 64}, 'batch': 1,
             'seq': 128, 'mesh': {}, 'remat_policy': 'none', 'layers': 1,
             'device_kind': 'TPU v5 lite'}
    got = reader.read({'pattern': r' custom-call\(', 'module': 'jit_step',
                       'kernel': 'flash_attention'}, facts, red)
    fa = manifest.load_module('kernels', 'flash_attention')
    from benchmark.harness import device
    least = fa.least_seconds(facts, 1.0, device.peaks('TPU v5 lite'))
    assert got == pytest.approx(100 * least['seconds'] / 1000e-9)


def test_exposed_collective_share(small):
    red = trace.reduce(small, SPANS)
    reader = manifest.load_module('readers', 'collective_exposed')
    assert reader.read({}, {}, red) == pytest.approx(10.0)


def test_a_trace_without_device_operations_is_refused():
    empty = {'planes': [{'name': '/host:CPU', 'lines': [
        {'name': 't', 'events': [['bench.trace_window', 0, 10]]}]}]}
    with pytest.raises(ValueError):
        trace.reduce(empty, SPANS)


def test_label_of_an_hlo_text_name():
    assert trace.label(
        '%checkpoint.19 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, '
        'bf16[128,1024,64]{2,1,0}) custom-call(bf16[128,1024,64]{2,1,0} '
        '%bitcast.422)') == 'checkpoint.19 custom-call bf16[128,1024,64]'
    assert trace.label('jit_step(1)') == 'jit_step(1)'

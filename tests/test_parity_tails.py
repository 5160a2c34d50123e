"""VERDICT r2 #6: namespace parity tails — utils / inference / incubate /
device.cuda / fleet re-exports, each exercised, not just imported."""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle


def test_utils_deprecated_warns():
    @paddle.utils.deprecated(since='2.0', update_to='paddle.new_api')
    def old_api(x):
        return x + 1

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        assert old_api(1) == 2
    assert any('deprecated' in str(x.message) for x in w)
    assert 'paddle.new_api' in old_api.__doc__


def test_utils_unique_name():
    un = paddle.utils.unique_name
    a, b = un.generate('fc'), un.generate('fc')
    assert a != b and a.startswith('fc') and b.startswith('fc')
    with un.guard('scope'):
        c = un.generate('fc')
        assert c.startswith('scope')
    d = un.generate('fc')
    assert not d.startswith('scope')


def test_utils_require_version():
    paddle.utils.require_version('0.0.1')
    with pytest.raises(Exception):
        paddle.utils.require_version('99.0.0')


def test_utils_dlpack_roundtrip():
    t = paddle.to_tensor(np.arange(6, dtype='float32').reshape(2, 3))
    cap = paddle.utils.dlpack.to_dlpack(t)
    back = paddle.utils.dlpack.from_dlpack(cap)
    np.testing.assert_array_equal(back.numpy(), t.numpy())


def test_utils_dlpack_from_torch_capsule():
    torch = pytest.importorskip('torch')
    t = torch.arange(4, dtype=torch.float32)
    cap = torch.utils.dlpack.to_dlpack(t)       # legacy one-shot capsule
    back = paddle.utils.dlpack.from_dlpack(cap)
    np.testing.assert_array_equal(back.numpy(), [0., 1., 2., 3.])


def test_utils_download_local_and_missing(tmp_path):
    dl = paddle.utils.download
    p = tmp_path / 'weights.bin'
    p.write_bytes(b'abc')
    assert dl.get_path_from_url(str(p), decompress=False) == str(p)
    with pytest.raises(FileNotFoundError):
        dl.get_path_from_url('https://example.com/no-such-file.bin',
                             root_dir=str(tmp_path))
    with pytest.raises(IOError):
        dl.get_path_from_url(str(p), md5sum='0' * 32, decompress=False)


def test_utils_cpp_extension_builds_and_runs(tmp_path):
    src = tmp_path / 'addmul.cc'
    src.write_text('extern "C" long addmul(long a, long b) '
                   '{ return a * b + 1; }\n')
    lib = paddle.utils.cpp_extension.load(
        'addmul_test', [str(src)], build_directory=str(tmp_path))
    import ctypes
    lib.addmul.restype = ctypes.c_long
    assert lib.addmul(6, 7) == 43


def test_utils_run_check_smoke(capsys):
    assert paddle.utils.run_check()
    assert 'successfully' in capsys.readouterr().out


def test_inference_tails():
    from paddle_tpu import inference as inf
    assert inf.Tensor is not None and inf.DataType.FLOAT32 == 'float32'
    assert inf.get_num_bytes_of_data_type(inf.DataType.INT64) == 8
    assert inf.get_num_bytes_of_data_type('float32') == 4
    assert 'paddle_tpu' in inf.get_version()


def test_inference_predictor_pool(tmp_path):
    import paddle_tpu.nn as nn

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, x):
            return self.fc(x)

    from paddle_tpu import inference as inf
    net = Net()
    net.eval()
    path = os.path.join(str(tmp_path), 'pool')
    paddle.jit.save(net, path,
                    input_spec=[paddle.static.InputSpec([None, 4], 'float32')])
    pool = inf.PredictorPool(inf.Config(path + '.pdmodel'), 2)
    x = np.random.rand(3, 4).astype('float32')
    (a,) = pool.retrive(0).run([x])
    (b,) = pool.retrive(1).run([x])
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_incubate_segment_ops():
    d = paddle.to_tensor(np.array([[1., 2.], [3., 4.], [5., 6.]], 'float32'))
    ids = paddle.to_tensor(np.array([0, 0, 1], 'int64'))
    np.testing.assert_allclose(paddle.incubate.segment_sum(d, ids).numpy(),
                               [[4., 6.], [5., 6.]])
    np.testing.assert_allclose(paddle.incubate.segment_mean(d, ids).numpy(),
                               [[2., 3.], [5., 6.]])
    np.testing.assert_allclose(paddle.incubate.segment_max(d, ids).numpy(),
                               [[3., 4.], [5., 6.]])
    np.testing.assert_allclose(paddle.incubate.segment_min(d, ids).numpy(),
                               [[1., 2.], [5., 6.]])


def test_device_cuda_shims():
    cuda = paddle.device.cuda
    # tests force the CPU platform -> 0 accelerator chips (reference
    # semantics: CUDA-free host reports 0)
    assert cuda.device_count() == 0
    cuda.synchronize()
    s = cuda.current_stream()
    s.synchronize()
    e = s.record_event()
    assert e.query()
    cuda.empty_cache()
    assert paddle.device.get_cudnn_version() is None
    assert paddle.device.ParallelEnv is not None
    assert paddle.device.is_compiled_with_rocm() is False


def test_fleet_reexports_and_util():
    from paddle_tpu.distributed import fleet
    for s in ('Role', 'DatasetBase', 'InMemoryDataset', 'QueueDataset',
              'FileInstantDataset', 'BoxPSDataset', 'MultiSlotDataGenerator',
              'MultiSlotStringDataGenerator', 'metrics',
              'CommunicateTopology', 'HybridCommunicateGroup'):
        assert hasattr(fleet, s), s
    out = fleet.util.all_reduce(np.array([1.0, 2.0]), mode='sum')
    np.testing.assert_allclose(np.asarray(out), [1.0, 2.0])  # 1-proc identity
    fleet.util.barrier()


def test_fleet_metrics():
    from paddle_tpu.distributed import fleet
    assert float(fleet.metrics.sum(np.array([3.0]))[0]) == 3.0
    assert fleet.metrics.mae(np.array([2.0]), np.array([4.0])) == 0.5
    assert fleet.metrics.rmse(np.array([16.0]), np.array([4.0])) == 2.0
    assert fleet.metrics.acc(np.array([3.0]), np.array([4.0])) == 0.75
    auc = fleet.metrics.auc(np.array([0, 0, 10]), np.array([10, 0, 0]))
    assert auc > 0.99      # perfectly separated -> ~1.0


def test_fleet_data_generator():
    from paddle_tpu.distributed import fleet

    class G(fleet.MultiSlotDataGenerator):
        def generate_sample(self, line):
            def gen():
                toks = line.split()
                yield [('ids', [int(t) for t in toks]), ('label', [1])]
            return gen

    lines = G().run_from_memory(['1 2 3', '4 5'])
    assert lines == ['3 1 2 3 1 1\n', '2 4 5 1 1\n']


def test_utils_image_util():
    iu = paddle.utils.image_util
    im = (np.random.RandomState(0).rand(40, 60, 3) * 255).astype('uint8')
    r = iu.resize_short(im, 32)
    assert min(r.shape[:2]) == 32 and r.shape[0] == 32   # short side = H
    c = iu.center_crop(r, 24)
    assert c.shape[:2] == (24, 24)
    f = iu.left_right_flip(c)
    np.testing.assert_array_equal(f[:, 0], c[:, -1])
    t = iu.simple_transform(im, 36, 32, is_train=False,
                            mean=[127.0, 127.0, 127.0])
    assert t.shape == (3, 32, 32) and t.dtype == np.float32


def test_utils_gast_and_op_checker():
    assert paddle.utils.gast.parse('x = 1')            # stdlib ast role
    checker = paddle.utils.OpLastCheckpointChecker()
    assert checker.filter_updates('matmul') == []


def test_incubate_auto_checkpoint_and_layer_helper(tmp_path, monkeypatch):
    monkeypatch.setenv('PADDLE_CHECKPOINT_DIR', str(tmp_path))
    acp = paddle.incubate.auto_checkpoint
    assert list(acp.train_epoch_range(2)) == [0, 1]
    assert list(acp.train_epoch_range(4)) == [2, 3]    # resumed
    h = paddle.incubate.LayerHelper('fc')
    w = h.create_parameter(shape=[4, 2])
    b = h.create_parameter(shape=[2], is_bias=True)
    assert list(w.shape) == [4, 2] and not w.stop_gradient
    assert float(np.abs(np.asarray(b.numpy())).sum()) == 0.0


def test_inference_convert_to_mixed_precision(tmp_path):
    import paddle_tpu.nn as nn
    from paddle_tpu import inference as inf

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, x):
            return self.fc(x)

    net = Net()
    net.eval()
    src = os.path.join(str(tmp_path), 'fp32')
    paddle.jit.save(net, src,
                    input_spec=[paddle.static.InputSpec([None, 4], 'float32')])
    dst = inf.convert_to_mixed_precision(
        src + '.pdmodel', save_model_path=os.path.join(str(tmp_path), 'bf16'))
    from paddle_tpu.jit import load_saved_artifacts
    params, _buffers, meta, exe = load_saved_artifacts(dst)
    import jax.numpy as jnp
    assert all(v.dtype == jnp.bfloat16 for v in params.values())
    assert meta['precision'] == 'bfloat16' and exe is None
    # serves through attach_layer at the stored precision
    pred = inf.create_predictor(inf.Config(dst + '.pdmodel'))
    pred.attach_layer(Net())
    (out,) = pred.run([np.random.rand(3, 4).astype('float32')])
    assert out.shape == (3, 2)


def test_reference_all_exports_zero_missing():
    """Every name in every reference __all__ (28 namespaces) resolves on the
    corresponding paddle_tpu namespace (r4 audit; keeps future drift loud)."""
    import ast
    import importlib
    import os

    def public_names(p):
        names = set()
        for node in ast.walk(ast.parse(open(p).read())):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == '__all__':
                        try:
                            names |= set(ast.literal_eval(node.value))
                        except Exception:
                            pass
        return names

    ref = '/root/reference/python/paddle'
    if not os.path.isdir(ref):
        pytest.skip('reference tree unavailable')
    pairs = [
        ('__init__.py', 'paddle_tpu'), ('nn/__init__.py', 'paddle_tpu.nn'),
        ('nn/functional/__init__.py', 'paddle_tpu.nn.functional'),
        ('nn/initializer/__init__.py', 'paddle_tpu.nn.initializer'),
        ('static/__init__.py', 'paddle_tpu.static'),
        ('static/nn/__init__.py', 'paddle_tpu.static.nn'),
        ('optimizer/lr.py', 'paddle_tpu.optimizer.lr'),
        ('nn/utils/__init__.py', 'paddle_tpu.nn.utils'),
        ('optimizer/__init__.py', 'paddle_tpu.optimizer'),
        ('metric/__init__.py', 'paddle_tpu.metric'),
        ('vision/__init__.py', 'paddle_tpu.vision'),
        ('vision/models/__init__.py', 'paddle_tpu.vision.models'),
        ('vision/transforms/__init__.py', 'paddle_tpu.vision.transforms'),
        ('vision/datasets/__init__.py', 'paddle_tpu.vision.datasets'),
        ('vision/ops.py', 'paddle_tpu.vision.ops'),
        ('text/__init__.py', 'paddle_tpu.text'),
        ('io/__init__.py', 'paddle_tpu.io'),
        ('distributed/__init__.py', 'paddle_tpu.distributed'),
        ('distributed/fleet/__init__.py', 'paddle_tpu.distributed.fleet'),
        ('distributed/fleet/utils/__init__.py',
         'paddle_tpu.distributed.fleet.utils'),
        ('distributed/utils.py', 'paddle_tpu.distributed.utils'),
        ('amp/__init__.py', 'paddle_tpu.amp'),
        ('autograd/__init__.py', 'paddle_tpu.autograd'),
        ('jit/__init__.py', 'paddle_tpu.jit'),
        ('utils/__init__.py', 'paddle_tpu.utils'),
        ('incubate/__init__.py', 'paddle_tpu.incubate'),
        ('inference/__init__.py', 'paddle_tpu.inference'),
        ('onnx/__init__.py', 'paddle_tpu.onnx'),
        ('linalg.py', 'paddle_tpu.linalg'),
        ('regularizer.py', 'paddle_tpu.regularizer'),
        ('distribution.py', 'paddle_tpu.distribution'),
    ]
    problems = []
    for refp, mod in pairs:
        full = os.path.join(ref, refp)
        if not os.path.exists(full):
            continue
        want = public_names(full)
        if not want:
            continue
        try:
            ours = importlib.import_module(mod)
        except ModuleNotFoundError:
            parent, _, attr = mod.rpartition('.')
            ours = getattr(importlib.import_module(parent), attr)
        missing = sorted(n for n in want if not hasattr(ours, n))
        if missing:
            problems.append((mod, missing))
    assert not problems, problems

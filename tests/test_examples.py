"""candle_uno workload + runnable examples (reference §2.11 example apps
double as integration tests; SURVEY §4)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.models.candle_uno import build_candle_uno

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_candle_uno_trains():
    """Shrunk feature shapes, same graph shape as candle_uno.cc."""
    shapes = {"dose": 1, "cell.rnaseq": 30, "drug.descriptors": 40,
              "drug.fingerprints": 20}
    feats = {"dose1": "dose", "dose2": "dose", "cell.rnaseq": "cell.rnaseq",
             "drug1.descriptors": "drug.descriptors",
             "drug1.fingerprints": "drug.fingerprints"}
    cfg = ff.FFConfig(batch_size=16, compute_dtype="float32")
    model, inputs, preds = build_candle_uno(
        cfg, dense_layers=(32, 32), dense_feature_layers=(16, 16),
        feature_shapes=shapes, input_features=feats)
    model.compile(ff.SGDOptimizer(lr=0.01), final_tensor=preds)
    model.init_layers(seed=0)
    assert model.loss_type == "mean_squared_error_avg_reduce"
    # dose towers pass through raw (width-1 features are not encoded),
    # multi-dim features get towers: concat width = 1 + 1 + 3*16
    assert model.get_parameter_by_name("head/kernel") is not None
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((16, shapes[k])).astype(np.float32)
          for k in feats.values()]
    y = rng.random((16, 1)).astype(np.float32)
    losses = [float(model.train_batch(*xs, y)) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# An example that asks for no devices runs on ONE.  Its script takes "all
# visible chips" (``-ll:tpu`` 0), and a child of this suite would see the
# eight virtual devices of tests/conftest.py: eight-way data parallelism
# nothing in a one-chip example needs, whose every step is a rendezvous
# of eight threads that, beside five other busy workers, ran into XLA's
# 40 s limit (ROADMAP D15).  One device has no rendezvous to starve.
_ONE_DEVICE = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def _run_example(script, *extra, env=None, timeout=600):
    from tests.subproc import cached_env
    env = cached_env(**{**_ONE_DEVICE, **(env or {})})
    out = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu.cli", os.path.join(REPO, script),
         *extra],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    return out


@pytest.mark.parametrize("script", [
    "examples/python/native/mnist_mlp.py",
    "examples/python/native/mnist_mlp_accum.py",
    "examples/python/native/print_layers.py",
    "examples/python/native/mnist_mlp_attach.py",
    "examples/python/native/tensor_attach.py",
    "examples/python/native/print_input.py",
])
def test_native_example_scripts_run(script):
    _run_example(script, "-b", "32", "-e", "1")


def test_pipeline_moe_example_runs():
    """{n,e,p} composition example (round-4 PipelineSegment showcase) —
    on a real 8-device mesh, not the single-device fallback."""
    out = _run_example(
        "examples/python/native/pipeline_moe_transformer.py", "-b", "8",
        "-e", "1",
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "THROUGHPUT" in out.stdout
    assert "mesh n2 x e2 x p2" in out.stdout


@pytest.mark.slow  # seq 2048 x 8-device ring compile
def test_longcontext_app_runs_ring_attention():
    """The long-context app must actually run 8-way sequence-parallel
    ring attention, not a single-device fallback."""
    out = _run_example(
        "examples/apps/longcontext.py", "-b", "4", "-e", "1",
        "-ll:tpu", "8", timeout=900,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "ring attention over s=8" in out.stdout
    assert "THROUGHPUT" in out.stdout


@pytest.mark.slow  # full 224x224 AlexNet compile via the torch shim
def test_alexnet_torch_example_runs():
    _run_example("examples/python/native/alexnet_torch.py", "-b", "32",
                 "-e", "1")


@pytest.mark.parametrize("script", [
    "examples/python/keras/seq_mnist_mlp.py",
    "examples/python/keras/unary.py",
    "examples/python/keras/func_mnist_mlp_concat.py",
    "examples/python/keras/seq_reuters_mlp.py",
    "examples/python/keras/candle_uno_keras.py",
    "examples/python/keras/func_mnist_mlp_net2net.py",
    "examples/python/keras/func_mnist_mlp.py",
])
def test_keras_example_scripts_run(script):
    _run_example(script, "-b", "64", "-e", "2")


@pytest.mark.slow
@pytest.mark.parametrize("script", [
    "examples/python/native/cifar10_cnn.py",
    "examples/python/native/cifar10_cnn_attach.py",
    "examples/python/native/cifar10_cnn_concat.py",
    "examples/python/native/mnist_cnn.py",
    "examples/python/keras/func_cifar10_cnn.py",
    "examples/python/keras/seq_mnist_cnn.py",
    "examples/python/keras/func_cifar10_cnn_nested.py",
    "examples/python/keras/func_cifar10_alexnet.py",
    "examples/python/keras/callback.py",
    "examples/python/keras/func_mnist_cnn.py",
    "examples/python/keras/seq_cifar10_cnn.py",
    "examples/python/keras/func_cifar10_cnn_concat.py",
    "examples/python/keras/func_cifar10_cnn_concat_model.py",
])
def test_cnn_example_scripts_run(script):
    _run_example(script, "-b", "64", "-e", "4")


@pytest.mark.slow
@pytest.mark.parametrize("script", [
    "examples/python/native/resnet.py",
    "examples/python/native/inception.py",
])
def test_big_model_example_scripts_run(script):
    _run_example(script, "-b", "8", "-e", "1")

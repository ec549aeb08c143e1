"""End-to-end MNIST LeNet training — parity with the reference book test
(``python/paddle/fluid/tests/book/test_recognize_digits.py``): train until
loss drops, eval accuracy, save/load params, run via the Executor facade,
and train data-parallel on the 8-device mesh with identical convergence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io, optimizer as opt
from paddle_tpu.data import datasets, reader as rd, DataFeeder, device_iterator
from paddle_tpu.models import LeNet
from paddle_tpu.ops import nn as F
from paddle_tpu.ops import tensor as T
from paddle_tpu.train import build_train_step, make_train_state


def _loss_fn(model):
    def loss_fn(params, image, label):
        logits = model(params, image)
        loss = jnp.mean(F.softmax_with_cross_entropy(logits, label))
        acc = T.accuracy(logits, label)
        return loss, {"acc": acc}

    return loss_fn


def _train(steps=60, batch_size=64, mesh=None, grad_accum=1, seed=0):
    model = LeNet()
    optimizer = opt.Adam(learning_rate=1e-3)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(seed))
    step = build_train_step(_loss_fn(model), optimizer,
                            grad_accum_steps=grad_accum)
    step = jax.jit(step, donate_argnums=0)

    data = rd.batch(rd.shuffle(datasets.synthetic_mnist(n=batch_size * steps),
                               1024, seed=1), batch_size)
    losses = []
    for batch in device_iterator(data, ["image", "label"], mesh=mesh):
        state, metrics = step(state, **batch)
        losses.append(float(metrics["loss"]))
    return model, state, losses


def test_mnist_convergence():
    model, state, losses = _train(steps=60)
    assert losses[0] > 1.5          # starts near log(10)≈2.3
    assert losses[-1] < 0.35 * losses[0], (losses[0], losses[-1])


def test_mnist_eval_and_checkpoint(tmp_path):
    model, state, _ = _train(steps=60)
    # eval accuracy on fresh synthetic data
    eval_data = rd.batch(datasets.synthetic_mnist(n=256, seed=9), 64)
    feeder = DataFeeder(["image", "label"])

    @jax.jit
    def eval_step(params, image, label):
        logits = model(params, image)
        return T.accuracy(logits, label)

    accs = [float(eval_step(state["params"], **feeder.feed(b)))
            for b in eval_data()]
    assert np.mean(accs) > 0.85, np.mean(accs)

    # save/load roundtrip (save_persistables parity)
    path = str(tmp_path / "lenet.pdparams")
    io.save_params(state["params"], path)
    restored = io.load_params(path, target=state["params"])
    out1 = eval_step(state["params"], **feeder.feed(next(iter(eval_data()))))
    out2 = eval_step(restored, **feeder.feed(next(iter(eval_data()))))
    np.testing.assert_allclose(float(out1), float(out2))


@pytest.mark.slow
def test_mnist_data_parallel_matches_single(mesh8):
    """DP-on-mesh must converge like single-device (parity with
    parallel_executor_test_base.py loss-parity methodology)."""
    _, _, single = _train(steps=30, batch_size=64, seed=0)
    with mesh8:
        _, _, dp = _train(steps=30, batch_size=64, mesh=mesh8, seed=0)
    # same seeds -> identical math up to reduction order
    np.testing.assert_allclose(single[:5], dp[:5], rtol=2e-2)
    assert dp[-1] < 0.5 * dp[0]


@pytest.mark.slow
def test_mnist_grad_accum():
    """grad_accum=4 with 4x batch ≈ plain training (BatchMergePass parity)."""
    _, _, losses = _train(steps=20, batch_size=128, grad_accum=4)
    assert losses[-1] < 0.8 * losses[0]


def test_mnist_executor_facade():
    """Run the same training through Program/Executor (fluid exe.run style)."""
    model = LeNet()
    optimizer = opt.SGD(learning_rate=0.05)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))
    raw_step = build_train_step(_loss_fn(model), optimizer)

    program = pt.Program(fn=lambda st, image, label: raw_step(st, image=image, label=label),
                         name="mnist_train", donate_state=True)
    exe = pt.Executor()
    data = rd.batch(datasets.synthetic_mnist(n=64 * 20), 64)
    feeder = DataFeeder(["image", "label"])
    first = last = None
    for batch in data():
        state, fetches = exe.run(program, state, feed=feeder.feed(batch),
                                 fetch_list=["loss"])
        last = float(fetches["loss"])
        if first is None:
            first = last
    assert last < first

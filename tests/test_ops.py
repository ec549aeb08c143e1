"""Op library tests: output parity vs NumPy references + numeric grad checks.

Parity with the reference's per-op OpTest files
(python/paddle/fluid/tests/unittests/test_*_op.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import ops
from paddle_tpu.core.registry import all_ops, get_op
from paddle_tpu.ops import activation, elementwise, math as pmath, nn, reduction, tensor
from paddle_tpu.testing import check_grad, check_output

RNG = np.random.RandomState(42)


def randn(*shape):
    return RNG.randn(*shape).astype(np.float32)


# -- auto-generated output parity for every op with a reference impl -------

_UNARY_CASES = {
    "default": (randn(4, 5),),
}


def _sample_args(name):
    """Construct sample args per op name for the auto parity sweep."""
    x = randn(4, 6)
    pos = np.abs(randn(4, 6)) + 0.5
    table = {
        "log": (pos,), "sqrt": (pos,), "rsqrt": (pos,), "reciprocal": (pos,),
        "cholesky": (np.eye(4, dtype=np.float32) * 2 + 0.1 * np.ones((4, 4), np.float32),),
        "matmul": (randn(4, 5), randn(5, 3)),
        "mul": (randn(4, 5), randn(5, 3)),
        "bmm": (randn(2, 3, 4), randn(2, 4, 5)),
        "dot": (randn(4, 6), randn(4, 6)),
        "fc": (randn(4, 6), randn(6, 3), randn(3)),
        "addmm": (randn(4, 3), randn(4, 5), randn(5, 3)),
        "norm": (x,),
        "one_hot": (RNG.randint(0, 5, (7,)), 5),
        "concat": ([randn(2, 3), randn(2, 3)],),
        "stack": ([randn(2, 3), randn(2, 3)],),
        "reshape": (x, (6, 4)),
        "transpose": (x, (1, 0)),
        "gather": (randn(5, 3), RNG.randint(0, 5, (4,))),
        "cast": (x, "float64"),
        "expand": (randn(2, 3), (2, 2)),
        "tile": (randn(2, 3), (2, 2)),
        "where": (x > 0, x, -x),
        "flip": (x, 0),
        "squeeze": (randn(2, 1, 3), (1,)),
        "unsqueeze": (randn(2, 3), (1,)),
        "argsort": (x,), "argmax": (x,), "argmin": (x,),
        "range": (0, 10, 2),
        "clip": (x, -0.5, 0.5),
        "leaky_relu": (x,), "elu": (x,), "relu6": (x,),
        "hard_sigmoid": (x,), "hard_swish": (x,),
        "prelu": (x, np.float32(0.1)),
        "pow": (pos,),
        "cross_entropy": (np.abs(randn(4, 5)) / 5 + 0.1, RNG.randint(0, 5, (4,))),
        "square_error_cost": (x, randn(4, 6)),
        "pad": (randn(2, 3), ((1, 1), (0, 2))),
        "label_smooth": (np.eye(5, dtype=np.float32)[RNG.randint(0, 5, (4,))],),
        "lookup_table": (RNG.randint(0, 5, (4,)), randn(5, 3)),
        "assign": (x,), "zeros_like": (x,), "ones_like": (x,),
        "isfinite": (x,), "isnan": (x,),
        "eye": (4,), "diag": (randn(4),),
        "einsum": ("ij,jk->ik", randn(3, 4), randn(4, 5)),
        "kron": (randn(2, 3), randn(3, 2)),
        "index_select": (randn(5, 3), RNG.randint(0, 5, (4,))),
        "index_sample": (randn(4, 6), RNG.randint(0, 6, (4, 3))),
        "multiplex": (RNG.randint(0, 2, (4,)), randn(4, 3), randn(4, 3)),
        "log_loss": (np.abs(randn(4, 1)) % 0.8 + 0.1,
                     RNG.randint(0, 2, (4, 1)).astype(np.float32)),
        "rank_loss": (RNG.randint(0, 2, (4, 1)).astype(np.float32),
                      randn(4, 1), randn(4, 1)),
        "hinge_loss": (randn(4, 1),
                       RNG.randint(0, 2, (4, 1)).astype(np.float32)),
        "conv_shift": (randn(4, 7), randn(4, 3)),
        "modified_huber_loss": (randn(4, 6),
                                RNG.randint(0, 2, (4, 6)).astype(np.float32)),
    }
    if name in ("equal", "not_equal", "less_than", "less_equal",
                "greater_than", "greater_equal"):
        return (randn(4, 6), randn(4, 6))
    if name in ("logical_and", "logical_or", "logical_xor"):
        return (x > 0, randn(4, 6) > 0)
    if name == "logical_not":
        return (x > 0,)
    if name in ("acos", "asin"):
        return (np.clip(x, -0.99, 0.99),)
    if name.startswith("elementwise_"):
        return (randn(4, 6), randn(4, 6))
    if name.startswith("reduce_") or name in ("logsumexp",):
        if name in ("reduce_all", "reduce_any"):
            return (x > 0,)
        return (x,)
    return table.get(name, (x,))


@pytest.mark.parametrize("name", sorted(
    n for n, info in all_ops().items() if info.reference is not None))
def test_op_output_parity(name):
    info = get_op(name)
    args = _sample_args(name)
    rtol, atol = (2e-4, 2e-5) if name in ("gelu",) else (1e-5, 1e-6)
    check_output(info.fn, info.reference, args, rtol=rtol, atol=atol)


# -- targeted numeric gradient checks (op_test.py check_grad parity) -------

@pytest.mark.parametrize("name,args,wrt", [
    ("matmul", (randn(3, 4), randn(4, 2)), (0, 1)),
    ("softmax", (randn(3, 5),), (0,)),
    ("layer_norm", (randn(3, 5), randn(5), randn(5)), (0, 1, 2)),
    ("tanh", (randn(3, 4),), (0,)),
    ("sigmoid", (randn(3, 4),), (0,)),
    ("gelu", (randn(3, 4),), (0,)),
    ("elementwise_mul", (randn(3, 4), randn(3, 4)), (0, 1)),
    ("elementwise_div", (randn(3, 4), np.abs(randn(3, 4)) + 1.0), (0, 1)),
    ("reduce_mean", (randn(3, 4),), (0,)),
    ("logsumexp", (randn(3, 4),), (0,)),
    ("log_softmax", (randn(3, 5),), (0,)),
    ("fc", (randn(3, 4), randn(4, 2), randn(2)), (0, 1, 2)),
    ("lookup_table", (np.array([0, 2, 1]), randn(4, 3)), (1,)),
])
def test_op_numeric_grad(name, args, wrt):
    info = get_op(name)
    check_grad(info.fn, args, wrt=wrt)


def test_conv2d_grad():
    x, w = randn(2, 5, 5, 3), randn(3, 3, 3, 4)
    check_grad(nn.conv2d, (x, w), wrt=(0, 1), rtol=2e-3, atol=2e-3)


def test_conv2d_matches_reference_convolution():
    # spot-check against scipy-style direct computation with padding
    x, w = randn(1, 4, 4, 1), randn(3, 3, 1, 2)
    out = nn.conv2d(x, w, stride=1, padding=1)
    assert out.shape == (1, 4, 4, 2)
    # center pixel = full 3x3 window dot kernel
    want = np.sum(x[0, 0:3, 0:3, 0] [..., None] * w[:, :, 0, :], axis=(0, 1))
    np.testing.assert_allclose(np.asarray(out[0, 1, 1]), want, rtol=1e-4, atol=1e-4)


def test_pool2d():
    x = randn(1, 4, 4, 2)
    out = nn.pool2d(x, kernel=2, stride=2, pool_type="max")
    assert out.shape == (1, 2, 2, 2)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                               x[0, 0:2, 0:2].max(axis=(0, 1)))
    avg = nn.pool2d(x, kernel=2, stride=2, pool_type="avg")
    np.testing.assert_allclose(np.asarray(avg[0, 0, 0]),
                               x[0, 0:2, 0:2].mean(axis=(0, 1)), rtol=1e-6)


def test_pool2d_nchw():
    x = randn(1, 2, 4, 4)
    out = nn.pool2d(x, kernel=2, stride=2, pool_type="max", data_format="NCHW")
    assert out.shape == (1, 2, 2, 2)


def test_batch_norm_inference():
    x = randn(4, 3, 3, 2)
    scale, bias = np.ones(2, np.float32), np.zeros(2, np.float32)
    mean, var = np.zeros(2, np.float32), np.ones(2, np.float32)
    out, m2, v2 = nn.batch_norm(x, scale, bias, mean, var, training=False)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(m2), mean)


def test_softmax_with_cross_entropy():
    logits = randn(4, 7)
    labels = RNG.randint(0, 7, (4,))
    loss = nn.softmax_with_cross_entropy(logits, labels)
    # reference: -log softmax picked
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = -np.log(p[np.arange(4), labels])[:, None]
    np.testing.assert_allclose(np.asarray(loss), want, rtol=1e-5, atol=1e-6)
    # soft label
    soft = np.abs(randn(4, 7)); soft /= soft.sum(-1, keepdims=True)
    loss2 = nn.softmax_with_cross_entropy(logits, soft, soft_label=True)
    want2 = -np.sum(soft * np.log(p), -1, keepdims=True)
    np.testing.assert_allclose(np.asarray(loss2), want2, rtol=1e-5, atol=1e-6)


def test_softmax_with_cross_entropy_grad():
    logits = randn(3, 5)
    labels = np.array([0, 2, 4])
    check_grad(lambda x: nn.softmax_with_cross_entropy(x, labels), (logits,))


def test_dropout_statistics():
    x = jnp.ones((1000,))
    out = nn.dropout(x, jax.random.PRNGKey(0), rate=0.25)
    kept = np.asarray(out) > 0
    assert 0.68 < kept.mean() < 0.82  # ~75% kept
    # upscale_in_train: expectation preserved
    assert abs(np.asarray(out).mean() - 1.0) < 0.1
    # eval mode = identity
    np.testing.assert_array_equal(
        np.asarray(nn.dropout(x, jax.random.PRNGKey(0), rate=0.5, training=False)),
        np.asarray(x))


# one dropout site of the BERT cells: batch 48 x 512 tokens x hidden 768
_SITE = (48, 512, 768)
_SITE_N = int(np.prod(_SITE))


def _kept(key, rate, shape=_SITE):
    return np.asarray(nn.dropout(jnp.ones(shape), key, rate=rate)) != 0


@pytest.mark.parametrize("rate", [0.1, 0.25, 0.5])
def test_dropout_keeps_one_minus_rate_and_scales_by_its_inverse(rate):
    keep = 1.0 - rate
    x = jnp.asarray(RNG.rand(*_SITE).astype(np.float32) + 0.5)
    out = np.asarray(nn.dropout(x, jax.random.PRNGKey(7), rate=rate))
    kept = out != 0
    sigma = np.sqrt(keep * rate / _SITE_N)
    assert abs(kept.mean() - keep) < 3 * sigma
    # the keep threshold is not rounded to a narrow draw (a uint8 compare at
    # rate 0.1 keeps 230/256, which is 22 sigma off at this size)
    np.testing.assert_array_equal(out[kept], np.asarray(x / keep)[kept])


def test_dropout_same_key_same_mask_typed_or_raw():
    a = _kept(jax.random.PRNGKey(3), 0.1)
    np.testing.assert_array_equal(a, _kept(jax.random.PRNGKey(3), 0.1))
    np.testing.assert_array_equal(a, _kept(jax.random.key(3), 0.1))
    assert (a != _kept(jax.random.PRNGKey(4), 0.1)).any()


@pytest.mark.parametrize("derive", ["fold_in", "split"])
def test_dropout_masks_of_derived_keys_are_independent(derive):
    """No two sites, steps or microbatches share a stream: masks under
    fold_in(key, i) and under split(key, 4) agree as often as independent
    draws do (keep^2 + rate^2), within 3 sigma."""
    key = jax.random.PRNGKey(11)
    keys = ([jax.random.fold_in(key, i) for i in range(4)]
            if derive == "fold_in" else list(jax.random.split(key, 4)))
    rate = 0.1
    masks = [_kept(k, rate) for k in keys]
    agree = (1 - rate) ** 2 + rate ** 2
    sigma = np.sqrt(agree * (1 - agree) / _SITE_N)
    for i in range(4):
        for j in range(i + 1, 4):
            assert abs((masks[i] == masks[j]).mean() - agree) < 3 * sigma, (i, j)


def test_dropout_gradient_is_the_mask_over_keep():
    x = jnp.asarray(randn(64, 768))
    key = jax.random.key(5)
    g = jax.grad(lambda v: nn.dropout(v, key, rate=0.1).sum())(x)
    np.testing.assert_array_equal(
        np.asarray(g), _kept(key, 0.1, x.shape).astype(np.float32) / np.float32(0.9))


@pytest.mark.parametrize("make_key", [jax.random.PRNGKey, jax.random.key],
                         ids=["raw", "typed"])
def test_dropout_draws_from_the_bit_generator_not_threefry(make_key):
    # the printed jaxpr holds the equations of every nested call too
    text = str(jax.make_jaxpr(lambda k, v: nn.dropout(v, k, rate=0.1))(
        make_key(0), jnp.ones(_SITE)))
    assert text.count("rng_bit_generator[") == 1
    assert "shape=(48, 512, 768)" in text
    assert "threefry2x32" not in text and "random_bits" not in text


def test_top_k():
    x = np.array([[1.0, 5.0, 3.0], [9.0, 2.0, 4.0]], np.float32)
    vals, idx = tensor.top_k(x, 2)
    np.testing.assert_array_equal(np.asarray(idx), [[1, 2], [0, 2]])
    np.testing.assert_array_equal(np.asarray(vals), [[5.0, 3.0], [9.0, 4.0]])


def test_accuracy_op():
    logits = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]], np.float32)
    labels = np.array([1, 0, 0])
    acc = tensor.accuracy(logits, labels)
    np.testing.assert_allclose(float(acc), 2 / 3, rtol=1e-6)


def test_elementwise_axis_broadcast():
    x = randn(2, 3, 4, 5)
    y = randn(3, 4)
    out = elementwise.add(x, y, axis=1)
    np.testing.assert_allclose(np.asarray(out), x + y[None, :, :, None],
                               rtol=1e-6)


def test_split_and_concat_roundtrip():
    x = randn(6, 4)
    parts = tensor.split(x, 3, axis=0)
    assert len(parts) == 3 and parts[0].shape == (2, 4)
    back = tensor.concat(parts, axis=0)
    np.testing.assert_array_equal(np.asarray(back), x)
    sizes = tensor.split(x, [1, 2, 3], axis=0)
    assert [s.shape[0] for s in sizes] == [1, 2, 3]


def test_scatter():
    x = np.zeros((4, 2), np.float32)
    out = tensor.scatter(jnp.asarray(x), np.array([1, 3]),
                         np.ones((2, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(out[1]), [1, 1])
    np.testing.assert_array_equal(np.asarray(out[0]), [0, 0])


def test_masked_select_static():
    x = np.arange(6).astype(np.float32)
    mask = x > 2
    out = tensor.masked_select(jnp.asarray(x), jnp.asarray(mask), size=3)
    np.testing.assert_array_equal(np.asarray(out), [3, 4, 5])

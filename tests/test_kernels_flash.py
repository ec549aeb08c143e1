"""The flash bodies beyond the parity battery of ``tests/test_kernels.py``
(a file of its own for ``--dist loadfile``): a flash body holds only what
its shape needs, and the backward is one kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ---------------------------------------------------------------------------
# the flash bodies (ops/attention.py): a body holds only what its static
# shape needs, and the backward is one kernel
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_eqns(sub))
    return found


def _kernel_ops(eqn):
    """(primitive name, result shape) of every operation of a Pallas
    kernel's body, branches of a ``cond`` included."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            for out in e.outvars[:1]:
                yield e.primitive.name, tuple(getattr(out.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)
    return list(walk(eqn.params["jaxpr"]))


class TestFlashBodies:
    # name: (sq, sk, block_q, block_k)
    GEOMETRY = {
        "one_pair": (64, 64, 64, 64),
        "one_pair_sq_lt_sk": (40, 56, 64, 64),
        "blocks": (64, 64, 32, 32),
        "one_key_block": (64, 32, 32, 32),
        "ragged_sq": (56, 64, 32, 32),
        "ragged_sk": (64, 56, 32, 32),
        "ragged_both_sq_lt_sk": (40, 72, 32, 32),
    }

    @staticmethod
    def _inputs(sq, sk, dtype, key_bias, b=2, h=2, d=32, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, h, sk, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, h, sk, d), jnp.float32).astype(dtype)
        g = jax.random.normal(ks[3], (b, h, sq, d), jnp.float32).astype(dtype)
        bias = None
        if key_bias:
            # batch 0: every key masked (all its rows are dead);
            # batch 1: the last third of the keys masked
            keep = jnp.stack([jnp.zeros(sk, bool),
                              jnp.arange(sk) < sk - sk // 3])
            from paddle_tpu.ops.attention import make_padding_bias
            bias = make_padding_bias(keep)
        return q, k, v, g, bias

    @staticmethod
    def _reference(q, k, v, g, bias, causal):
        """float32 composed attention on the same (rounded) inputs: out,
        lse, which rows have a key at all, and the three gradients."""
        from paddle_tpu.ops import attention as A
        q, k, v, g = (x.astype(jnp.float32) for x in (q, k, v, g))
        out, vjp = jax.vjp(
            lambda q, k, v: A.scaled_dot_product_attention(
                q, k, v, bias=bias, causal=causal), q, k, v)
        s = A._masked_scores(q, k, bias, scale=q.shape[-1] ** -0.5,
                             causal=causal)
        alive = jnp.max(s, axis=-1) > A.NEG_INF / 2
        return out, jax.nn.logsumexp(s, axis=-1), alive, vjp(g)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("key_bias", [False, True],
                             ids=["nobias", "keybias_dead_row"])
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_forward_lse_and_gradients_match_composed_float32(
            self, geometry, causal, key_bias, dtype):
        from paddle_tpu.ops import attention as A
        sq, sk, bq, bk = self.GEOMETRY[geometry]
        q, k, v, g, bias = self._inputs(sq, sk, dtype, key_bias)
        want_out, want_lse, alive, want_grads = self._reference(
            q, k, v, g, bias, causal)
        out, lse = A._flash_fwd(q, k, v, bias, scale=q.shape[-1] ** -0.5,
                                causal=causal, block_q=bq, block_k=bk,
                                interpret=True, return_lse=True)
        _, vjp = jax.vjp(lambda q, k, v: A.flash_attention(
            q, k, v, bias, causal, None, bq, bk, True), q, k, v)
        grads = vjp(g)
        tol = (dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32
               else dict(atol=3e-2, rtol=3e-2))
        gtol = (dict(atol=2e-4, rtol=2e-4) if dtype == jnp.float32
                else dict(atol=6e-2, rtol=6e-2))
        assert out.dtype == dtype and lse.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want_out), **tol)
        alive = np.asarray(alive)
        np.testing.assert_allclose(np.asarray(lse)[alive],
                                   np.asarray(want_lse)[alive], **tol)
        assert (np.asarray(lse)[~alive] <= A.NEG_INF / 2).all()
        for got, want in zip(grads, want_grads):
            assert got.dtype == dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want), **gtol)

    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    @pytest.mark.parametrize("blocks", [(64, 64), (32, 32)],
                             ids=["one_pair", "blocks"])
    def test_backward_against_a_foreign_lse(self, blocks, causal):
        """What ring attention hands ``_flash_bwd``: the logsumexp and
        the output of attention over MORE keys than the block it asks
        the gradients of. They are that block's share of the whole
        attention's gradients."""
        from paddle_tpu.ops import attention as A
        q, k, v, g, _ = self._inputs(64, 128, jnp.float32, False, seed=3)
        scale = q.shape[-1] ** -0.5
        # keys 0..63 are the block (the diagonal one under causal), keys
        # 64..127 a block every query sees whole, folded in by hand
        k1, k2, v1, v2 = k[:, :, :64], k[:, :, 64:], v[:, :, :64], v[:, :, 64:]

        def whole(q, k1, v1):
            s1 = A._masked_scores(q, k1, None, scale=scale, causal=causal)
            s2 = A._masked_scores(q, k2, None, scale=scale, causal=False)
            s = jnp.concatenate([s1, s2], axis=-1)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", p,
                             jnp.concatenate([v1, v2], axis=2))
            return out, jax.nn.logsumexp(s, axis=-1)

        (out, lse), vjp = jax.vjp(whole, q, k1, v1)
        _, want_dk, want_dv = vjp((g, jnp.zeros_like(lse)))
        kw = dict(scale=scale, causal=causal)
        dq, dk, dv = A._flash_bwd(q, k1, v1, None, out, lse, g,
                                  block_q=blocks[0], block_k=blocks[1],
                                  interpret=True, **kw)
        want_dq = A._lax_flash_block_bwd(q, k1, v1, None, out, lse, g,
                                         **kw)[0]
        for got, want in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4)

    @staticmethod
    def _grad_jaxpr(sq, sk, bq, bk, causal, key_bias):
        from paddle_tpu.ops import attention as A
        # a head width no block has: a (bq, bk) shape names the scores
        q, k, v, _, bias = TestFlashBodies._inputs(sq, sk, jnp.float32,
                                                   key_bias, d=16)
        return jax.make_jaxpr(jax.grad(
            lambda q, k, v: A.flash_attention(
                q, k, v, bias, causal, None, bq, bk, True).sum(),
            argnums=(0, 1, 2)))(q, k, v).jaxpr

    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_gradient_is_two_pallas_calls(self, geometry):
        """The forward and ONE backward, whatever the blocking."""
        calls = _pallas_eqns(self._grad_jaxpr(*self.GEOMETRY[geometry],
                                              causal=False, key_bias=True))
        assert len(calls) == 2
        assert [len(c.outvars) for c in calls] == [2, 3]   # o, lse; dq dk dv

    @pytest.mark.parametrize("geometry, causal, masked", [
        ("one_pair", False, False), ("blocks", False, False),
        ("one_key_block", False, False), ("one_pair", True, True),
        ("blocks", True, True), ("ragged_sq", False, True),
        ("ragged_sk", False, True)])
    def test_masks_exist_only_where_the_shape_needs_them(
            self, geometry, causal, masked):
        sq, sk, bq, bk = self.GEOMETRY[geometry]
        bq, bk = min(bq, sq), min(bk, sk)
        calls = _pallas_eqns(self._grad_jaxpr(sq, sk, bq, bk, causal,
                                              key_bias=True))
        if geometry == "ragged_sq":
            # the forward's rows past seq_q are never written back; the
            # backward drops them through (bq, 1) columns and the
            # (bq, Dh) operands
            fwd_ops, bwd_ops = map(_kernel_ops, calls)
            assert "iota" not in [name for name, _ in fwd_ops]
            assert ("iota", (bq, 1)) in bwd_ops
            assert ("select_n", (bq, bk)) not in fwd_ops + bwd_ops
            return
        for ops in map(_kernel_ops, calls):
            assert ("iota" in [name for name, _ in ops]) == masked
            assert (("select_n", (bq, bk)) in ops) == masked

    @pytest.mark.parametrize("geometry, causal, fwd, bwd, masks", [
        ("one_pair", False, "single_block", "single_block", "none"),
        ("one_pair", True, "single_block", "single_block", "causal"),
        ("one_key_block", False, "single_block", "blocked", "none"),
        ("blocks", False, "blocked", "blocked", "none"),
        ("ragged_sk", False, "blocked", "blocked", "ragged"),
        ("ragged_sq", True, "blocked", "blocked", "causal")])
    def test_lowerings_are_counted_by_the_body_taken(
            self, geometry, causal, fwd, bwd, masks):
        from paddle_tpu.observability import registry as obs
        counter = obs.counter("flash_attention_lowerings_total")
        labels = [{"pass": "fwd", "body": fwd, "masks": masks},
                  {"pass": "bwd", "body": bwd, "masks": masks}]
        before = [counter.value(**lb) for lb in labels]
        total = sum(counter.value(**dict(lb)) for lb in counter.labels_seen())
        self._grad_jaxpr(*self.GEOMETRY[geometry], causal=causal,
                         key_bias=False)
        assert [counter.value(**lb) for lb in labels] == [
            n + 1 for n in before]
        assert sum(counter.value(**dict(lb))
                   for lb in counter.labels_seen()) == total + 2

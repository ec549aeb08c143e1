"""The state-space kernels beyond the parity battery of
``tests/test_kernels.py`` (a file of its own for ``--dist loadfile``): how
a pool's type, a scan's tile and a grid's head blocks behave."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels

# ---------------------------------------------------------------------------
# the state-space kernels (ops/ssm_scan.py)
# ---------------------------------------------------------------------------

class TestStateSpaceKernels:
    """Beyond the parity battery above (both kernels, both outputs,
    against the token-by-token recurrence): how the pool's type, the
    scan's tile and the decode grid's head blocks behave."""

    NAMES = ["ssd_chunk_scan", "ssm_decode_update"]

    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    @pytest.mark.parametrize("name", NAMES)
    def test_a_bfloat16_pool_rounds_the_stored_state_only(self, name, impl):
        """The pool comes back in the type it came in. ``y`` is float32
        and is computed from the float32 state of the step, so it moves
        only by what the START state lost when it was rounded; rows no
        lane holds keep their bits."""
        args, kw = kernels.get(name).sample_inputs(1)
        rounded = args[5].astype(jnp.bfloat16)
        y32, p32 = kernels.dispatch(
            name, *args[:5], rounded.astype(jnp.float32), *args[6:],
            impl=impl, **kw)
        y16, p16 = kernels.dispatch(name, *args[:5], rounded, *args[6:],
                                    impl=impl, **kw)
        assert p16.dtype == jnp.bfloat16 and y16.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(y16), np.asarray(y32),
                                   atol=1e-5)
        assert (np.asarray(p16) == np.asarray(
            p32.astype(jnp.bfloat16))).all()

    def test_decode_grid_step_holds_heads_of_one_group(self):
        from paddle_tpu.ops import ssm_scan
        # the published mixer: 16 heads a group, tiles of (256, 128)
        # float32: all 16 in one step, 2 MiB of state in and out each
        assert ssm_scan._head_block(32, 16, 256, 128) == 16
        assert ssm_scan._head_block(4, 2, 16, 16) == 2
        # a tile four times as large: the most that divide the group and
        # stay under the budget
        assert ssm_scan._head_block(32, 16, 1024, 128) == 4
        assert ssm_scan._head_block(24, 12, 1024, 128) == 4

    def test_scan_tiles_a_long_chunk_and_refuses_a_ragged_one(self):
        from paddle_tpu.ops import ssm_scan
        assert ssm_scan._tile(8) == 8 and ssm_scan._tile(128) == 128
        assert ssm_scan._tile(384) == ssm_scan.SCAN_TILE
        with pytest.raises(ValueError, match="multiple of the scan tile"):
            ssm_scan._tile(200)

    @pytest.mark.parametrize("name", NAMES)
    def test_dispatch_is_counted_by_kernel_and_impl(self, name):
        from paddle_tpu.observability import registry as obs_registry
        c = obs_registry.counter("kernel_dispatch_total")
        before = {i: c.value(kernel=name, impl=i)
                  for i in ("lax", "pallas_interpret")}
        args, kw = kernels.get(name).sample_inputs(0)
        kernels.dispatch(name, *args, impl="pallas_interpret", **kw)
        assert c.value(kernel=name, impl="pallas_interpret") \
            == before["pallas_interpret"] + 1
        assert c.value(kernel=name, impl="lax") == before["lax"]

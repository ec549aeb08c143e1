"""Test fixture: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-without-a-cluster strategy
(test_dist_base.py spawns localhost subprocesses); here XLA's virtual CPU
devices give us 8 devices in-process, so multi-chip sharding paths compile
and execute as they would on an 8-chip slice. The suite never touches a
chip: this file pins the CPU backend before any backend is used.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)
# numeric-parity tests compare kernels against numpy in true float32; the
# backend's "default" matmul precision is bf16-class and would drown the
# comparison in ~1e-3 noise
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    """8-device DP mesh."""
    from paddle_tpu.core.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(dp=8))


@pytest.fixture(scope="session")
def mesh_dp2_tp4():
    from paddle_tpu.core.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(dp=2, tp=4))


def _script(name, *where):
    """``<root>/<where>/<name>.py`` loaded anew as a module."""
    import importlib.util
    import os
    import sys
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), *where,
        name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod             # dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def script():
    """``script(name, *where)``: a script of the repo as a module."""
    return _script


@pytest.fixture(scope="module")
def chip_smoke():
    return _script("chip_smoke")


@pytest.fixture
def graph_lint_cli():
    return _script("graph_lint", "tools")

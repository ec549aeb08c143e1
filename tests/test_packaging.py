"""Wheel build + install test (SURVEY §2.8 — the reference ships a wheel
via setup.py.in + paddle_build.sh and tests the installed package; here
the wheel is pure-Python with native .cc sources shipped as package data
and compiled on first use)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # excluded from the quick CI gate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestWheel:
    @pytest.fixture(scope="class")
    def wheel(self, tmp_path_factory):
        out = tmp_path_factory.mktemp("wheel")
        r = subprocess.run(
            [sys.executable, "-m", "pip", "wheel", "--no-deps",
             "--no-build-isolation", "-w", str(out), REPO],
            capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        wheels = [f for f in os.listdir(out) if f.endswith(".whl")]
        assert len(wheels) == 1, wheels
        return os.path.join(str(out), wheels[0])

    def test_wheel_contains_native_sources(self, wheel):
        import zipfile
        names = zipfile.ZipFile(wheel).namelist()
        assert any(n.endswith("native/kv_store.cc") for n in names), \
            "native sources must ship with the wheel"
        assert any(n.endswith("native/pjrt_runner.cc") for n in names)
        assert not any(n.endswith(".so") for n in names), \
            "no prebuilt binaries in a pure wheel"

    def test_installed_wheel_imports_and_runs(self, wheel, tmp_path):
        """Install into an isolated target dir; import paddle_tpu from
        the INSTALLED copy (repo shadowed), run an op + a native-backed
        piece so the on-demand g++ build works from installed sources."""
        target = str(tmp_path / "site")
        r = subprocess.run(
            [sys.executable, "-m", "pip", "install", "--no-deps",
             "--target", target, wheel],
            capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]

        check = (
            "import os, sys\n"
            "import paddle_tpu, paddle_tpu.ops as ops\n"
            f"assert paddle_tpu.__file__.startswith({target!r}), "
            "paddle_tpu.__file__\n"
            "import jax.numpy as jnp\n"
            "out = ops.softmax(jnp.zeros((2, 3)))\n"
            "assert out.shape == (2, 3)\n"
            "import numpy as np\n"
            "from paddle_tpu.parallel.host_kv import HostKVStore\n"
            "s = HostKVStore(4, optimizer='adagrad', seed=0)\n"
            "s.push(np.arange(5, dtype=np.int64),"
            " np.ones((5, 4), np.float32), lr=1.0)\n"
            "assert len(s) == 5\n"
            "print('WHEEL OK', paddle_tpu.__version__)\n"
        )
        from paddle_tpu.testing import subprocess_env

        # ONLY the installed copy on the path (no repo shadowing)
        env = subprocess_env(repo_on_path=False)
        env["PYTHONPATH"] = target
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", check], env=env,
                           capture_output=True, text=True, timeout=600,
                           cwd=str(tmp_path))
        assert r.returncode == 0, (r.stdout[-500:], r.stderr[-2000:])
        assert "WHEEL OK" in r.stdout

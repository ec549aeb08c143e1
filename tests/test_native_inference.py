"""Native C++ PJRT inference runner tests (inference/capi analog).

The artifact/contract pieces run everywhere; actually executing through a
PJRT plugin needs real hardware (the CPU test mesh has no C-API plugin),
so the end-to-end parity check runs in a subprocess against the default
plugin and SKIPs when none is usable — mirroring how the reference gates
its TensorRT/GPU predictor tests on hardware.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.slow  # excluded from the quick CI gate


from paddle_tpu.inference import Predictor, save_inference_model
from paddle_tpu.nn.layers import Linear
from paddle_tpu.nn.module import Layer


class _MLP(Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = Linear(16, 32, sharding=None)
        self.fc2 = Linear(32, 4, sharding=None)

    def forward(self, params, x):
        h = jnp.tanh(self.fc1(params["fc1"], x))
        return jax.nn.softmax(self.fc2(params["fc2"], h), -1), h.sum(-1)


def _export(tmp_path):
    model = _MLP()
    params = model.init(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
    d = str(tmp_path / "model")
    save_inference_model(d, lambda p, x: model(p, x), params, [x])
    return d, x


class TestNativeArtifacts:
    def test_frozen_artifacts_written(self, tmp_path):
        d, x = _export(tmp_path)
        names = set(os.listdir(d))
        assert {"__model__.stablehlo", "__model__frozen__.stablehlo",
                "compile_options.pb", "params.pkl",
                "meta.json"} <= names
        meta = json.load(open(os.path.join(d, "meta.json")))
        assert meta["outputs"] == [
            {"shape": [8, 4], "dtype": "float32"},
            {"shape": [8], "dtype": "float32"},
        ]
        # frozen module is raw MLIR bytecode (params baked in): non-trivial
        assert os.path.getsize(
            os.path.join(d, "__model__frozen__.stablehlo")) > 1000

    def test_runner_builds_and_reports_bad_plugin(self):
        """The C++ runner compiles on any host and fails CLEANLY (error
        string, not crash) on a bogus plugin path."""
        import ctypes

        from paddle_tpu.native.pjrt import _ERR_LEN, _lib

        lib = _lib()
        err = ctypes.create_string_buffer(_ERR_LEN)
        h = lib.pjr_create(b"/nonexistent/plugin.so", err, _ERR_LEN)
        assert not h
        assert b"dlopen" in err.value


# Self-contained: exports ON the platform it serves on (an export carries
# its lowering platform), computes the in-process reference on the same
# device/precision, then round-trips through the native C++ runner — a
# plumbing/layout bug would be orders of magnitude outside the bound.
_SUBPROC_CHECK = textwrap.dedent("""
    import sys
    import numpy as np
    from paddle_tpu.native.pjrt import NativePredictor, default_plugin_path
    model_dir = sys.argv[1]
    plugin = default_plugin_path()
    if plugin is None:
        print("NO_PLUGIN"); sys.exit(0)
    # ONLY environment problems (no device, client init failure — e.g.
    # another process holds the TPU library) exit 7 -> the parent SKIPs;
    # every other failure must FAIL the test
    import jax, jax.numpy as jnp
    from paddle_tpu.inference import Predictor, save_inference_model
    from paddle_tpu import io as io_lib
    from paddle_tpu.nn.layers import Linear
    from paddle_tpu.nn.module import Layer

    class MLP(Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = Linear(16, 32, sharding=None)
            self.fc2 = Linear(32, 4, sharding=None)
        def forward(self, params, x):
            h = jnp.tanh(self.fc1(params["fc1"], x))
            return jax.nn.softmax(self.fc2(params["fc2"], h), -1), h.sum(-1)

    model = MLP()
    params = io_lib.load_params(model_dir + "/params.pkl")
    x = np.load(model_dir + "/x.npy")
    save_inference_model(model_dir, lambda p, x: model(p, x), params, [x])
    ref = [np.asarray(r) for r in
           jax.tree_util.tree_leaves(Predictor(model_dir).run(x))]
    try:
        p = NativePredictor(model_dir)
    except RuntimeError as e:
        if "client init failed" in str(e):   # device unusable, not a bug
            print(f"ENV_UNUSABLE: {e}", file=sys.stderr)
            sys.exit(7)
        raise
    outs = p.run(x)
    assert len(outs) == len(ref), (len(outs), len(ref))
    for o, r in zip(outs, ref):
        np.testing.assert_allclose(o, r, rtol=1e-5, atol=1e-6)
    # serving loop: repeated calls are stable
    again = p.run(x)
    for a, o in zip(again, outs):
        np.testing.assert_array_equal(a, o)
    p.close()
    print("OK")
""")


class TestNativeExecution:
    def test_native_matches_python_predictor(self, tmp_path):
        d, x = _export(tmp_path)
        np.save(os.path.join(d, "x.npy"), x)

        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        try:
            r = subprocess.run(
                [sys.executable, "-c", _SUBPROC_CHECK, d], env=env,
                capture_output=True, text=True, timeout=240)
        except subprocess.TimeoutExpired:
            pytest.skip("PJRT plugin unresponsive (no usable device)")
        if "NO_PLUGIN" in r.stdout:
            pytest.skip("no PJRT C-API plugin on this host")
        if r.returncode == 7:
            # environment (not runner) problem — the subprocess probes
            # client creation before any real work
            pytest.skip(f"plugin unusable: {r.stderr[-300:]}")
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-1500:]

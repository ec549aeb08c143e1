"""Python binding for the native PJRT inference runner.

Reference mapping: the C API of fluid inference (``inference/capi/``:
``PD_NewAnalysisConfig``/``PD_PredictorRun``) wrapping the C++
AnalysisPredictor. Here ctypes wraps ``native/pjrt_runner.cc``, which
dlopens a PJRT C-API plugin and serves the exported StableHLO artifact —
the serving loop lives in C++, Python only hands over numpy buffers.
"""

from __future__ import annotations

import ctypes
import importlib.util
import json
import os
from typing import List, Optional, Sequence

import numpy as np

from paddle_tpu import native

_ERR_LEN = 2048

# keep in sync with to_pjrt_type() in pjrt_runner.cc
_DTYPE_CODES = {
    "float32": 0, "float64": 1, "int32": 2, "int64": 3, "bool": 4,
    "bfloat16": 5, "float16": 6, "uint8": 7, "int8": 8,
}


def _tf_include_dir() -> str:
    """The local TF/XLA install vendors pjrt_c_api.h (no network here)."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise RuntimeError("tensorflow (for pjrt_c_api.h) not found")
    return os.path.join(list(spec.submodule_search_locations)[0], "include")


def _lib():
    lib = native.load_library(
        "pjrtrunner", ["pjrt_runner.cc"],
        extra_flags=[f"-I{_tf_include_dir()}", "-ldl"])
    lib.pjr_create.restype = ctypes.c_void_p
    lib.pjr_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                               ctypes.c_int]
    lib.pjr_create_with_options.restype = ctypes.c_void_p
    lib.pjr_create_with_options.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_int]
    lib.pjr_destroy.argtypes = [ctypes.c_void_p]
    lib.pjr_compile.restype = ctypes.c_void_p
    lib.pjr_compile.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int64, ctypes.c_char_p,
                                ctypes.c_int64, ctypes.c_char_p,
                                ctypes.c_int]
    lib.pjr_num_outputs.restype = ctypes.c_int
    lib.pjr_num_outputs.argtypes = [ctypes.c_void_p]
    lib.pjr_exec_destroy.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.pjr_execute.restype = ctypes.c_int
    lib.pjr_execute.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p),      # in_bufs
        ctypes.POINTER(ctypes.c_int64),       # dims_flat
        ctypes.POINTER(ctypes.c_int),         # ranks
        ctypes.POINTER(ctypes.c_int),         # dtypes
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p),      # out_bufs
        ctypes.POINTER(ctypes.c_int64),       # out_sizes
        ctypes.c_char_p, ctypes.c_int,
    ]
    return lib


def default_plugin_path() -> Optional[str]:
    """Locate a PJRT C-API plugin: the explicit env var, or libtpu from
    site-packages — nothing else."""
    env = os.environ.get("PADDLE_TPU_PJRT_PLUGIN")
    if env:
        return env
    spec = importlib.util.find_spec("libtpu")
    if spec is not None and spec.submodule_search_locations:
        p = os.path.join(list(spec.submodule_search_locations)[0],
                         "libtpu.so")
        if os.path.exists(p):
            return p
    return None


class NativePredictor:
    """C++ serving shell over an exported inference artifact.

    Loads ``__model__frozen__.stablehlo`` (params baked in at export —
    the frozen-program convention of ``save_inference_model``) plus the
    recorded compile options, compiles once through the plugin, then
    ``run(*inputs)`` round-trips numpy buffers through the C ABI.
    """

    def __init__(self, model_dir: str, plugin_path: Optional[str] = None,
                 plugin_options: Optional[dict] = None):
        """``plugin_options``: plugin-specific client create options
        (str or int values) — e.g. libtpu tuning knobs."""
        plugin_path = plugin_path or default_plugin_path()
        if plugin_path is None:
            raise RuntimeError("no PJRT plugin found (set "
                               "PADDLE_TPU_PJRT_PLUGIN)")
        self._lib = _lib()
        err = ctypes.create_string_buffer(_ERR_LEN)
        opts = plugin_options or {}
        names, svals, ivals, kinds = [], [], [], []
        for k, v in opts.items():
            names.append(k.encode())
            if isinstance(v, str):
                svals.append(v.encode())
                ivals.append(0)
                kinds.append(0)
            else:
                svals.append(b"")
                ivals.append(int(v))
                kinds.append(1)
        n = len(names)
        self._h = self._lib.pjr_create_with_options(
            plugin_path.encode(), n,
            (ctypes.c_char_p * n)(*names) if n else None,
            (ctypes.c_char_p * n)(*svals) if n else None,
            (ctypes.c_int64 * n)(*ivals) if n else None,
            (ctypes.c_int * n)(*kinds) if n else None,
            err, _ERR_LEN)
        if not self._h:
            raise RuntimeError(
                f"PJRT client init failed ({plugin_path}): "
                f"{err.value.decode()}")
        with open(os.path.join(model_dir,
                               "__model__frozen__.stablehlo"), "rb") as f:
            code = f.read()
        with open(os.path.join(model_dir, "compile_options.pb"), "rb") as f:
            copts = f.read()
        with open(os.path.join(model_dir, "meta.json")) as f:
            self.meta = json.load(f)
        self._exec = self._lib.pjr_compile(
            self._h, code, len(code), copts, len(copts), err, _ERR_LEN)
        if not self._exec:
            raise RuntimeError(f"PJRT compile failed: {err.value.decode()}")
        self.output_specs = self.meta.get("outputs", [])
        n = self._lib.pjr_num_outputs(self._exec)
        if self.output_specs and n != len(self.output_specs):
            raise RuntimeError(
                f"artifact outputs {len(self.output_specs)} != "
                f"executable outputs {n}")

    def run(self, *inputs) -> List[np.ndarray]:
        """Execute on the device; returns the flattened output leaves."""
        arrs = [np.ascontiguousarray(a) for a in inputs]
        n_in = len(arrs)
        in_bufs = (ctypes.c_void_p * n_in)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs])
        dims_flat = []
        for a in arrs:
            dims_flat.extend(a.shape)
        dims = (ctypes.c_int64 * len(dims_flat))(*dims_flat)
        ranks = (ctypes.c_int * n_in)(*[a.ndim for a in arrs])
        try:
            codes = (ctypes.c_int * n_in)(
                *[_DTYPE_CODES[str(a.dtype)] for a in arrs])
        except KeyError as e:
            raise TypeError(f"unsupported input dtype {e}") from None

        outs = []
        for spec in self.output_specs:
            outs.append(np.empty(spec["shape"], dtype=spec["dtype"]))
        n_out = len(outs)
        out_bufs = (ctypes.c_void_p * n_out)(
            *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
        out_sizes = (ctypes.c_int64 * n_out)(*[o.nbytes for o in outs])

        err = ctypes.create_string_buffer(_ERR_LEN)
        rc = self._lib.pjr_execute(
            self._h, self._exec, n_in, in_bufs, dims, ranks, codes,
            n_out, out_bufs, out_sizes, err, _ERR_LEN)
        if rc != 0:
            raise RuntimeError(f"PJRT execute failed: {err.value.decode()}")
        return outs

    def close(self):
        if getattr(self, "_exec", None):
            self._lib.pjr_exec_destroy(self._h, self._exec)
            self._exec = None
        if getattr(self, "_h", None):
            self._lib.pjr_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

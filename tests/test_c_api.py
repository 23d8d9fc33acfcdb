"""C inference API (native/c_api.cc) — reference capi_exp role.

Two consumers are driven: (a) this process via ctypes (the library
detects the already-initialized interpreter), and (b) a REAL standalone
C program, compiled here and run in a subprocess, which embeds Python
itself — the actual C-deployment story.
"""
import ctypes
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference.c_api import build_c_api

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    m.eval()
    path = str(tmp_path_factory.mktemp("capi") / "model")
    paddle.jit.save(m, path, input_spec=[paddle.jit.InputSpec([None, 8])])
    x = np.random.RandomState(0).randn(3, 8).astype("float32")
    return path + ".pdmodel", x, m(paddle.to_tensor(x)).numpy()


def test_c_api_via_ctypes(saved):
    so = build_c_api()
    assert so, "C API failed to build"
    model, x, ref = saved
    lib = ctypes.CDLL(so)
    lib.PD_PredictorCreate.restype = ctypes.c_void_p
    lib.PD_PredictorCreate.argtypes = [ctypes.c_char_p]
    lib.PD_GetLastError.restype = ctypes.c_char_p
    p = lib.PD_PredictorCreate(model.encode())
    assert p, lib.PD_GetLastError()
    try:
        assert lib.PD_PredictorGetInputNum(ctypes.c_void_p(p)) == 1
        assert lib.PD_PredictorGetOutputNum(ctypes.c_void_p(p)) == 1

        data = np.ascontiguousarray(x)
        shape = (ctypes.c_int64 * 2)(*x.shape)
        ins = (ctypes.c_void_p * 1)(data.ctypes.data)
        shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shape)
        ndims = (ctypes.c_int * 1)(2)
        dts = (ctypes.c_int * 1)(0)  # PD_DTYPE_FLOAT32
        rc = lib.PD_PredictorRun(ctypes.c_void_p(p), ins, shapes, ndims,
                                 dts, 1)
        assert rc == 0, lib.PD_GetLastError()

        oshape = (ctypes.c_int64 * 8)()
        ondim = ctypes.c_int()
        rc = lib.PD_PredictorGetOutputShape(
            ctypes.c_void_p(p), 0, oshape, ctypes.byref(ondim), 8)
        assert rc == 0, lib.PD_GetLastError()
        got_shape = tuple(oshape[i] for i in range(ondim.value))
        assert got_shape == ref.shape

        buf = np.zeros(ref.size, np.float32)
        lib.PD_PredictorGetOutputData.restype = ctypes.c_int64
        n = lib.PD_PredictorGetOutputData(
            ctypes.c_void_p(p), 0,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(buf.size))
        assert n == ref.size, lib.PD_GetLastError()
        np.testing.assert_allclose(buf.reshape(ref.shape), ref,
                                   rtol=1e-5, atol=1e-6)
    finally:
        lib.PD_PredictorDestroy(ctypes.c_void_p(p))


_C_DRIVER = textwrap.dedent("""
    #include <stdio.h>
    #include <stdint.h>
    typedef struct PD_Predictor PD_Predictor;
    extern PD_Predictor* PD_PredictorCreate(const char*);
    extern void PD_PredictorDestroy(PD_Predictor*);
    extern int PD_PredictorRun(PD_Predictor*, const void**,
                               const int64_t**, const int*, const int*,
                               int);
    extern int64_t PD_PredictorGetOutputData(PD_Predictor*, int, float*,
                                             int64_t);
    extern const char* PD_GetLastError(void);

    int main(int argc, char** argv) {
        PD_Predictor* p = PD_PredictorCreate(argv[1]);
        if (!p) { fprintf(stderr, "create: %s\\n", PD_GetLastError());
                  return 1; }
        float x[16];
        for (int i = 0; i < 16; i++) x[i] = (float)i * 0.1f - 0.8f;
        int64_t shape[2] = {2, 8};
        const void* ins[1] = {x};
        const int64_t* shapes[1] = {shape};
        int ndims[1] = {2}; int dts[1] = {0};
        if (PD_PredictorRun(p, ins, shapes, ndims, dts, 1)) {
            fprintf(stderr, "run: %s\\n", PD_GetLastError()); return 2;
        }
        float out[8];
        int64_t n = PD_PredictorGetOutputData(p, 0, out, 8);
        if (n < 0) { fprintf(stderr, "out: %s\\n", PD_GetLastError());
                     return 3; }
        for (int64_t i = 0; i < n; i++) printf("%.6f\\n", out[i]);
        PD_PredictorDestroy(p);
        return 0;
    }
""")


@pytest.mark.slow
def test_c_api_from_standalone_c_program(saved, tmp_path):
    """Compile and run an actual C consumer: it embeds Python, loads the
    model, runs inference, prints the outputs."""
    so = build_c_api()
    assert so, "C API failed to build"
    model, _, _ = saved
    src = tmp_path / "driver.c"
    src.write_text(_C_DRIVER)
    exe = str(tmp_path / "driver")
    subprocess.run(["gcc", str(src), so, "-o", exe,
                    f"-Wl,-rpath,{os.path.dirname(so)}"], check=True,
                   capture_output=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([exe, model], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    vals = [float(v) for v in r.stdout.strip().splitlines()]
    assert len(vals) == 8

    # reference from the Python path
    x = (np.arange(16, dtype=np.float32) * 0.1 - 0.8).reshape(2, 8)
    from paddle_tpu.inference import Config, create_predictor
    ref = create_predictor(Config(model)).run([x])[0]
    np.testing.assert_allclose(np.asarray(vals).reshape(2, 4), ref,
                               rtol=1e-5, atol=1e-6)

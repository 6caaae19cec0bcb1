"""C inference API (native/inference_c.cc + capi_host.py) — the
reference's C++ inference/capi counterpart (round-3 verdict #8).

Covers both hosting modes: loaded into an existing Python process via
ctypes, and linked into a standalone C program that embeds the
interpreter (compiled and executed by the test).
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")


def _save_model(dirname):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        out = fluid.layers.fc(input=h, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                      main_program=main)
        xs = np.random.RandomState(3).rand(4, 6).astype("f")
        ref, = exe.run(main, feed={"x": xs}, fetch_list=[out])
    return xs, np.asarray(ref)


def _load_lib():
    from paddle_tpu.native import load_library
    lib = load_library("ptpu_infer")
    if lib is None:
        pytest.skip("libptpu_infer.so unavailable (no toolchain)")
    lib.ptpu_create.restype = ctypes.c_int64
    lib.ptpu_create.argtypes = [ctypes.c_char_p]
    lib.ptpu_run.restype = ctypes.c_int64
    lib.ptpu_last_error.restype = ctypes.c_char_p
    return lib


def test_c_api_inference_in_process(tmp_path):
    model_dir = str(tmp_path / "m")
    xs, ref = _save_model(model_dir)
    lib = _load_lib()

    h = lib.ptpu_create(model_dir.encode())
    assert h > 0, lib.ptpu_last_error().decode()
    assert lib.ptpu_num_feeds(ctypes.c_int64(h)) == 1
    name = ctypes.create_string_buffer(64)
    assert lib.ptpu_feed_name(ctypes.c_int64(h), 0, name, 64) == 0
    assert name.value == b"x"

    data = np.ascontiguousarray(xs)
    names = (ctypes.c_char_p * 1)(b"x")
    bufs = (ctypes.POINTER(ctypes.c_float) * 1)(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    shape = (ctypes.c_int64 * 2)(*data.shape)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shape)
    ndims = (ctypes.c_int * 1)(2)
    out = np.zeros(64, "f")
    out_shape = (ctypes.c_int64 * 8)()
    out_ndim = ctypes.c_int(0)
    n = lib.ptpu_run(
        ctypes.c_int64(h), names, bufs, shapes, ndims, 1,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(out.size), out_shape, 8, ctypes.byref(out_ndim))
    assert n == ref.size, lib.ptpu_last_error().decode()
    assert out_ndim.value == 2
    assert tuple(out_shape[:2]) == ref.shape
    np.testing.assert_allclose(out[:n].reshape(ref.shape), ref,
                               rtol=1e-5, atol=1e-6)
    lib.ptpu_destroy(ctypes.c_int64(h))

    # error path: nonexistent model dir reports through ptpu_last_error
    assert lib.ptpu_create(b"/nonexistent/model") == 0
    assert b"" != lib.ptpu_last_error()


C_MAIN = r"""
#include <stdio.h>
#include <stdint.h>
#include <string.h>

extern const char* ptpu_last_error();
extern int64_t ptpu_create(const char* model_dir);
extern int64_t ptpu_run(int64_t, const char**, const float**,
                        const int64_t**, const int*, int,
                        float*, int64_t, int64_t*, int, int*);
extern void ptpu_destroy(int64_t);

int main(int argc, char** argv) {
  int64_t h = ptpu_create(argv[1]);
  if (h <= 0) { fprintf(stderr, "create: %s\n", ptpu_last_error()); return 1; }
  float x[2 * 6];
  for (int i = 0; i < 12; ++i) x[i] = 0.1f * i;
  const char* names[1] = {"x"};
  const float* bufs[1] = {x};
  int64_t shape[2] = {2, 6};
  const int64_t* shapes[1] = {shape};
  int ndims[1] = {2};
  float out[64];
  int64_t out_shape[8];
  int out_ndim = 0;
  int64_t n = ptpu_run(h, names, bufs, shapes, ndims, 1, out, 64,
                       out_shape, 8, &out_ndim);
  if (n < 0) { fprintf(stderr, "run: %s\n", ptpu_last_error()); return 2; }
  double total = 0;
  for (int64_t i = 0; i < n; ++i) total += out[i];
  // softmax rows sum to 1 each
  printf("n=%lld ndim=%d rows=%lld total=%.4f\n", (long long)n, out_ndim,
         (long long)out_shape[0], total);
  ptpu_destroy(h);
  return 0;
}
"""


def test_c_api_standalone_binary(tmp_path):
    model_dir = str(tmp_path / "m")
    _save_model(model_dir)
    _load_lib()  # ensures the .so is built

    csrc = tmp_path / "main.c"
    csrc.write_text(C_MAIN)
    exe_path = str(tmp_path / "infer")
    ldflags = subprocess.run(
        ["python3-config", "--ldflags", "--embed"],
        capture_output=True, text=True, check=True).stdout.split()
    subprocess.run(
        ["gcc", str(csrc), "-o", exe_path, "-L" + NATIVE, "-lptpu_infer",
         "-Wl,-rpath," + NATIVE] + ldflags,
        check=True, capture_output=True, timeout=120)

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe_path, model_dir], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    assert "n=6 ndim=2 rows=2" in r.stdout
    total = float(r.stdout.strip().split("total=")[1])
    assert abs(total - 2.0) < 1e-4  # two softmax rows


def _save_embedding_model(dirname):
    """CTR-style model: int64 id feed -> embedding -> fc; TWO fetch
    targets (probabilities + pre-softmax logits) to exercise multi-fetch."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[50, 8])
        pooled = fluid.layers.reduce_sum(emb, dim=1)
        logits = fluid.layers.fc(input=pooled, size=3)
        prob = fluid.layers.softmax(logits)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["ids"], [prob, logits], exe,
                                      main_program=main)
        ids_np = np.random.RandomState(5).randint(
            0, 50, size=(3, 4)).astype("int64")
        refs = exe.run(main, feed={"ids": ids_np},
                       fetch_list=[prob, logits])
    return ids_np, [np.asarray(r) for r in refs]


def test_c_api_v2_int64_feeds_multi_fetch(tmp_path):
    """v2 ABI: int64 id buffers feed an embedding model directly (no
    float smuggling), and BOTH fetch targets read back with dtype+shape
    (round-3 verdict #8 / ADVICE #2)."""
    model_dir = str(tmp_path / "m")
    ids_np, refs = _save_embedding_model(model_dir)
    lib = _load_lib()
    lib.ptpu_run2.restype = ctypes.c_int64
    lib.ptpu_output.restype = ctypes.c_int64

    h = lib.ptpu_create(model_dir.encode())
    assert h > 0, lib.ptpu_last_error().decode()

    dt = ctypes.create_string_buffer(16)
    assert lib.ptpu_feed_dtype(ctypes.c_int64(h), 0, dt, 16) == 0
    assert dt.value == b"int64"

    data = np.ascontiguousarray(ids_np)
    names = (ctypes.c_char_p * 1)(b"ids")
    bufs = (ctypes.c_void_p * 1)(data.ctypes.data_as(ctypes.c_void_p))
    shape = (ctypes.c_int64 * 2)(*data.shape)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shape)
    ndims = (ctypes.c_int * 1)(2)
    n_out = lib.ptpu_run2(ctypes.c_int64(h), names, bufs, shapes, ndims, 1)
    assert n_out == 2, lib.ptpu_last_error().decode()
    assert lib.ptpu_num_outputs(ctypes.c_int64(h)) == 2

    for i, ref in enumerate(refs):
        out = np.zeros(256, "f")
        out_shape = (ctypes.c_int64 * 8)()
        out_ndim = ctypes.c_int(0)
        odt = ctypes.create_string_buffer(16)
        nbytes = lib.ptpu_output(
            ctypes.c_int64(h), i,
            out.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int64(out.nbytes), out_shape, 8,
            ctypes.byref(out_ndim), odt, 16)
        assert nbytes == ref.nbytes, lib.ptpu_last_error().decode()
        assert odt.value == b"float32"
        assert out_ndim.value == ref.ndim
        assert tuple(out_shape[:ref.ndim]) == ref.shape
        got = out[:ref.size].reshape(ref.shape)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    lib.ptpu_destroy(ctypes.c_int64(h))


C_MAIN_V2 = r"""
#include <stdio.h>
#include <stdint.h>
#include <string.h>

extern const char* ptpu_last_error();
extern int64_t ptpu_create(const char* model_dir);
extern int ptpu_feed_dtype(int64_t, int, char*, int);
extern int64_t ptpu_run2(int64_t, const char**, const void**,
                         const int64_t**, const int*, int);
extern int ptpu_num_outputs(int64_t);
extern int64_t ptpu_output(int64_t, int, void*, int64_t, int64_t*, int,
                           int*, char*, int);
extern void ptpu_destroy(int64_t);

int main(int argc, char** argv) {
  int64_t h = ptpu_create(argv[1]);
  if (h <= 0) { fprintf(stderr, "create: %s\n", ptpu_last_error()); return 1; }
  char dt[16];
  if (ptpu_feed_dtype(h, 0, dt, 16) != 0 || strcmp(dt, "int64") != 0) {
    fprintf(stderr, "dtype: %s (%s)\n", dt, ptpu_last_error());
    return 2;
  }
  int64_t ids[2 * 4] = {1, 5, 9, 13, 2, 6, 10, 14};
  const char* names[1] = {"ids"};
  const void* bufs[1] = {ids};
  int64_t shape[2] = {2, 4};
  const int64_t* shapes[1] = {shape};
  int ndims[1] = {2};
  int64_t n_out = ptpu_run2(h, names, bufs, shapes, ndims, 1);
  if (n_out < 0) { fprintf(stderr, "run2: %s\n", ptpu_last_error()); return 3; }
  float out[64];
  int64_t out_shape[8];
  int out_ndim = 0;
  char odt[16];
  int64_t nb = ptpu_output(h, 0, out, sizeof(out), out_shape, 8, &out_ndim,
                           odt, 16);
  if (nb < 0) { fprintf(stderr, "output: %s\n", ptpu_last_error()); return 4; }
  double s = 0;
  for (int64_t i = 0; i < (int64_t)(nb / sizeof(float)); ++i) s += out[i];
  printf("nout=%lld rows=%lld dtype=%s sum=%.4f\n", (long long)n_out,
         (long long)out_shape[0], odt, s);
  ptpu_destroy(h);
  return 0;
}
"""


def test_c_api_v2_standalone_binary(tmp_path):
    model_dir = str(tmp_path / "m")
    _save_embedding_model(model_dir)
    _load_lib()

    csrc = tmp_path / "main_v2.c"
    csrc.write_text(C_MAIN_V2)
    exe_path = str(tmp_path / "infer_v2")
    ldflags = subprocess.run(
        ["python3-config", "--ldflags", "--embed"],
        capture_output=True, text=True, check=True).stdout.split()
    subprocess.run(
        ["gcc", str(csrc), "-o", exe_path, "-L" + NATIVE, "-lptpu_infer",
         "-Wl,-rpath," + NATIVE] + ldflags,
        check=True, capture_output=True, timeout=120)

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe_path, model_dir], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    assert "nout=2 rows=2 dtype=float32" in r.stdout
    s = float(r.stdout.strip().split("sum=")[1])
    assert abs(s - 2.0) < 1e-4  # two softmax rows sum to 1 each


def _save_lstm_model(dirname):
    """Sentiment-style lod model: ids -> embedding -> fc -> lstm -> max
    pool -> fc softmax, saved via save_inference_model. Returns flat-row
    ids, sequence lengths, and the direct-executor reference output."""
    from paddle_tpu.core.lod import LoDTensor

    V, E, H = 20, 4, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        words = fluid.layers.data("words", [1], dtype="int64", lod_level=1)
        emb = fluid.layers.embedding(input=words, size=[V, E])
        proj = fluid.layers.fc(input=emb, size=4 * H, num_flatten_dims=2)
        hidden, _ = fluid.layers.dynamic_lstm(input=proj, size=4 * H,
                                              use_peepholes=False)
        pooled = fluid.layers.sequence_pool(input=hidden, pool_type="max")
        out = fluid.layers.fc(input=pooled, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(7)
    lens = [3, 5, 2]
    seqs = [rng.randint(0, V, (n, 1)).astype("int64") for n in lens]
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["words"], [out], exe,
                                      main_program=main)
        ref, = exe.run(main,
                       feed={"words": LoDTensor.from_sequences(seqs)},
                       fetch_list=[out])
    flat = np.concatenate(seqs, axis=0)
    return flat, lens, np.asarray(ref)


def test_c_api_v2_lod_sequence_feeds(tmp_path):
    """ptpu_run2_lod: flat [total, 1] int64 rows + per-sequence lengths
    drive a saved LSTM model from C — the era paddle_arguments
    sequence_start_positions serving path."""
    model_dir = str(tmp_path / "mseq")
    flat, lens, ref = _save_lstm_model(model_dir)
    lib = _load_lib()
    lib.ptpu_run2_lod.restype = ctypes.c_int64
    lib.ptpu_output.restype = ctypes.c_int64

    h = lib.ptpu_create(model_dir.encode())
    assert h > 0, lib.ptpu_last_error().decode()

    data = np.ascontiguousarray(flat)
    names = (ctypes.c_char_p * 1)(b"words")
    bufs = (ctypes.c_void_p * 1)(data.ctypes.data_as(ctypes.c_void_p))
    shape = (ctypes.c_int64 * 2)(*data.shape)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(shape)
    ndims = (ctypes.c_int * 1)(2)
    lod = (ctypes.c_int64 * len(lens))(*lens)
    lods = (ctypes.POINTER(ctypes.c_int64) * 1)(lod)
    lod_lens = (ctypes.c_int * 1)(len(lens))
    n_out = lib.ptpu_run2_lod(ctypes.c_int64(h), names, bufs, shapes,
                              ndims, lods, lod_lens, 1)
    assert n_out == 1, lib.ptpu_last_error().decode()

    out = np.zeros(64, "f")
    out_shape = (ctypes.c_int64 * 8)()
    out_ndim = ctypes.c_int(0)
    odt = ctypes.create_string_buffer(16)
    nbytes = lib.ptpu_output(
        ctypes.c_int64(h), 0, out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(out.nbytes), out_shape, 8, ctypes.byref(out_ndim),
        odt, 16)
    assert nbytes == ref.nbytes, lib.ptpu_last_error().decode()
    got = out[:ref.size].reshape(ref.shape)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    # mismatched lengths must error, not corrupt
    bad = (ctypes.c_int64 * len(lens))(*[n + 1 for n in lens])
    bads = (ctypes.POINTER(ctypes.c_int64) * 1)(bad)
    r = lib.ptpu_run2_lod(ctypes.c_int64(h), names, bufs, shapes, ndims,
                          bads, lod_lens, 1)
    assert r == -1
    assert b"lengths sum" in lib.ptpu_last_error()
    lib.ptpu_destroy(ctypes.c_int64(h))


def test_run_lod_rejects_mismatched_feed_lists(tmp_path):
    """Direct Python callers of capi_host.run_lod with a short lods (or
    buffers/shapes) list must get a ValueError, not silently dropped
    trailing feeds (ADVICE r4 #1; the C entry point always builds
    nfeeds-length arrays, so only Python callers are exposed)."""
    from paddle_tpu import capi_host
    model_dir = str(tmp_path / "m")
    xs, _ = _save_model(model_dir)
    h = capi_host.create(model_dir)
    try:
        buf = np.ascontiguousarray(xs).tobytes()
        with pytest.raises(ValueError, match="mismatched feed lists"):
            capi_host.run_lod(h, ["x"], [buf], [list(xs.shape)], [])
        with pytest.raises(ValueError, match="mismatched feed lists"):
            capi_host.run_lod(h, ["x"], [], [list(xs.shape)], [()])
    finally:
        capi_host.destroy(h)


def test_capi_autodetects_combined_era_dir(tmp_path):
    """ptpu/capi_host create() on an era dir with a combined params
    file (the common era C-API deployment layout) must auto-load it —
    WHATEVER the file is named (the C ABI has no params_filename arg,
    so a lone non-model file is detected as the combined file)."""
    from paddle_tpu import capi_host
    model_dir = str(tmp_path / "comb")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6], dtype="float32")
        out = fluid.layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_reference_model(model_dir, ["x"], [out], exe,
                                      main_program=main,
                                      params_filename="params.bin")
        xs = np.random.RandomState(4).rand(2, 6).astype("f")
        want, = exe.run(main, feed={"x": xs}, fetch_list=[out])
    h = capi_host.create(model_dir)
    try:
        capi_host.run(h, ["x"], [np.ascontiguousarray(xs).tobytes()],
                      [list(xs.shape)])
        got = capi_host.output_array(h, 0)
    finally:
        capi_host.destroy(h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)

"""C ABI wrapper library: in-process ctypes binding + standalone C demo.

The reference exposes its trainer as a C shared library
(reference: wrapper/cxxnet_wrapper.h:29-225) for foreign-language
bindings; here native/capi.cc provides the same surface over an
embedded CPython. These tests exercise both load modes:

* ctypes from this very interpreter (the library joins the running
  interpreter instead of creating one), and
* a pure C program (native/capi_demo.c) that embeds Python standalone.
"""

import ctypes
import os
import subprocess

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
LIB = os.path.join(ROOT, "cxxnet_tpu", "lib", "libcxxnet_wrapper.so")

NET_CFG = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 16
dev = cpu
eta = 0.2
metric = error
"""

ITER_CFG = """
iter = synth
shape = 1,1,8
nclass = 4
ninst = 64
batch_size = 16
iter = end
"""


def _build(target):
    r = subprocess.run(["make", "-C", NATIVE, target],
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("native toolchain unavailable: %s" % r.stderr[-500:])


@pytest.fixture(scope="module")
def lib():
    _build("wrapper")
    lib = ctypes.CDLL(LIB)
    for name in ("CXNIOCreateFromConfig", "CXNNetCreate"):
        getattr(lib, name).restype = ctypes.c_void_p
    for name in ("CXNIOGetData", "CXNIOGetLabel", "CXNNetGetWeight",
                 "CXNNetPredictBatch", "CXNNetPredictIter",
                 "CXNNetExtractBatch", "CXNNetExtractIter"):
        getattr(lib, name).restype = ctypes.POINTER(ctypes.c_float)
    lib.CXNNetEvaluate.restype = ctypes.c_char_p
    return lib


def test_io_roundtrip(lib):
    it = ctypes.c_void_p(lib.CXNIOCreateFromConfig(ITER_CFG.encode()))
    assert it.value
    assert lib.CXNIONext(it) == 1
    shape = (ctypes.c_uint * 4)()
    stride = ctypes.c_uint()
    p = lib.CXNIOGetData(it, shape, ctypes.byref(stride))
    dims = tuple(shape)
    assert dims == (16, 1, 1, 8)
    data = np.ctypeslib.as_array(p, shape=dims).copy()
    assert np.isfinite(data).all()
    lshape = (ctypes.c_uint * 2)()
    p = lib.CXNIOGetLabel(it, lshape, ctypes.byref(stride))
    labels = np.ctypeslib.as_array(p, shape=tuple(lshape)).copy()
    assert labels.shape == (16, 1)
    assert set(np.unique(labels)) <= {0.0, 1.0, 2.0, 3.0}
    # exhaust and rewind
    n = 1
    while lib.CXNIONext(it):
        n += 1
    assert n == 4
    lib.CXNIOBeforeFirst(it)
    assert lib.CXNIONext(it) == 1
    lib.CXNIOFree(it)


def test_net_train_predict_weights(lib, tmp_path):
    net = ctypes.c_void_p(lib.CXNNetCreate(b"cpu", NET_CFG.encode()))
    it = ctypes.c_void_p(lib.CXNIOCreateFromConfig(ITER_CFG.encode()))
    assert net.value and it.value
    lib.CXNNetSetParam(net, b"seed", b"7")
    lib.CXNNetInitModel(net)

    ev0 = lib.CXNNetEvaluate(net, it, b"init").decode()
    assert "init-error:" in ev0
    err0 = float(ev0.rsplit(":", 1)[1])

    for r in range(6):
        lib.CXNNetStartRound(net, r)
        lib.CXNIOBeforeFirst(it)
        while lib.CXNIONext(it):
            lib.CXNNetUpdateIter(net, it)
    ev1 = lib.CXNNetEvaluate(net, it, b"fit").decode()
    err1 = float(ev1.rsplit(":", 1)[1])
    assert err1 < err0

    # raw-batch paths
    rs = np.random.RandomState(3)
    batch = rs.randn(16, 1, 1, 8).astype(np.float32)
    labels = rs.randint(0, 4, (16, 1)).astype(np.float32)
    dshape = (ctypes.c_uint * 4)(16, 1, 1, 8)
    lshape = (ctypes.c_uint * 2)(16, 1)
    dptr = batch.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    lptr = labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    lib.CXNNetUpdateBatch(net, dptr, dshape, lptr, lshape)

    out_size = ctypes.c_uint()
    p = lib.CXNNetPredictBatch(net, dptr, dshape, ctypes.byref(out_size))
    assert out_size.value == 16
    preds = np.ctypeslib.as_array(p, shape=(16,)).copy()
    assert set(np.unique(preds)) <= {0.0, 1.0, 2.0, 3.0}

    oshape = (ctypes.c_uint * 4)()
    p = lib.CXNNetExtractBatch(net, dptr, dshape, b"3", oshape)
    assert tuple(oshape) == (16, 1, 1, 4)
    probs = np.ctypeslib.as_array(p, shape=tuple(oshape)).copy()
    np.testing.assert_allclose(probs.reshape(16, 4).sum(-1), 1.0,
                               atol=1e-5)

    # weight get/set round trip
    wshape = (ctypes.c_uint * 4)()
    wdim = ctypes.c_uint()
    p = lib.CXNNetGetWeight(net, b"fc1", b"wmat", wshape, ctypes.byref(wdim))
    assert wdim.value == 2 and tuple(wshape)[:2] == (16, 8)
    w = np.ctypeslib.as_array(p, shape=(16, 8)).copy()
    w2 = (w * 0.5).astype(np.float32)
    lib.CXNNetSetWeight(
        net, w2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint(w2.size), b"fc1", b"wmat")
    p = lib.CXNNetGetWeight(net, b"fc1", b"wmat", wshape, ctypes.byref(wdim))
    np.testing.assert_allclose(
        np.ctypeslib.as_array(p, shape=(16, 8)), w2, rtol=1e-6)
    # absent weight -> NULL
    assert not lib.CXNNetGetWeight(net, b"nosuch", b"wmat", wshape,
                                   ctypes.byref(wdim))

    # save / load through the ABI
    mpath = str(tmp_path / "capi.model").encode()
    lib.CXNNetSaveModel(net, mpath)
    net2 = ctypes.c_void_p(lib.CXNNetCreate(b"cpu", NET_CFG.encode()))
    lib.CXNNetLoadModel(net2, mpath)
    # PredictIter works on the iterator's *current* batch, like the
    # reference (reference: wrapper/cxxnet_wrapper.cpp:171-173)
    lib.CXNIOBeforeFirst(it)
    assert lib.CXNIONext(it) == 1
    p = lib.CXNNetPredictIter(net2, it, ctypes.byref(out_size))
    assert p and out_size.value == 16
    lib.CXNNetFree(net2)
    lib.CXNNetFree(net)
    lib.CXNIOFree(it)


def test_standalone_c_program():
    """A pure C binary embeds the interpreter and trains end to end."""
    _build("demo")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([os.path.join(NATIVE, "capi_demo")],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=NATIVE)
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    assert "capi_demo: ok" in r.stdout

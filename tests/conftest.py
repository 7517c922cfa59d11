"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run on
XLA's host platform with 8 virtual devices, exactly as the driver's
multichip dry-run does (see cxxnet_tpu.parallel.force_host_cpu).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

# floor for persistent-cache writes, should the cache ever be turned on
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")

from cxxnet_tpu.parallel import force_host_cpu

force_host_cpu(8)

# persistent XLA compilation cache: DISABLED for the suite (r6).
#
# History: r5 enabled a .jax-cache dir because the suite's wall time is
# compile-dominated, then had to set
# jax_persistent_cache_enable_xla_caches=none because the XLA-level
# kernel/autotune caches are not keyed by device assignment (8-device
# entries corrupted submesh programs). That was not enough. The
# remaining jax key-value cache stores SERIALIZED EXECUTABLES, and on
# this box it demonstrably accumulates poisoned blobs within a day of
# normal runs:
#   * r6 repro 1: elastic-resume loads came back numerically wrong —
#     bisected to ONE cached jit_train_step blob; deleting that single
#     file fixed it (the r5 "order-sensitive test_lm chunking pair"
#     was the same failure class landing on different tests).
#   * r6 repro 2: after one day of cache accrual,
#     test_guards::test_nan_guard_2_recovers_via_cli SEGFAULTED
#     standalone (device_put inside the in-process CLI recovery path)
#     and passed the moment the cache dir was wiped — the same
#     "poisoned state segfaults later CLI tests" failure r5 saw from
#     the XLA-level caches.
# A run that segfaults half-way scores worse than any compile time
# saved, so the suite now always compiles fresh: correctness of the
# run beats ~3 minutes of wall time. (A fresh-cache full run measured
# 739s vs 536s warm on the 2-core rig, inside the tier-1 budget.)
# The env var carries the same setting into every subprocess a test
# spawns (`python -m cxxnet_tpu`, the C demo, tools): cli.main places
# the cache in <checkout>/.jax-cache (parallel.place_compile_cache),
# and a spawned run must not start the accrual described above.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax

jax.config.update("jax_enable_compilation_cache", False)

import pytest


@pytest.fixture
def no_persistent_compile_cache():
    """Explicit shield for trajectory-agreement tests (the test_lm
    chunking pair, elastic resume): these compare two compilations of
    related programs at tight tolerances, the exact shape the poisoned
    persistent cache broke twice (see the comment above). The cache is
    currently disabled suite-wide, so this is a no-op belt — but it
    documents WHICH tests must never run against a shared compile
    cache if the cache is ever re-enabled for wall-time reasons."""
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


def write_idx(path, arr):
    """MNIST idx(.gz) writer — single source of truth lives in
    tools/make_mnist_idx.py (the user-facing staging tool); re-exported
    here for the reader tests and reference-config end-to-end runs."""
    from tools.make_mnist_idx import write_idx as _w
    _w(str(path), arr)


def make_quadrant_mnist(data_dir, seed=0, ntrain=600, ntest=200):
    """Write the four MNIST idx.gz files with a learnable synthetic
    task (label = brightest 14x14 quadrant of a 28x28 canvas) — used by
    the reference-config end-to-end CLI tests."""
    import os
    import numpy as np
    rs = np.random.RandomState(seed)

    def make(n):
        labs = rs.randint(0, 4, size=(n,)).astype(np.uint8)
        imgs = rs.randint(0, 40, size=(n, 28, 28)).astype(np.uint8)
        for i, l in enumerate(labs):
            y, x = divmod(int(l), 2)
            imgs[i, y * 14:(y + 1) * 14, x * 14:(x + 1) * 14] += 120
        return imgs, labs
    ti, tl = make(ntrain)
    ei, el = make(ntest)
    write_idx(os.path.join(str(data_dir), "train-images-idx3-ubyte.gz"), ti)
    write_idx(os.path.join(str(data_dir), "train-labels-idx1-ubyte.gz"), tl)
    write_idx(os.path.join(str(data_dir), "t10k-images-idx3-ubyte.gz"), ei)
    write_idx(os.path.join(str(data_dir), "t10k-labels-idx1-ubyte.gz"), el)


def make_packfile(img_root, lst_path, bin_path, n, seed=0, side=48,
                  nclass=121, prefix="im"):
    """Synthesize n random jpegs + .lst index and pack them into a
    BinaryPage packfile — shared by reference-config end-to-end tests."""
    import os
    import cv2
    import numpy as np
    from cxxnet_tpu.io import binpage
    rs = np.random.RandomState(seed)
    os.makedirs(str(img_root), exist_ok=True)
    lines = []
    for i in range(n):
        name = "%s_%d.jpg" % (prefix, i)
        img = rs.randint(0, 255, size=(side, side, 3), dtype=np.uint8)
        cv2.imwrite(os.path.join(str(img_root), name), img)
        lines.append("%d\t%d\t%s" % (i, rs.randint(0, nclass), name))
    with open(str(lst_path), "w") as f:
        f.write("\n".join(lines) + "\n")
    binpage.pack_images(str(lst_path), str(img_root), str(bin_path),
                        silent=True)

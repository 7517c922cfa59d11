"""Overlapped feed pipeline tests (io/prefetch.py + the hardened
ThreadBufferIterator): ordering/determinism under prefetch_worker > 1,
backpressure bounds, before_first restart semantics, producer-error
propagation, stall-metric accounting, and trajectory identity with the
device prefetcher on vs off."""
import numpy as np
import pytest

from cxxnet_tpu import config
from cxxnet_tpu.io import (DataBatch, DataIterator, ThreadBufferIterator,
                           create_iterator)
from cxxnet_tpu.io.prefetch import (DevicePrefetchIterator,
                                    ParallelDecodeIterator)
from cxxnet_tpu.metrics import StallClock
from cxxnet_tpu.profiler import StepTimer
from cxxnet_tpu.trainer import Trainer


# ---------------------------------------------------------------------------
# parallel decode pool


def _jpeg_bytes(seed, side=40):
    import cv2
    rs = np.random.RandomState(seed)
    img = cv2.resize(rs.randint(0, 256, (8, 8, 3), np.uint8),
                     (side, side))
    _, enc = cv2.imencode(".jpg", img)
    return enc.tobytes()


class RawStub:
    """Minimal next_raw() source: n distinct JPEGs in index order."""

    def __init__(self, n, fail_at=None):
        self.n = n
        self.fail_at = fail_at
        self.reads = 0
        self._bufs = [_jpeg_bytes(i) for i in range(n)]
        self._pos = 0

    def set_param(self, name, val):
        pass

    def init(self):
        pass

    def before_first(self):
        self._pos = 0

    def next_raw(self):
        if self._pos >= self.n:
            return None
        i = self._pos
        self._pos += 1
        self.reads += 1
        buf = b"not an image" if i == self.fail_at else self._bufs[i]
        return i, np.asarray([float(i % 5)], np.float32), "raw", buf


def _drain_indices(it):
    out = []
    it.before_first()
    while it.next():
        out.append(it.value.index)
    return out


def test_pool_preserves_order_and_matches_serial():
    serial = ParallelDecodeIterator(RawStub(37), prefetch_worker=0)
    pooled = ParallelDecodeIterator(RawStub(37), prefetch_worker=3)
    serial.init()
    pooled.init()
    assert _drain_indices(pooled) == list(range(37))
    # decoded pixel data identical to the serial path, image by image
    serial.before_first()
    pooled.before_first()
    while serial.next():
        assert pooled.next()
        np.testing.assert_array_equal(serial.value.data, pooled.value.data)
        assert serial.value.index == pooled.value.index
    assert not pooled.next()


def test_pool_backpressure_bounds_readahead():
    base = RawStub(64)
    it = ParallelDecodeIterator(base, prefetch_worker=2,
                                prefetch_depth=5)
    it.init()
    it.before_first()
    consumed = 0
    while it.next():
        consumed += 1
        # the reader may run at most depth ahead of consumption: the
        # bounded in-flight window IS the backpressure
        assert base.reads <= consumed + 5
        assert it.in_flight <= 5
    assert consumed == 64


def test_pool_before_first_restarts_cleanly():
    it = ParallelDecodeIterator(RawStub(20), prefetch_worker=2,
                                prefetch_depth=4)
    it.init()
    it.before_first()
    for _ in range(3):     # abandon mid-epoch with futures in flight
        assert it.next()
    assert _drain_indices(it) == list(range(20))
    # and again: a drained iterator restarts too
    assert _drain_indices(it) == list(range(20))


def test_pool_decode_error_raises_in_consumer():
    it = ParallelDecodeIterator(RawStub(12, fail_at=6),
                                prefetch_worker=2)
    it.init()
    it.before_first()
    with pytest.raises(ValueError, match="decode"):
        while it.next():
            pass


def test_pool_worker_clamp_and_param_validation():
    import os
    it = ParallelDecodeIterator(RawStub(4))
    it.set_param("prefetch_worker", "64")
    it.init()
    assert it._workers <= (os.cpu_count() or 1)
    with pytest.raises(ValueError):
        it.set_param("prefetch_mode", "fibers")
    with pytest.raises(ValueError):
        it.set_param("prefetch_depth", "-1")


def test_pool_process_mode_matches_thread_mode():
    ref = ParallelDecodeIterator(RawStub(6), prefetch_worker=0)
    ref.init()
    it = ParallelDecodeIterator(RawStub(6), prefetch_worker=2,
                                prefetch_mode="process")
    it.init()
    try:
        ref.before_first()
        it.before_first()
        n = 0
        while it.next():
            assert ref.next()
            np.testing.assert_array_equal(ref.value.data, it.value.data)
            n += 1
        assert n == 6
    finally:
        it.close()


def test_decode_worker_imports_stay_jax_free():
    """A spawned decode worker unpickles ``prefetch._decode_task`` and
    runs it: that import set must never hold jax — a worker that
    initialised a backend would take the chip from its parent."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import cxxnet_tpu.io.prefetch; "
         "from cxxnet_tpu.io.image import DataInst, _decode_image; "
         "print(sorted(m for m in sys.modules "
         "if m.split('.')[0] in ('jax', 'jaxlib', 'libtpu')))"],
        capture_output=True, text=True, timeout=120, cwd=repo)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "[]", r.stdout


def test_imgbin_pipeline_deterministic_across_worker_counts(tmp_path):
    """The full imgbin chain (pool + random augment + batcher) emits
    bitwise-identical batches for prefetch_worker 0 and 3: parallel
    decode must not change batch order or augment RNG consumption."""
    from conftest import make_packfile
    lst, binp = tmp_path / "a.lst", tmp_path / "a.bin"
    make_packfile(tmp_path / "img", lst, binp, 50, side=48)

    def make(workers):
        return create_iterator(
            [("iter", "imgbinx"), ("image_list", str(lst)),
             ("image_bin", str(binp)), ("rand_crop", "1"),
             ("rand_mirror", "1"), ("seed_data", "9"),
             ("native_decode", "0"),
             ("prefetch_worker", str(workers))],
            [("batch_size", "16"), ("input_shape", "3,40,40"),
             ("silent", "1")])

    a, b = make(0), make(3)
    for _ in range(2):          # two epochs: RNG streams stay in sync
        a.before_first()
        b.before_first()
        while a.next():
            assert b.next()
            np.testing.assert_array_equal(a.value.data, b.value.data)
            np.testing.assert_array_equal(a.value.label, b.value.label)
        assert not b.next()


# ---------------------------------------------------------------------------
# ThreadBufferIterator hardening


class FailingIterator(DataIterator):
    def __init__(self, n_ok, total=8):
        self.n_ok = n_ok
        self.total = total
        self._pos = 0

    def before_first(self):
        self._pos = 0

    def next(self):
        if self._pos >= self.n_ok:
            raise ValueError("synthetic decode failure")
        self._pos += 1
        return self._pos <= self.total

    @property
    def value(self):
        # divisible over the conftest 8-device mesh, so staging works
        # and the PRODUCER error is what propagates
        return DataBatch(np.zeros((32, 1, 1, 16), np.float32),
                         np.zeros((32, 1), np.float32))


def test_threadbuffer_propagates_producer_error():
    it = ThreadBufferIterator(FailingIterator(n_ok=3))
    it.before_first()
    assert it.next() and it.next() and it.next()
    # the 4th batch died on the producer: next() must raise, not hang
    with pytest.raises(RuntimeError, match="synthetic decode failure"):
        it.next()
    # and the iterator is reusable afterwards (fresh producer)
    it.base.n_ok = 100
    it.before_first()
    assert it.next()


def test_threadbuffer_buffer_size_set_param():
    it = ThreadBufferIterator(FailingIterator(n_ok=100))
    it.set_param("buffer_size", "5")
    it.before_first()
    assert it._queue.maxsize == 5
    while it.next():
        pass
    with pytest.raises(ValueError):
        it.set_param("buffer_size", "0")


def test_threadbuffer_error_during_restart_is_swallowed():
    it = ThreadBufferIterator(FailingIterator(n_ok=3))
    it.before_first()
    assert it.next()
    it.base.n_ok = 100           # producer already failed or will fail
    it.before_first()            # drain must not raise
    assert it.next()


# ---------------------------------------------------------------------------
# device prefetch + trajectory identity


MLP_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 32
dev = cpu
eta = 0.5
momentum = 0.9
metric = error
"""


def make_trainer(**overrides):
    tr = Trainer()
    for k, v in config.parse_string(MLP_CONF):
        tr.set_param(k, v)
    for k, v in overrides.items():
        tr.set_param(k, str(v))
    tr.init_model()
    return tr


def make_synth():
    return create_iterator(
        [("iter", "synth"), ("batch_size", "32"), ("shape", "1,1,16"),
         ("nclass", "4"), ("ninst", "160"), ("shuffle", "1"),
         ("iter", "end")])


def run_plain(tr, itr, rounds):
    out = []
    for _ in range(rounds):
        itr.before_first()
        while itr.next():
            tr.update(itr.value)
        out.append(tr.evaluate(None, "train"))
    return out


def run_feed(tr, itr, rounds, **kw):
    feed = DevicePrefetchIterator(itr, tr, **kw)
    out = []
    for _ in range(rounds):
        feed.before_first()
        while feed.next():
            item = feed.value
            if isinstance(item, list):
                for s in item:
                    tr.update(s)
            elif item.fused:
                tr.update_fused(item)
            else:
                tr.update(item)
        out.append(tr.evaluate(None, "train"))
    return feed, out


def _weights(tr):
    return [np.asarray(a) for p in tr.params if p
            for a in p.values()]


def assert_weights_close(ta, tb):
    # house tolerance (test_fuse_steps): XLA CPU execution is NOT
    # bitwise run-to-run deterministic (threaded reductions), so
    # trajectory comparisons — even same program, same inputs — must
    # allow float jitter; the BATCH STREAM itself is pinned bitwise by
    # test_device_prefetch_preserves_stream below
    for a, b in zip(_weights(ta), _weights(tb)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_device_prefetch_identical_trajectory():
    ta = make_trainer()
    run_plain(ta, make_synth(), 3)
    tb = make_trainer()
    run_feed(tb, make_synth(), 3, depth=3)
    assert_weights_close(ta, tb)
    # donation must not change the math either (modulo float jitter:
    # aliasing can legally change XLA's fusion choices)
    tc = make_trainer(donate_inputs=1)
    run_feed(tc, make_synth(), 3)
    assert_weights_close(ta, tc)


def test_device_prefetch_fused_group_trajectory():
    tr = make_trainer(fuse_steps=5)
    itr = make_synth()
    for _ in range(2):
        itr.before_first()
        batches = []
        while itr.next():
            b = itr.value
            batches.append(DataBatch(b.data.copy(), b.label.copy()))
        tr.update_fused(tr.stage_fused(batches))   # 160/32 = one group
        tr.evaluate(None, "train")
    tb = make_trainer(fuse_steps=5, donate_inputs=1)
    run_feed(tb, make_synth(), 2)
    assert_weights_close(tr, tb)


def test_device_prefetch_preserves_stream():
    """The bitwise half of the 'identical results' contract: the feed
    stages exactly the batches the plain loop sees — same order, same
    bytes, across shuffled rounds — so any trajectory difference can
    only be float jitter, never data. (Host-side comparison: numpy and
    the staging copy ARE deterministic.)"""
    tr = make_trainer()
    plain, feed_seen = make_synth(), make_synth()
    feed = DevicePrefetchIterator(feed_seen, tr, depth=2)
    for _ in range(2):
        plain.before_first()
        feed.before_first()
        while plain.next():
            assert feed.next()
            staged = feed.value
            np.testing.assert_array_equal(
                np.asarray(staged.device[0]), plain.value.data)
            np.testing.assert_array_equal(
                np.asarray(staged.device[2][0]), plain.value.label)
        assert not feed.next()


def test_device_prefetch_restart_mid_epoch():
    tr = make_trainer()
    feed = DevicePrefetchIterator(make_synth(), tr, depth=1)
    feed.before_first()
    assert feed.next()      # producer now blocked on the full queue
    feed.before_first()     # restart must drain it out, not deadlock
    n = 0
    while feed.next():
        n += 1
    assert n == 5


def test_device_prefetch_propagates_producer_error():
    tr = make_trainer()
    bad = FailingIterator(n_ok=2)   # dies mid-epoch on its own thread
    feed = DevicePrefetchIterator(bad, tr)
    feed.before_first()
    with pytest.raises(RuntimeError, match="synthetic decode failure"):
        while feed.next():
            pass


# ---------------------------------------------------------------------------
# stall accounting


def test_stallclock_accounting():
    c = StallClock()
    assert c.wait_frac == 0.0
    c.add_wait(0.3)
    c.add_busy(0.1)
    assert c.waits == 1 and c.events == 1
    assert c.total_s == pytest.approx(0.4)
    assert c.wait_frac == pytest.approx(0.75)
    snap = c.snapshot()
    assert snap["wait_s"] == pytest.approx(0.3)
    c.clear()
    assert c.total_s == 0.0


def test_device_prefetch_stats_accounting():
    tr = make_trainer()
    feed, _ = run_feed(tr, make_synth(), 2, depth=2)
    st = feed.stats()
    # the producer pulled batches and staged them; the clocks saw it
    assert st["source_wait"]["waits"] > 0
    assert st["stage_busy"]["events"] > 0
    assert st["get_wait"]["waits"] > 0
    assert 0.0 <= st["feed_stall_frac"] <= 1.0


def test_steptimer_feed_stall_fraction():
    t = StepTimer()
    t.tick()
    t.note_feed_wait(0.01)
    t.tick()
    assert 0.0 < t.round_feed_stall_frac <= 1.0
    assert "feed stall" in t.summary(32)
    assert t.feed.wait_s == pytest.approx(0.01)
    t.reset_clock()
    assert t.round_feed_stall_frac == 0.0
    assert "feed stall" not in t.summary(32)


# ---------------------------------------------------------------------------
# CLI integration: legacy loop (device_prefetch = 0) == new loop


CLI_CONF = """
data = train
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 128
    shuffle = 1
iter = end
eval = test
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 64
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,16
batch_size = 32
dev = cpu
save_model = 0
num_round = 3
max_round = 3
eta = 0.3
metric = error
silent = 1
"""


def _run_cli(tmp_path, capsys, *overrides):
    """Returns the per-round test-error trajectory from stderr."""
    import re
    from cxxnet_tpu.cli import LearnTask
    conf = tmp_path / "t.conf"
    conf.write_text(CLI_CONF)
    LearnTask().run([str(conf)] + list(overrides))
    err = capsys.readouterr().err
    vals = [float(v) for v in re.findall(r"test-error:([0-9.]+)", err)]
    assert vals, err
    return vals


def _assert_trajectories_agree(a, b):
    # error-rate trajectories agree to a few eval instances: the data
    # stream is bitwise identical across feed modes (pinned above), but
    # XLA CPU execution is not run-to-run deterministic, and ULP jitter
    # amplified over rounds can flip boundary instances of the argmax
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert abs(x - y) <= 0.08, (a, b)


def test_cli_device_prefetch_agrees_with_legacy(tmp_path, capsys):
    new = _run_cli(tmp_path, capsys, "device_prefetch=1")
    legacy = _run_cli(tmp_path, capsys, "device_prefetch=0")
    _assert_trajectories_agree(new, legacy)


def test_cli_device_prefetch_fused_agrees_with_legacy(tmp_path, capsys):
    new = _run_cli(tmp_path, capsys, "fuse_steps=2")
    legacy = _run_cli(tmp_path, capsys, "fuse_steps=2",
                      "device_prefetch=0")
    _assert_trajectories_agree(new, legacy)

"""The state a train step takes is placed as the step declares it.

A jit keys its build on whether a donated input is committed. A step's
outputs are committed by ``out_shardings``, so a first step whose
params or optimizer state are not costs a second trace, lowering and
backend build at step 2: the whole train step, twice a start. A
one-device trainer used to leave fresh state uncommitted, and anything
assigned to ``Trainer.params`` from outside (the benchmark's driver
assigns a jit's output) was taken as it came. ``Trainer.params`` and
``Trainer.opt_state`` now commit what they are assigned to ``_psh`` /
``_osh`` on every mesh; these tests hold that on ONE device (``dev =
cpu:0``: the suite's eight virtual devices always took the committed
path), by who assigned the state and by which step programs run."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import config
from cxxnet_tpu.obs import trace as obs_trace
from cxxnet_tpu.trainer import Trainer

# batch_norm's running statistics ride out of the accumulate step and
# are folded into the params on the host (_merge_state)
from test_fuse_steps import BN_CONF, make_batches


def _trainer(**overrides):
    tr = Trainer()
    for k, v in config.parse_string(BN_CONF):
        tr.set_param(k, v)
    for k, v in dict(overrides, dev="cpu:0", momentum=0.9).items():
        tr.set_param(k, str(v))
    return tr


def _fresh(tmp_path, **kw):
    tr = _trainer(**kw)
    tr.init_model()
    return tr


def _from_a_jit(tmp_path, **kw):
    """As benchmark/drivers/train.py::place_weights: the fresh weights
    dropped, a jit's outputs (uncommitted) assigned in their place."""
    tr = _fresh(tmp_path, **kw)
    shape_of = jax.tree.map(lambda x: x.shape, tr.params)
    tr.params = None

    def make(key):
        leaves, tree = jax.tree.flatten(
            shape_of, is_leaf=lambda s: isinstance(s, tuple))
        keys = jax.random.split(key, len(leaves))
        return tree.unflatten([0.1 * jax.random.normal(k, s, jnp.float32)
                               for k, s in zip(keys, leaves)])
    made = jax.jit(make)(jax.random.PRNGKey(3))
    assert not any(x.committed for x in jax.tree.leaves(made))
    tr.params = made
    return tr


def _set_weight(tmp_path, **kw):
    tr = _fresh(tmp_path, **kw)
    w = tr.get_weight("fc1", "wmat")
    tr.set_weight(0.5 * w, "fc1", "wmat")
    np.testing.assert_array_equal(tr.get_weight("fc1", "wmat"), 0.5 * w)
    return tr


def _resumed(tmp_path, **kw):
    path = str(tmp_path / "0001.model")
    _fresh(tmp_path, **kw).save_model(path)
    tr = _trainer(**kw)
    tr.load_model(path)
    return tr


def _finetuned(tmp_path, **kw):
    path = str(tmp_path / "0001.model")
    _fresh(tmp_path, **kw).save_model(path)
    tr = _trainer(**kw)
    tr.copy_model_from(path)
    return tr


def _per_step(tr, batches):
    for b in batches:
        tr.update(b)
        yield tr.last_loss


def _fused(tr, batches):
    k = tr.fuse_steps
    for i in range(0, len(batches), k):
        tr.update_fused(tr.stage_fused(batches[i:i + k]))
        yield tr.last_loss


def _assert_placed(tr):
    want = jax.tree.leaves((tr._psh, tr._osh))
    leaves = jax.tree.leaves((tr.params, tr.opt_state))
    assert len(leaves) == len(want) > 0
    for x, sh in zip(leaves, want):
        assert x.committed and x.sharding == sh
    assert all(x.committed for x in jax.tree.leaves(tr.grad_accum))


# id: (who assigned the state, conf overrides, the loop, the step numbers
# of the dispatches that may build: the accumulate program is first run
# at step 1 and the apply program at step 2; a fused group of two is
# one dispatch, put down to its last step)
CASES = {
    "fresh_init": (_fresh, {}, _per_step, [1]),
    "params_from_a_jit": (_from_a_jit, {}, _per_step, [1]),
    "set_weight": (_set_weight, {}, _per_step, [1]),
    "update_period_2": (_fresh, {"update_period": 2}, _per_step, [1, 2]),
    "update_fused": (_fresh, {"fuse_steps": 2}, _fused, [2]),
    "resumed": (_resumed, {}, _per_step, [1]),
    "finetune_copy": (_finetuned, {}, _per_step, [1]),
    "from_a_jit_update_period_2": (_from_a_jit, {"update_period": 2},
                                   _per_step, [1, 2]),
    "from_a_jit_fused": (_from_a_jit, {"fuse_steps": 2}, _fused, [2]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_step_program_is_built_once(case, tmp_path):
    make, overrides, loop, builds_at = CASES[case]
    tr = make(tmp_path, **overrides)
    assert tr.n_devices == 1
    _assert_placed(tr)

    began = time.perf_counter()
    losses = list(loop(tr, make_batches(6)))
    assert np.isfinite(np.asarray(jax.device_get(losses))).all()
    built = [e[3] for e in obs_trace.compile_events()
             if e[2] >= began and e[0] == "backend"
             and e[3] is not None and e[3][0] == "trainer.update"]
    assert built == [("trainer.update", n) for n in builds_at]
    _assert_placed(tr)   # the steps' own outputs, stored past the seam


@pytest.mark.parametrize("committed", [False, True],
                         ids=["uncommitted", "committed"])
def test_an_assigned_device_array_keeps_its_buffer(committed, tmp_path):
    """Placing re-wraps a device array: no second copy of the state."""
    tr = _fresh(tmp_path)
    params = jax.tree.map(lambda x: x + 1.0, tr.params)
    if not committed:
        params = jax.device_put(jax.device_get(params))
    assert all(x.committed == committed for x in jax.tree.leaves(params))
    tr.params = params
    for new, old in zip(jax.tree.leaves(tr.params),
                        jax.tree.leaves(params)):
        assert new.committed
        assert new.unsafe_buffer_pointer() == old.unsafe_buffer_pointer()
    tr.params = tr.opt_state = None   # as the benchmark's driver frees it
    assert tr.params is None and tr.opt_state is None


def _by_hand(tr, shardings):
    """The state stored past the seam: committed to ``shardings``, or
    (None) left uncommitted as a one-device trainer's used to be."""
    for attr, sh in zip(("_params", "_opt_state"), shardings):
        host = jax.device_get(getattr(tr, attr))
        setattr(tr, attr, jax.device_put(host, sh))
    want = shardings[0] is not None
    assert all(x.committed == want for x in jax.tree.leaves(
        (tr._params, tr._opt_state)))


@pytest.mark.parametrize("overrides,loop", [
    ({}, _per_step), ({"update_period": 2}, _per_step),
    ({"fuse_steps": 2}, _fused)], ids=["per_step", "accumulate", "fused"])
@pytest.mark.parametrize("by_hand", ["placed", "uncommitted"])
def test_placement_changes_no_loss(by_hand, overrides, loop, tmp_path):
    """The first losses of a one-device trainer are bit-equal to those
    of the same conf and seed with the state placed by hand before step
    1, and with it left uncommitted: the seam chooses which build of
    the step runs first, not what the step computes."""
    batches = make_batches(6, seed=1)
    tr = _fresh(tmp_path, **overrides)
    got = jax.device_get(list(loop(tr, batches)))
    other = _fresh(tmp_path, **overrides)
    _by_hand(other, (other._psh, other._osh) if by_hand == "placed"
             else (None, None))
    want = jax.device_get(list(loop(other, batches)))
    assert len(got) >= 3
    assert [np.float32(x).tobytes() for x in got] == \
        [np.float32(x).tobytes() for x in want]
    for a, b in zip(jax.tree.leaves(jax.device_get(tr.params)),
                    jax.tree.leaves(jax.device_get(other.params))):
        assert a.tobytes() == b.tobytes()

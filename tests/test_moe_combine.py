"""``moe_combine``, the Pallas kernel that adds a piece's sorted rows
back into their tokens' rows (``ops/moe_sorted.py``), in interpret mode
at small shapes against XLA's ``.at[].add``: the routings a layer can
hand it, and the routed walk's forward and gradient with it in place of
the scatter-add. ``test_chip_compile.py`` compiles it at a cell's
shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from cxxnet_tpu.ops import moe_sorted as ms

E = 128


def _scatter_add(acc, rows, w, tok, live):
    valid = (jnp.arange(rows.shape[0]) < live)[:, None]
    return acc.at[tok].add(jnp.where(valid, rows * w[:, None], 0))


# tokens, and each piece's (destination tokens, live) of 256 rows in
# blocks of 32 (``combine_block`` at this chunk): one compile for all
CASES = {
    # a token in several rows of one block, and in the blocks before
    "repeated_tokens": (64, lambda r: [(r.integers(0, 16, 256), 256)]),
    # rows from ``live`` on hold no pair: NaN, never read
    "invalid_tail": (64, lambda r: [(r.integers(0, 64, 256), 200)]),
    # the last piece of a walk: a few live rows, most blocks empty
    "partial_last_piece": (64, lambda r: [
        (r.integers(0, 64, 256), 256), (r.integers(0, 64, 256), 5)]),
    # one token's pairs in two pieces
    "token_in_two_pieces": (64, lambda r: [
        (np.sort(r.integers(0, 24, 256)), 256),
        (np.sort(r.integers(0, 24, 256)), 131)]),
    # each token in two rows a piece, as few tokens make a chunk of 256
    "small_chunk": (64, lambda r: [(r.permutation(128).repeat(2) % 64, 256)]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_combine_adds_each_live_row_into_its_token(case):
    tokens, pieces = CASES[case]
    n = 256
    block = ms.combine_block(n, E)
    assert block == 32
    r = np.random.default_rng(len(case))
    acc = jnp.asarray(r.standard_normal((tokens, E)), jnp.float32)
    want, got = acc, ms.combine_sums(tokens, E, n).at[:tokens, 0].set(acc)
    for tok, live in pieces(r):
        tok = jnp.asarray(tok, jnp.int32)
        rows = r.standard_normal((n, E)).astype(np.float32)
        rows[live:] = np.nan
        rows, w = jnp.asarray(rows), jnp.asarray(r.random(n), jnp.float32)
        want = _scatter_add(want, rows, w, tok, live)
        got = ms.moe_combine(got, rows, w, tok, live, block, True)
    np.testing.assert_allclose(got[:tokens, 0], want, rtol=1e-6, atol=1e-6)


def test_combine_orders_every_copy_of_a_repeated_token():
    """The interpreter's race detector, copies run when waited for: a
    token's read-modify-write never overlaps another copy of its row."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    r = np.random.default_rng(0)
    tok = jnp.asarray(r.integers(0, 12, 48), jnp.int32)
    rows = jnp.asarray(r.standard_normal((48, E)), jnp.float32)
    w = jnp.asarray(r.random(48), jnp.float32)
    acc = jnp.asarray(r.standard_normal((12, E)), jnp.float32)
    got = ms.moe_combine(ms.combine_sums(12, E, 48).at[:12, 0].set(acc),
                         rows, w, tok, 40, ms.combine_block(48, E),
                         pltpu.InterpretParams(detect_races=True))
    assert not ipc.races.races_found
    np.testing.assert_allclose(got[:12, 0],
                               _scatter_add(acc, rows, w, tok, 40),
                               rtol=1e-6, atol=1e-6)


def test_routed_walk_with_the_kernel_gives_the_scatter_adds_sums(
        monkeypatch):
    """``experts`` with ``moe_combine`` (``combine_by_kernel`` made true
    under interpret) against XLA's scatter-add, forward and every
    gradient: pieces of 128 rows, the last partial, tokens with several
    experts here and in both pieces."""
    monkeypatch.setattr(ms, "GMM_TILE", (128, 128, 128))
    r = np.random.default_rng(5)
    P, m, total, held, topk = 80, 64, 8, 4, 4
    x = jnp.asarray(r.standard_normal((P, E)), jnp.float32)
    w1 = jnp.asarray(r.standard_normal((held, E, 2 * m)) * 0.1, jnp.float32)
    w2 = jnp.asarray(r.standard_normal((held, m, E)) * 0.1, jnp.float32)
    cot = jnp.asarray(r.standard_normal((P, E)), jnp.float32)
    wts, idx = ms.route(x, jnp.asarray(r.standard_normal((total, E)),
                                       jnp.float32), topk, True)
    order, counts, stats = ms.plan(idx, 1, held, 128)
    assert 128 < stats[0] < 256

    def walk(kernel):
        monkeypatch.setattr(ms, "combine_by_kernel", lambda _: kernel)
        return jax.jit(lambda: jax.value_and_grad(lambda *a: (ms.experts(
            *a, order, counts, topk, 128, True) * cot).sum(),
            argnums=(0, 1, 2, 3))(x, wts.reshape(-1), w1, w2))()
    got, want = walk(True), walk(False)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

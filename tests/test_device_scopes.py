"""Device time by the program's own names (``obs.trace.PARTS``,
``note_program``, ``device_scopes``, ``scope_of``): the train step of a
tiny net of each attention kind, built as ``cli.main`` builds it, one
step on the CPU, then the table from instruction to ``op_name`` of the
step's own executable.

What only a chip can show (that the join to a device trace's events
holds, what share of the time the parts cover) is the benchmark's:
``benchmark/scope_time.py``, ``scope_coverage_pct.train``.
"""

import collections
import contextlib
import gc
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import layers as L
from cxxnet_tpu.io import DataBatch
from cxxnet_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module  # noqa: E402

SEQ, ROWS = 32, 2
BLOCK = ("attn_proj", "attn_core", "norm")
ROUTED = ("router", "moe_dispatch", "moe_experts")
# kind -> (the benchmark's tiny configuration of it, the words of PARTS
# its block makes in the forward and the backward pass, its layers)
KINDS = {
    "plain": ("gpt2", BLOCK + ("mlp",),
              ("embed", "transformer_stack", "lm_head")),
    "grouped_sorted": ("sdar", BLOCK + ("attn_prep",) + ROUTED,
                       ("bd_noise", "embed", "transformer_stack",
                        "lm_head")),
    "mla_mtp": ("joyai", BLOCK + ("attn_prep", "mlp") + ROUTED,
                ("embed", "transformer_stack", "mtp", "lm_head")),
    "dsa": ("keye", BLOCK + ("attn_prep", "idx_proj", "idx") + ROUTED,
            ("embed", "transformer_stack", "lm_head")),
}
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(",
                         re.M)
NOT_OPERATIONS = ("parameter", "constant", "tuple", "get-tuple-element")


def _trainer(kind, remat, extra=()):
    """The tiny configuration's trainer as ``cli.main`` builds it, every
    stack under ``remat``."""
    with open(os.path.join(BENCH, "tests", KINDS[kind][0] + "_tiny.json")) \
            as f:
        cfg = json.load(f)
    conf = []
    for line in cfg["program"]["conf"]:
        if line.split("=")[0].strip() == "remat":
            continue
        conf.append(line)
        if re.match(r"layer\[.*= (transformer_stack|mtp):", line):
            conf += ["  remat = %d" % remat] + list(extra)
    cfg["program"]["conf"] = conf + ["dev = cpu:0"]
    drv = load_module(os.path.join(BENCH, "drivers", "train.py"))
    mix = {"seq_len": SEQ, "rows_per_step": ROWS, "prefetch_depth": 2}
    return drv.build_task(cfg, mix, 7).trainer, cfg


def _batch(cfg):
    toks = np.random.default_rng(0).integers(
        0, cfg["sizes"]["vocab_size"] - 2, (ROWS, SEQ + 1))
    return DataBatch(
        data=toks[:, :SEQ].reshape(ROWS, 1, SEQ, 1).astype(np.float32),
        label=toks[:, 1:].astype(np.float32))


@pytest.fixture(scope="module")
def stepped():
    """(kind, remat) -> (the table of the step that ran, its optimized
    text), each built once."""
    made = {}

    def get(kind, remat):
        if (kind, remat) not in made:
            tr, cfg = _trainer(kind, remat)
            tr.update(_batch(cfg))
            table = obs_trace.device_scopes()["train_step"]
            fn, specs = obs_trace._programs["train_step"]
            made[kind, remat] = (table,
                                 fn.lower(*specs).compile().as_text())
        return made[kind, remat]
    return get


def _by_scope(table):
    """{(part, phase): instructions}."""
    return collections.Counter(obs_trace.scope_of(op)
                               for op in table.values())


cells = pytest.mark.parametrize("kind,remat", [
    (k, r) for k in KINDS for r in (0, 1)])


@cells
def test_the_table_covers_the_step(stepped, kind, remat):
    """Of the compiled step's instructions that are not parameters,
    constants or tuples, four in five have a part: a word of PARTS or a
    layer's type. By count, at this size, on this compiler: the rest is
    the step's own arithmetic outside any layer (the rng's split: a
    hundred instructions, as many as a tiny block) and what XLA's CPU
    passes make without metadata (``reduce-window`` rewrites, copies).
    Whatever autodiff made lies in a layer: none of it goes without."""
    table, text = stepped(kind, remat)
    real = [n for n, op in INSTRUCTION.findall(text)
            if op not in NOT_OPERATIONS]
    assert len(real) > 1000
    covered = [n for n in real
               if n in table and obs_trace.scope_of(table[n])[0]]
    assert len(covered) >= 0.80 * len(real), (len(covered), len(real))
    lost = {op for op in table.values() if re.search(r"jvp\(\w", op)
            and obs_trace.scope_of(op)[0] is None}
    assert not lost, sorted(lost)[:5]


@cells
def test_each_part_is_in_each_phase_it_should_have(stepped, kind, remat):
    table, _ = stepped(kind, remat)
    _, parts, layers = KINDS[kind]
    seen = _by_scope(table)
    for part in parts:
        for phase in ("fwd", "bwd"):
            assert seen[part, phase] > 0, (part, phase)
    for layer in layers:
        assert seen[layer, "fwd"] > 0, layer
    assert seen["opt", "opt"] > 0
    assert {phase for (part, phase) in seen if part == "opt"} == {"opt"}
    # the head replays its chunks' logits under a jax.checkpoint of its
    # own whatever the stack does; a stack only under remat = 1, and
    # then its projections, norms, router and dispatch
    replayed = {part for (part, phase) in seen if phase == "replay"}
    if remat:
        assert replayed >= {"lm_head", "attn_proj", "norm"} | (
            {"router", "moe_dispatch"} & set(parts)), replayed
    else:
        assert replayed == {"lm_head"}
    assert {phase for (_, phase) in seen} <= set(obs_trace.PHASES)


def test_mtp_blocks_parts_read_the_same_words_under_mtp(stepped):
    table, _ = stepped("mla_mtp", 0)
    under = {obs_trace.scope_of(op)[0] for op in table.values()
             if "jvp(mtp)" in op}
    assert under >= {"mtp", "attn_proj", "attn_prep", "attn_core", "norm",
                     "mlp", "router", "moe_dispatch", "moe_experts"}


def test_scope_of_on_the_strings_jax_writes():
    """The literal ``op_name``s of jax 0.9 for a scanned, checkpointed
    block under ``value_and_grad``, and the update's."""
    stack = "jit(step)/%s/while/body/closed_call/%smlp/dot_general"
    assert obs_trace.scope_of(stack % ("jvp(stack)", "")) == ("mlp", "fwd")
    assert obs_trace.scope_of(stack % (
        "transpose(jvp(stack))", "checkpoint/")) == ("mlp", "bwd")
    assert obs_trace.scope_of(stack % (
        "transpose(jvp(stack))", "checkpoint/rematted_computation/")) \
        == ("mlp", "replay")
    assert obs_trace.scope_of("jit(step)/opt/mul") == ("opt", "opt")
    # the innermost word of PARTS decides; a kernel's own scope is none
    assert obs_trace.scope_of(
        "jit(train_step)/transpose(jvp(transformer_stack))/moe_dispatch/"
        "while/body/moe_experts/jit(_kernel)/moe_tgmm/pallas_call") \
        == ("moe_experts", "bwd")
    # no word of PARTS: the layer's type (the layers are loaded here)
    assert obs_trace.scope_of(
        "jit(train_step)/jvp(lm_head)/while/body/dot_general") \
        == ("lm_head", "fwd")
    # a jitted function's name and the primitive at the end are no
    # scopes, whatever layer type they spell
    assert obs_trace.scope_of("jit(loss)/jvp()/jit(relu)/max") \
        == (None, "fwd")
    assert obs_trace.scope_of(
        "jit(loss)/transpose(jvp())/checkpoint/split") == (None, "bwd")
    # in no scope at all: the step's own arithmetic
    assert obs_trace.scope_of("jit(train_step)/add") == (None, "other")
    assert set(obs_trace.PARTS) & set(L._REGISTRY) == set()
    assert all(re.fullmatch(r"[a-z_]+", p) for p in obs_trace.PARTS)


@pytest.mark.parametrize("kind", list(KINDS))
def test_scopes_are_metadata_only(kind, monkeypatch):
    """The lowered loss-and-gradient, debug info off, is the same text
    with every ``named_scope`` of the program a null context."""
    def lowered():
        from cxxnet_tpu.trainer import _strip_nones
        tr, _ = _trainer(kind, 1)
        net = tr.net
        params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
        labels = [jax.ShapeDtypeStruct((ROWS, 1), jnp.float32)] \
            * tr.net_cfg.label_name_map["label"] \
            + [jax.ShapeDtypeStruct((ROWS, SEQ), jnp.float32)]

        def step(params, state, data, labels, rng, epoch):
            loss, grads = jax.value_and_grad(net.loss_fn)(
                params, data, labels, rng, epoch)
            return loss, tr.opt.apply(params, _strip_nones(grads), state,
                                      epoch)
        return jax.jit(step).lower(
            params, jax.eval_shape(tr.opt.init_state, params),
            jax.ShapeDtypeStruct((ROWS, 1, SEQ, 1), jnp.float32), labels,
            jax.eval_shape(lambda: jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((), jnp.int32))
    with_scopes = lowered()
    named = with_scopes.as_text(debug_info=True)
    assert all(word in named for word in ("attn_proj", "opt", "lm_head"))

    class Null(contextlib.ContextDecorator):
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax, "named_scope", Null)
    without = lowered()
    names = set(re.findall(r'"(jit\([^"]*)"',
                           without.as_text(debug_info=True)))
    assert names and not any(obs_trace.scope_of(n)[0] for n in names)
    assert with_scopes.as_text() == without.as_text()


def _live_bytes():
    gc.collect()
    return sum(x.nbytes for x in jax.live_arrays())


def test_the_note_keeps_no_device_memory(monkeypatch):
    """After the driver's ``trainer.params = trainer.opt_state = None;
    del trainer`` the note holds the jitted step, the shapes and, through
    the step's closure, the trainer's host object: of ``jax.live_arrays``
    the scalars that object keeps (its rng, its epoch, the last loss:
    16 bytes here) and not one leaf of the weights or the moments."""
    def left(noted):
        if not noted:
            monkeypatch.setattr(obs_trace, "note_program",
                                lambda *a: None)
        before = _live_bytes()
        tr, cfg = _trainer("plain", 0)
        tr.update(_batch(cfg))
        jax.block_until_ready(tr.params)
        held = _live_bytes() - before
        tr.params = tr.opt_state = None
        del tr
        return held, _live_bytes() - before
    obs_trace._programs.clear()
    held, with_note = left(True)
    fn, specs = obs_trace._programs["train_step"]
    assert all(isinstance(s, jax.ShapeDtypeStruct)
               for s in jax.tree.leaves(specs))
    obs_trace._programs.clear()
    assert held > 1 << 20
    assert left(False) == (held, 0) and 0 <= with_note <= 64
    assert "train_step" not in obs_trace._programs


def test_the_newest_note_of_a_name_replaces_the_older():
    f = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    g = jax.jit(lambda x: jnp.exp(x) + 1.0)
    spec = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    obs_trace.note_program("unit_step", f, spec)
    first = obs_trace.device_scopes()["unit_step"]
    assert any("tanh" in op for op in first.values())
    assert obs_trace.device_scopes()["unit_step"] is first     # kept
    obs_trace.note_program("unit_step", g, spec)
    second = obs_trace.device_scopes()["unit_step"]
    assert any("exp" in op for op in second.values())
    assert not any("tanh" in op for op in second.values())
    del obs_trace._programs["unit_step"], obs_trace._scopes["unit_step"]


def test_nested_traces_cannot_push_set_ups_events_out():
    """M10: 10,000 traces nested in one another after set-up (one
    lowering of a train step fires as many) leave set-up's compile
    events readable."""
    import time
    jax.jit(lambda x: x + 1.0)(jnp.ones(3))     # the listener is on
    t = time.perf_counter() + 1.0   # (after everything logged so far)
    init, step, asked = (("unit.init", None), ("unit.update", 1),
                         ("unit.device_scopes", None))
    setup = [("trace", 0.5, t + 0.5, init), ("lower", 0.2, t + 0.7, init),
             ("backend", 2.0, t + 2.7, step),
             ("cache_read", 0.1, t + 2.6, step)]
    event = {v: k for k, v in obs_trace.COMPILE_PHASES.items()}
    real = time.perf_counter
    try:
        for what, secs, t_end, cause in setup:
            obs_trace._tls.phase = cause
            time.perf_counter = lambda t_end=t_end: t_end
            obs_trace._on_compile(event[what], secs)
        obs_trace._tls.phase = asked
        for i in range(10000):      # each ends before its container
            time.perf_counter = lambda i=i: t + 10.0 + i * 1e-3
            obs_trace._on_compile(event["trace"], 2e-4 if i % 50 else 5e-3)
        time.perf_counter = lambda: t + 21.0
        obs_trace._on_compile(event["trace"], 11.5)
        got = [e for e in obs_trace.compile_events() if e[2] >= t]
    finally:
        time.perf_counter = real
        obs_trace._tls.phase = None
        # (events dated ahead of the clock would be a later test's)
        for log in (obs_trace._compile_log, obs_trace._short_traces):
            kept = [e for e in log if e[3] not in (init, step, asked)]
            log.clear()
            log.extend(kept)
    assert [e for e in got if e[3] != asked] \
        == sorted(setup, key=lambda e: e[2])
    # of the nested ones the outermost alone is kept
    assert [e for e in got if e[3] == asked] \
        == [("trace", 11.5, t + 21.0, asked)]


def test_a_cache_entry_older_than_the_scopes_does_not_answer(tmp_path):
    """JAX keys its persistent cache without the metadata: a program
    that differs from a cached one by its scopes alone is handed the old
    executable, old names and all. ``device_scopes`` compiles under a
    key that holds the metadata, and the next asker reads that entry."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    was = {k: getattr(jax.config, k) for k in keys}

    def step(scoped):
        def f(w, x):
            with jax.named_scope("mlp") if scoped \
                    else contextlib.nullcontext():
                return jnp.tanh(x @ w).sum()
        return jax.jit(jax.grad(f))
    spec = (jax.ShapeDtypeStruct((16, 16), jnp.float32),) * 2

    def parts(compiled_text):
        return {obs_trace.scope_of(op)[0] for op in re.findall(
            r'op_name="([^"]*)"', compiled_text)}

    def reads():
        return sum(e[0] == "cache_read"
                   for e in obs_trace.compile_events())
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        step(False).lower(*spec).compile()          # the older program
        before = reads()
        # what jax alone does: the old entry answers, without a name
        assert parts(step(True).lower(*spec).compile().as_text()) == {None}
        assert reads() == before + 1
        tables = []
        for _ in range(2):  # (one line asks: the key holds the callers')
            obs_trace.note_program("unit_stale", step(True), spec)
            tables.append((obs_trace.device_scopes()["unit_stale"],
                           reads() - before))
        # compiled the first time, its own entry read back the second
        assert [n for _, n in tables] == [1, 2]
        assert tables[0][0] == tables[1][0]
        assert {obs_trace.scope_of(op) for op in tables[0][0].values()} \
            >= {("mlp", "fwd"), ("mlp", "bwd")}
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        obs_trace._programs.pop("unit_stale", None)
        obs_trace._scopes.pop("unit_stale", None)

"""Window driver of the training mixes (``"kind": "train"``).

Drives what ``python -m cxxnet_tpu <conf>`` drives in a round of its
train task: ``cli.LearnTask`` builds the trainer from the
configuration's conf text, ``io.ArrayIterator`` batches and shuffles the
rows, ``io.prefetch.DevicePrefetchIterator`` stages them ahead on its
own thread, ``Trainer.update`` dispatches the one compiled step. Tokens
and initial weights are made here from ``--seed``; no checkpoint is
written in the window.

Set-up builds that one object and drives it through the mix's
``check_steps`` first steps, through the same ``step()`` the window
calls, keeping each loss, the first gradient as the optimizer got it
(its first moment after one step, over 1 - beta1) and the weights'
change after the last of them. The window then runs on the same object
for ``--seconds``: at most ``steps_in_flight`` steps are dispatched
ahead of the one whose loss has been waited for, and the window ends
when the last dispatched step has ended. Afterwards the peak memory is
read, the program's state is freed, and the plain reference follows the
same steps from the same seed for ``compare.py`` to judge.
"""

import collections
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402
from harness import device_line, load_module  # noqa: E402


def conf_text(config, mix):
    """The configuration's conf with the mix's shapes appended."""
    s = mix["seq_len"]
    return "\n".join(list(config["program"]["conf"]) + [
        "input_shape = 1,%d,1" % s,
        "label_vec[0,%d) = label" % s,
        "batch_size = %d" % mix["rows_per_step"],
        "device_prefetch_depth = %d" % mix["prefetch_depth"]]) + "\n"


def build_task(config, mix, seed):
    """``cli.LearnTask`` initialised as ``cli.main`` would for a train
    task with no iterator section (the harness supplies the rows)."""
    from cxxnet_tpu import cli
    from cxxnet_tpu import config as conf_parser
    task = cli.LearnTask()
    for k, v in conf_parser.parse_string(conf_text(config, mix)):
        task.set_param(k, v)
    # the trainer's own seed only draws the weights replaced below
    task.set_param("seed", str(int(seed) % 65521))
    task.init()
    return task


def leaf_slots(trainer, layout):
    """{reference leaf: (layer index, tag)} by the program's layer types."""
    by_type = {info.type: li
               for li, info in enumerate(trainer.net_cfg.layers)}
    return {leaf: (by_type[ltype], tag)
            for leaf, (ltype, tag) in layout.items()}


def place_weights(trainer, ref, sizes, seq_len, seed, slots):
    """Initial weights from the seed, made on the device in one jitted
    call in the float32 the trainer keeps, in the trainer's own tree."""
    import jax
    shape_of = jax.tree.map(lambda x: x.shape, trainer.params)
    trainer.params = None
    gc.collect()

    def make(words):
        w = ref.init_weights(sizes, seq_len, words)
        tree = [None if s is None else {} for s in shape_of]
        for leaf, (li, tag) in slots.items():
            tree[li][tag] = w[leaf]
        return tree
    params = jax.jit(make)(ref.seed_words(seed))
    got = jax.tree.map(lambda x: x.shape, params)
    if got != shape_of:
        raise RuntimeError("the reference's weights do not fill the "
                           "program's tree: %s vs %s" % (got, shape_of))
    trainer.params = params


def first_gradient_norms(trainer, ref, slots, beta1):
    """Norm by leaf of the first gradient as the optimizer got it: Adam's
    first moment after one step from nought is (1 - beta1) times it."""
    import jax
    fn = jax.jit(lambda st: {leaf: ref.leaf_norm(leaf, st[li][tag]["m1"])
                             / (1.0 - beta1)
                             for leaf, (li, tag) in slots.items()})
    return ref.split_norms(jax.device_get(fn(trainer.opt_state)))


def change_norms(trainer, ref, slots, sizes, seq_len, seed):
    """Norm by leaf of weights now minus weights at the start, the start
    made again from the seed one leaf at a time (never a second copy of
    the model)."""
    import jax
    out = {}
    for leaf, (li, tag) in slots.items():
        fn = jax.jit(lambda p, words, leaf=leaf: ref.leaf_norm(
            leaf, p - ref.init_leaf(sizes, seq_len, words, leaf)))
        out[leaf] = jax.device_get(fn(trainer.params[li][tag],
                                      ref.seed_words(seed)))
    return ref.split_norms(out)


def note(ctx, what):
    """One line a set-up phase on standard error: seconds since the
    process began."""
    print("benchmark: %7.1f s  %s" % (time.perf_counter() - ctx["t_start"],
                                      what), file=sys.stderr, flush=True)


class CompileCounter:
    """Backend compilations JAX reports, so that none goes unseen inside
    the window."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            self.n += 1


def run(ctx):
    import jax
    from jax.profiler import TraceAnnotation

    from cxxnet_tpu.io import ArrayIterator
    from cxxnet_tpu.io.prefetch import DevicePrefetchIterator

    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    sizes, s = config["sizes"], mix["seq_len"]
    if s > sizes["n_positions"]:
        raise SystemExit("seq_len %d exceeds n_positions %d"
                         % (s, sizes["n_positions"]))
    rows = mix["rows_per_step"]
    ref = load_module(os.path.join(BENCH, "reference",
                                   config["reference"] + ".py"))
    compiles = CompileCounter()
    corpus = traffic_gen.train_corpus(mix, sizes["vocab_size"], seed)

    note(ctx, "corpus made")
    task = build_task(config, mix, seed)
    trainer = task.trainer
    slots = leaf_slots(trainer, ref.LAYOUT)
    place_weights(trainer, ref, sizes, s, seed, slots)
    note(ctx, "trainer built, weights placed")

    class Rows(ArrayIterator):
        """The program's batching and shuffling, which ends its round
        when the harness is done (so that the feed's thread ends)."""
        stop = False

        def next(self):
            return not self.stop and super().next()

    source = Rows(corpus[:, :s].reshape(-1, 1, s, 1).astype(np.float32),
                  corpus[:, 1:].astype(np.float32), rows,
                  shuffle=bool(mix["shuffle"]), round_batch=False,
                  seed=int(seed) % (2 ** 32 - 5))
    feed = DevicePrefetchIterator(source, trainer,
                                  depth=mix["prefetch_depth"])
    state = {"round": 1, "stall_s": 0.0}
    trainer.start_round(1)
    feed.before_first()

    def step():
        """One optimizer step as the train task's round loop makes it;
        -> (the step's loss on the device, the rows it read)."""
        t0 = time.perf_counter()
        with TraceAnnotation("bench.feed"):
            has = feed.next()
            if not has:                      # the round's rows are used up
                state["round"] += 1
                trainer.start_round(state["round"])
                feed.before_first()
                if not feed.next():
                    raise RuntimeError("the feed gave no batch")
        state["stall_s"] += time.perf_counter() - t0
        item = feed.value
        with TraceAnnotation("bench.dispatch"):
            trainer.update(item)
        return trainer.last_loss, item.host.inst_index

    # ------------------------------------------------------------------
    # the first steps, through the window's own call, with their readings
    observed = {"losses": []}
    seen_rows = []
    beta1 = config["optimizer"]["beta1"]
    for i in range(mix["check_steps"]):
        loss, idx = step()
        seen_rows.append(np.array(idx))
        observed["losses"].append(loss)
        if i == 0:
            observed["grad_norms"] = first_gradient_norms(
                trainer, ref, slots, beta1)
            note(ctx, "first step done (%d compilations so far)"
                 % compiles.n)
    observed["change_norms"] = change_norms(trainer, ref, slots, sizes, s,
                                            seed)
    observed["losses"] = [float(x) for x in observed["losses"]]
    jax.block_until_ready(trainer.params)

    # ------------------------------------------------------------------
    # the window
    depth = mix["steps_in_flight"]

    def window(seconds, span):
        """Dispatch steps until ``seconds`` have passed, wait for the
        last; -> (steps, seconds from start to the last step's end, the
        times at which each waited-for step had ended, losses)."""
        flight, ended, losses = collections.deque(), [], []
        state["stall_s"] = 0.0
        with TraceAnnotation(span):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                loss, _ = step()
                flight.append(loss)
                losses.append(loss)
                if len(flight) > depth:
                    with TraceAnnotation("bench.wait_step"):
                        jax.block_until_ready(flight.popleft())
                    ended.append(time.perf_counter())
            with TraceAnnotation("bench.wait_last"):
                while flight:
                    jax.block_until_ready(flight.popleft())
                    ended.append(time.perf_counter())
            return len(losses), time.perf_counter() - t0, ended, losses

    # everything set-up allocated (the tracer's leftovers are millions of
    # objects) is taken out of the collector's reach, so that no full
    # collection stops the dispatch loop inside the window
    gc.collect()
    gc.freeze()
    compiles_before = compiles.n
    setup_s = time.perf_counter() - ctx["t_start"]
    note(ctx, "set-up done, window starts")
    traced = None
    seconds = ctx["seconds"]
    if ctx["trace"]:
        # a short traced window of its own, then the rest untraced: the
        # device's numbers come from the first, the host's from the second
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        t_traced = min(float(mix["trace_seconds"]), seconds / 2.0)
        try:
            jax.profiler.start_trace(trace_dir)
            n_t, w_t, _, losses_t = window(t_traced, "bench.window")
            jax.profiler.stop_trace()
            traced = trace_reduce.reduce(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        traced["steps"] = n_t
        seconds -= t_traced
    else:
        losses_t = []
    steps, window_s, ended, losses = window(seconds, "bench.window")
    stall_s = state["stall_s"]
    compiles_in_window = compiles.n - compiles_before
    all_losses = np.asarray(jax.device_get(losses_t + losses), np.float64)
    failed = int((~np.isfinite(all_losses)).sum())
    chips = len({d for x in jax.tree.leaves(trainer.params)
                 for d in x.devices()})
    device = device_line(jax.devices())

    # ------------------------------------------------------------------
    # free the program, then the reference
    source.stop = True
    while feed.next():
        pass
    task._stager.shutdown()
    trainer.params = trainer.opt_state = None
    del task, trainer, feed, source, losses, losses_t
    gc.collect()

    gaps = sorted(b - a for a, b in zip(ended, ended[1:]))
    shape = {"steps": steps, "seconds": window_s, "feed_wait_s": stall_s,
             "step_gap_ms_p50": 1e3 * gaps[len(gaps) // 2] if gaps else None,
             "step_gap_ms_max": 1e3 * gaps[-1] if gaps else None}
    note(ctx, "window done, program freed: %s" % shape)
    batches = [(corpus[idx, :s], corpus[idx, 1:]) for idx in seen_rows]
    t_ref = time.perf_counter()
    reference = ref.follow(config, s, seed, batches,
                           rows_per_block=mix["reference_rows_per_block"])
    reference_s = time.perf_counter() - t_ref
    note(ctx, "reference followed %d steps in %.1f s"
         % (len(batches), reference_s))
    numbers = compare.train_numbers(observed, reference)
    numbers["compiles_in_window"] = (float(compiles_in_window),
                                     "backend compilations in the window")
    correct, compared = compare.judge(numbers, ctx["limits"])
    correct = correct and failed == 0 and steps > 0 \
        and bool(np.isfinite(observed["losses"]).all())

    tokens_per_step = rows * s
    tok_s = steps * tokens_per_step / window_s
    readings = {
        "kind": "train", "config": config, "mix": mix,
        "device_kind": device["kind"], "platform": device["platform"],
        "chips": chips, "steps": steps, "window_s": window_s,
        "tokens_per_step": tokens_per_step, "tok_s": tok_s,
        "stall_s": stall_s, "step_ended_s": ended, "trace": traced,
    }
    out = {
        "correct": correct, "attempted": steps + (traced or {}).get(
            "steps", 0), "failed": failed,
        "end_to_end": {"train_tok_s": tok_s, "setup_s": setup_s},
        "readings": readings, "device": device, "window": shape,
        "compared": compared,
    }
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = {"device_ops": traced["device_ops"],
                            "idle_gaps": traced["idle_gaps"]}
    return out

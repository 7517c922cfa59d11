"""CPU rehearsal of ``chip_smoke.py``'s control flow: every phase at a
tiny width, with the platform check relaxed HERE (monkeypatch — the
program has no option for it), and the shape of every line it prints.
What only the chip can say (the compiled Pallas kernels, times, the
compile cache) is not judged here."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TINY_CONF = """
data = train
iter = synth
    shape = 1,384,1
    token_vocab = 64
    lm_labels = 1
    ninst = 64
    shuffle = 1
iter = end

netconfig=start
layer[0->1] = embed:emb
  vocab_size = 64
  nhidden = 64
  learn_pos = 1
layer[1->2] = transformer_stack:ts1
  nlayer = 1
  nhead = 2
  causal = 1
  random_type = xavier
layer[2->3] = lm_head:lm_head
  nhidden = 64
  init_sigma = 0.02
netconfig=end
input_shape = 1,384,1
label_vec[0,384) = label

dev = tpu
batch_size = 8
updater = adam
eta = 0.001
metric = token_error
eval_train = 1
num_round = 10
max_round = 10
model_dir = models
"""

PHASE_KEYS = {"phase", "wall_s", "compile_s", "run_s", "cache_hits",
              "cache_misses", "cache_entries", "peak_device_bytes"}


@pytest.fixture
def tiny_smoke(tmp_path, monkeypatch):
    import chip_smoke
    conf = tmp_path / "tiny_lm.conf"
    conf.write_text(TINY_CONF)
    monkeypatch.setattr(chip_smoke, "CONF", str(conf))
    monkeypatch.setattr(chip_smoke, "_require_tpu", lambda chips: None)
    monkeypatch.setattr(chip_smoke, "TRAIN", {"ninst": 16, "rounds": 1})
    monkeypatch.setattr(chip_smoke, "EXPORT", {
        "max_new": 8, "prompt_len": 300, "batch": 4, "rows": "2",
        "widths": "192,320"})
    monkeypatch.setattr(chip_smoke, "TRAFFIC", {
        "seed": 0, "shared_prefix": 128, "tails": (20, 40),
        "lens": (130, 300), "concurrent": 4, "max_new": (4, 8)})
    monkeypatch.setattr(chip_smoke, "KERNELS", {
        "flash": (2, 2, 128, 64),
        "paged": {"B": 2, "nh": 2, "d": 64, "page": 128, "layers": 2,
                  "seqs": 2, "attend": 200},
        "decode": (8, 2, 256, 64)})
    return chip_smoke


def test_rehearsal_runs_every_phase_and_prints_json(tiny_smoke, capsys):
    rc = tiny_smoke.run(chips=1)
    out = capsys.readouterr().out
    lines = [json.loads(l) for l in out.splitlines()]   # JSON, all of it
    assert rc == 0, lines[-1]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}
    head = lines[0]
    assert head["compile_cache_dir"].endswith(".jax-cache") \
        or head["from_env"]
    assert {"entries", "jax", "chips", "device_kind"} <= set(head)
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == ["kernels", "train", "resume", "export",
                            "serve"]
    for l in phases.values():
        assert PHASE_KEYS <= set(l), l
        assert abs(l["compile_s"] + l["run_s"] - l["wall_s"]) < 0.01

    kern = {k["kernel"]: k for k in phases["kernels"]["kernels"]}
    assert set(kern) == {"flash_attention", "flash_attention_flat",
                         "paged_attend", "decode_attend"}
    for k in kern.values():
        assert k["ok"] and set(k["max_abs_err"]) == set(k["tolerance"])
    assert set(kern["flash_attention"]["max_abs_err"]) == {
        "out", "dq", "dk", "dv"}

    tr = phases["train"]
    assert len(tr["loss_per_step"]) == 2          # 16 inst / batch 8
    assert tr["checkpoints"] == ["0000.model", "0001.model"]
    # on a CPU flash resolves to the XLA attend: nothing recorded
    assert tr["train_kernels"] == [] and tr["platform"] == "cpu"
    assert len(phases["resume"]["loss_per_step"]) == 2
    assert phases["resume"]["checkpoints"][-1] == "0002.model"

    ex = phases["export"]
    assert ex["model_in"] == "0002.model" and ex["artifact_bytes"] > 0
    kinds = [p["kind"] for p in ex["programs"]]
    # tails of at most 300 - 128 tokens need the 192 bucket only
    assert kinds == ["prefill", "prefill", "step", "tail_prefill"]
    assert all(p["attend_impl"] == ["xla"] for p in ex["programs"]
               if p["kind"] == "prefill")
    assert ex["step_attend"] == [{"kv_dtype": "native",
                                  "attend_kernel": "fused-paged",
                                  "attend_impl": "xla"}]

    sv = phases["serve"]
    assert sv["requests"] == 5
    assert sv["tokens_returned"] == sum(sv["max_new"])
    assert sv["prompt_lens"][:3] == [148, 168, 300]
    assert sv["prefix_hits"] >= 1 and sv["tail_prefills"] >= 1
    # the longest prompt is past the tail buckets: the prefill program
    assert sv["prefill_dispatches"] > sv["tail_prefills"]
    assert sv["steady_state_compiles"] == 0
    assert sv["warmup_compiles"] > 0
    assert sv["reference_agreement"] == 1.0


def test_rehearsal_of_the_four_chip_option(tiny_smoke, monkeypatch,
                                           capsys):
    """``--chips 4`` runs only the cross-chip paths and their twins
    (here over the suite's virtual host devices): the data-parallel
    step against one device, the mesh artifact against the
    single-device one, and where replicas land."""
    monkeypatch.setattr(tiny_smoke, "EXPORT4", {
        "max_new": 8, "prompt_len": 128, "batch": 8, "rows": "4",
        "widths": "128"})
    monkeypatch.setattr(tiny_smoke, "EXPORT4_SINGLE", dict(
        tiny_smoke.EXPORT4, batch=2, rows="1"))
    rc = tiny_smoke.run(chips=4)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rc == 0, lines[-1]
    assert lines[-1]["ok"] is True and lines[0]["chips"] == 4
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == [
        "train_data4", "train_one_chip", "export_single",
        "export_mesh4", "serve_mesh4_vs_single", "replica_placement"]
    for l in phases.values():
        assert PHASE_KEYS <= set(l), l
    dp = phases["train_data4"]
    assert dp["mesh"] == {"data": 8}        # the suite's 8 host devices
    for what in ("parameters", "optimizer_state", "batch"):
        assert len(dp["devices_holding"][what]) == 8
    one = phases["train_one_chip"]
    assert len(one["loss_per_step"]) == len(dp["loss_per_step"]) == 2
    assert one["max_abs_loss_diff_vs_data4"] <= one["tolerance"]
    assert phases["export_mesh4"]["mesh"]["shape"] == [4]
    assert phases["export_single"]["mesh"] is None
    sv = phases["serve_mesh4_vs_single"]
    assert sv["mesh4_kv_pool_devices"] == [0, 1, 2, 3]
    assert sv["single_kv_pool_devices"] == [0]
    assert sv["agreement"] == 1.0 and sv["bitwise"] is True
    assert sv["agreement_token0_token1"] == [1.0, 1.0]
    assert sv["tokens_compared"] == 64
    # serve/replica.py places nothing: both replicas on the default device
    assert phases["replica_placement"]["replicas_land_on"] == {
        "r1": [0], "r2": [0]}


def test_refuses_a_cpu_by_itself(capsys):
    """Without the test's relaxation the script never completes on a
    CPU: non-zero exit, ``"ok": false``, a message naming the platform
    it found."""
    import chip_smoke
    with pytest.raises(SystemExit) as ei:
        chip_smoke.run(chips=1)
    assert ei.value.code == 2
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is False and "'cpu'" in last["error"]
    assert last["device"]["platform"] == "cpu"

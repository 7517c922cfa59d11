"""CLI + checkpoint/resume/finetune tests (reference: src/cxxnet_main.cpp)."""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONF = """
data = train
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 512
    shuffle = 1
iter = end
eval = test
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 128
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,16
batch_size = 64
dev = cpu
save_model = 1
num_round = 5
max_round = 5
eta = 0.5
momentum = 0.9
metric = error
"""


def run_cli(tmp_path, conf_text, *overrides, check=True, spawn=False):
    """Drive the CLI. In-process by default (same argv contract, but a
    fresh subprocess costs ~5s of jax import + recompiles on this
    1-core host — across this file that was ~1 min of suite budget);
    ``spawn=True`` keeps one true `python -m cxxnet_tpu` smoke path."""
    conf = tmp_path / "test.conf"
    conf.write_text(conf_text)
    if spawn:
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-m", "cxxnet_tpu", str(conf), *overrides],
            capture_output=True, text=True, cwd=str(tmp_path), check=False,
            env=env, timeout=600)
        if check and proc.returncode != 0:
            raise AssertionError("CLI failed:\n%s\n%s"
                                 % (proc.stdout, proc.stderr))
        return proc
    import contextlib
    import io as _io
    from types import SimpleNamespace
    from cxxnet_tpu.cli import main
    out, errbuf = _io.StringIO(), _io.StringIO()
    cwd = os.getcwd()
    os.chdir(str(tmp_path))
    rc = 1
    try:
        with contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(errbuf):
            try:
                rc = main([str(conf), *overrides])
            except Exception:
                if check:
                    raise
                import traceback
                traceback.print_exc(file=errbuf)
    finally:
        os.chdir(cwd)
    if check and rc != 0:
        raise AssertionError("CLI failed:\n%s\n%s"
                             % (out.getvalue(), errbuf.getvalue()))
    return SimpleNamespace(returncode=rc, stdout=out.getvalue(),
                           stderr=errbuf.getvalue())


def test_cli_train_and_checkpoints(tmp_path):
    # the one true `python -m cxxnet_tpu` subprocess smoke test
    proc = run_cli(tmp_path, CONF, spawn=True)
    # per-round eval lines on stderr, reference format
    lines = [l for l in proc.stderr.splitlines() if l.startswith("[")]
    assert len(lines) == 5
    assert "train-error:" in lines[0] and "test-error:" in lines[0]
    err_first = float(lines[0].rsplit(":", 1)[1])
    err_last = float(lines[-1].rsplit(":", 1)[1])
    assert err_last < err_first and err_last < 0.3, proc.stderr
    # model files: initial 0000 + one per round (save_model=1)
    models = sorted(os.listdir(tmp_path / "models"))
    assert models == ["%04d.model" % i for i in range(6)]


def test_cli_continue_training(tmp_path):
    run_cli(tmp_path, CONF)
    proc = run_cli(tmp_path, CONF, "continue=1", "num_round=7", "max_round=7")
    assert "Continue training from round 5" in proc.stdout
    models = sorted(os.listdir(tmp_path / "models"))
    assert "0007.model" in models


def test_cli_save_period_cadence(tmp_path):
    """save_model=2 writes only even-cadence files (reference checks the
    incremented counter, cxxnet_main.cpp:175-176)."""
    proc = run_cli(tmp_path, CONF, "save_model=2")
    models = sorted(os.listdir(tmp_path / "models"))
    assert models == ["0001.model", "0003.model", "0005.model"]


def test_cli_predict(tmp_path):
    run_cli(tmp_path, CONF)
    pred_conf = CONF + """
pred = pred.txt
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 100
iter = end
"""
    run_cli(tmp_path, pred_conf, "task=pred",
            "model_in=models/0005.model")
    preds = (tmp_path / "pred.txt").read_text().strip().splitlines()
    assert len(preds) == 100  # padding rows trimmed
    assert set(float(p) for p in preds).issubset({0.0, 1.0, 2.0, 3.0})


def test_cli_extract(tmp_path):
    run_cli(tmp_path, CONF)
    ext_conf = CONF + """
pred = feat.txt
iter = synth
    shape = 1,1,16
    nclass = 4
    ninst = 64
iter = end
"""
    run_cli(tmp_path, ext_conf, "task=extract",
            "model_in=models/0005.model", "extract_node_name=sg1")
    rows = (tmp_path / "feat.txt").read_text().strip().splitlines()
    assert len(rows) == 64
    assert len(rows[0].split()) == 32
    meta = (tmp_path / "feat.txt.meta").read_text().strip()
    assert meta == "64,1,1,32"


def test_cli_finetune(tmp_path):
    run_cli(tmp_path, CONF)
    # finetune a net reusing fc1 (same name) with a new head size
    ft_conf = CONF.replace("nhidden = 4", "nhidden = 8") \
                  .replace("fullc:fc2", "fullc:fc2_new")
    proc = run_cli(tmp_path, ft_conf, "task=finetune",
                   "model_in=models/0005.model", "model_dir=ft_models")
    assert "Copying layer fc1" in proc.stdout
    assert "Copying layer fc2" not in proc.stdout.replace("fc2_new", "XX")
    # finetune restarts the round counter at 0 (the reference only infers
    # start_counter from the model filename in LoadModel, not CopyModel)
    assert os.path.exists(tmp_path / "ft_models" / "0004.model")


def test_cli_test_io(tmp_path):
    proc = run_cli(tmp_path, CONF, "test_io=1")
    assert "start I/O test" in proc.stdout
    # no training -> no eval lines
    assert not any(l.startswith("[") for l in proc.stderr.splitlines())


def test_checkpoint_roundtrip(tmp_path):
    from cxxnet_tpu import checkpoint, config as cfgmod
    from cxxnet_tpu.graph import NetConfig
    import numpy as np
    net = NetConfig()
    net.configure(cfgmod.parse_string(
        "netconfig=start\nlayer[+1:f] = fullc:f\n nhidden = 3\n"
        "netconfig=end\ninput_shape = 1,1,4\n"))
    params = [{"wmat": np.ones((3, 4)), "bias": np.zeros(3)}]
    opt = [{"wmat": {"m": np.full((3, 4), 0.5)},
            "bias": {"m": np.zeros(3)}}]
    p = str(tmp_path / "x.model")
    checkpoint.save_model(p, net, 42, params, opt)
    cfg2, epoch, p2, o2, _ = checkpoint.load_model(p)
    assert epoch == 42
    assert cfg2.node_names == net.node_names
    np.testing.assert_allclose(p2[0]["wmat"], 1.0)
    np.testing.assert_allclose(o2[0]["wmat"]["m"], 0.5)

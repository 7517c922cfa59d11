"""The examples/ directory is part of the product: every config must
parse and graph-build, and the synthetic ones must train via the CLI."""
import glob
import os

import pytest

from cxxnet_tpu import config
from cxxnet_tpu.graph import NetConfig
from cxxnet_tpu.model import Network

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFS = sorted(glob.glob(os.path.join(REPO, "examples", "*", "*.conf")))


def test_examples_exist():
    assert len(CONFS) >= 6


@pytest.mark.parametrize("conf", CONFS, ids=[os.path.basename(c) for c in CONFS])
def test_example_config_builds(conf):
    entries = config.parse_file(conf)
    net = NetConfig()
    net.configure(entries)
    assert net.num_layers > 0
    # shape inference over the declared input proves the net is coherent
    Network(net, batch_size=4)


def test_synthetic_mlp_trains_via_cli(capsys, tmp_path, monkeypatch):
    from cxxnet_tpu.cli import main
    monkeypatch.chdir(tmp_path)
    rc = main([os.path.join(REPO, "examples", "synthetic", "mlp.conf"),
               "num_round=2", "dev=cpu", "batch_size=64", "silent=0"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "test-error:" in err


def test_tools_im2bin_roundtrip(tmp_path):
    import subprocess
    import sys

    import numpy as np
    from cxxnet_tpu.io.binpage import iter_packfile

    img_dir = tmp_path / "imgs"
    img_dir.mkdir()
    blobs = []
    lst = tmp_path / "train.lst"
    with open(lst, "w") as f:
        for i in range(5):
            blob = np.random.RandomState(i).bytes(100 + 37 * i)
            (img_dir / ("img%d.jpg" % i)).write_bytes(blob)
            blobs.append(blob)
            f.write("%d\t%d\timg%d.jpg\n" % (i, i % 2, i))
    out = tmp_path / "train.bin"
    rc = subprocess.call(
        [sys.executable, os.path.join(REPO, "tools", "im2bin.py"),
         str(lst), str(img_dir) + os.sep, str(out)])
    assert rc == 0
    unpacked = list(iter_packfile(str(out)))
    assert unpacked == blobs


def test_tools_partition_maker(tmp_path):
    import subprocess
    import sys

    import numpy as np
    from cxxnet_tpu.io.binpage import iter_packfile

    img_dir = tmp_path / "imgs"
    img_dir.mkdir()
    lst = tmp_path / "all.lst"
    with open(lst, "w") as f:
        for i in range(10):
            (img_dir / ("i%d.jpg" % i)).write_bytes(
                np.random.RandomState(i).bytes(50))
            f.write("%d\t0\ti%d.jpg\n" % (i, i))
    rc = subprocess.call(
        [sys.executable,
         os.path.join(REPO, "tools", "imgbin_partition_maker.py"),
         "--img_list", str(lst), "--img_root", str(img_dir) + os.sep,
         "--prefix", "part", "--out", str(tmp_path / "parts"),
         "--nparts", "3"])
    assert rc == 0
    total = 0
    for p in range(3):
        binp = tmp_path / "parts" / ("part_part-%d.bin" % p)
        assert binp.exists()
        total += len(list(iter_packfile(str(binp))))
    assert total == 10


def test_imagenet_rehearsal_tool_smoke(tmp_path):
    """tools/imagenet_rehearsal.py end to end at toy scale on CPU:
    synth -> native im2bin multi-part pack -> test_io -> train window."""
    import json
    import subprocess
    import sys

    pytest.importorskip("cv2")
    if not os.path.exists(os.path.join(REPO, "cxxnet_tpu", "lib",
                                       "im2bin")):
        pytest.skip("native im2bin not built")
    report = tmp_path / "r.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "imagenet_rehearsal.py"),
         "--images", "96", "--parts", "2", "--batch", "16",
         "--dev", "cpu", "--train-batches", "2",
         "--input-shape", "3,67,67",
         "--out", str(tmp_path / "data"), "--report", str(report)],
        capture_output=True, text=True, timeout=580,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(report.read_text())
    assert rep["parts"] == 2 and rep["pack_gb"] > 0
    assert rep["test_io_images_per_sec"] > 0
    assert rep["train_batches"] >= 2

"""CLI smoke tests: the Train/Test mains run end-to-end on tiny synthetic
datasets written in the reference's on-disk formats (idx-ubyte MNIST,
CIFAR bins, input.txt — reference models/*/Train.scala pipelines)."""

import gzip
import json
import os
import struct
import sys

import numpy as np
import pytest


def _write_mnist(folder, n=64, seed=0):
    os.makedirs(folder, exist_ok=True)
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, n).astype(np.uint8)
    images = (labels[:, None, None] * 20
              + rng.randint(0, 30, (n, 28, 28))).astype(np.uint8)
    for stem, count in [("train", n), ("t10k", n)]:
        with open(os.path.join(folder, f"{stem}-images-idx3-ubyte"),
                  "wb") as f:
            f.write(struct.pack(">IIII", 2051, count, 28, 28))
            f.write(images[:count].tobytes())
        with open(os.path.join(folder, f"{stem}-labels-idx1-ubyte"),
                  "wb") as f:
            f.write(struct.pack(">II", 2049, count))
            f.write(labels[:count].tobytes())
    return images, labels


def _write_cifar(folder, n_train=48, n_test=16, seed=0):
    os.makedirs(folder, exist_ok=True)
    rng = np.random.RandomState(seed)

    def write(path, count):
        with open(path, "wb") as f:
            for _ in range(count):
                lab = rng.randint(0, 10)
                img = (np.full((3, 32, 32), lab * 20, np.uint8)
                       + rng.randint(0, 20, (3, 32, 32)).astype(np.uint8))
                f.write(bytes([lab]))
                f.write(img.tobytes())

    per = max(1, n_train // 5)
    for i in range(1, 6):
        write(os.path.join(folder, f"data_batch_{i}.bin"), per)
    write(os.path.join(folder, "test_batch.bin"), n_test)


def test_lenet_train_and_test(tmp_path, capsys):
    from bigdl_tpu.cli import lenet

    data = str(tmp_path / "mnist")
    ckpt = str(tmp_path / "ckpt")
    _write_mnist(data)
    trained = lenet.main(["train", "-f", data, "-b", "16", "--maxEpoch", "6",
                          "--learningRate", "0.1", "--checkpoint", ckpt,
                          "--logEvery", "100"])
    assert trained is not None
    assert any(f.startswith("model.") for f in os.listdir(ckpt))
    results = lenet.main(["test", "-f", data, "-b", "16", "--model", ckpt])
    acc, _count = results[0].result()
    assert acc > 0.3  # tiny synthetic set, 2 epochs — just needs learning


def test_vgg_cli_parses_and_runs_one_epoch(tmp_path):
    from bigdl_tpu.cli import vgg

    data = str(tmp_path / "cifar")
    _write_cifar(data)
    trained = vgg.main(["train", "-f", data, "-b", "8", "--maxEpoch", "1",
                        "--logEvery", "100"])
    assert trained is not None


def test_autoencoder_cli(tmp_path):
    from bigdl_tpu.cli import autoencoder

    data = str(tmp_path / "mnist")
    _write_mnist(data)
    trained = autoencoder.main(["train", "-f", data, "-b", "16",
                                "--maxEpoch", "1", "--adagrad",
                                "--learningRate", "0.01",
                                "--logEvery", "100"])
    assert trained is not None


def test_rnn_cli(tmp_path, capsys):
    from bigdl_tpu.cli import rnn

    data = tmp_path / "text"
    data.mkdir()
    words = ["the", "quick", "brown", "fox", "jumps", "over", "lazy", "dog"]
    (data / "input.txt").write_text(" ".join(words * 50))
    trained = rnn.main(["train", "-f", str(data), "-b", "16",
                        "--maxEpoch", "2", "--seqLength", "5",
                        "--hiddenSize", "16", "--learningRate", "0.5",
                        "--logEvery", "100"])
    assert trained is not None
    out = capsys.readouterr().out
    assert "perplexity is" in out


def test_perf_harness_lenet(capsys):
    from bigdl_tpu.cli import perf

    out = perf.run("lenet5", batch=8, iterations=2, data_type="random",
                   use_bf16=False)
    assert out["records_per_second"] > 0
    printed = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(printed)
    assert parsed["model"] == "lenet5"
    assert parsed["images_per_second_per_chip"] > 0


def test_resnet_cli_cifar_fused_bn(tmp_path):
    """--fusedBN on the real training CLI (VERDICT r4 item 3): one epoch
    on synthetic CIFAR runs end-to-end with the Pallas BN stats path."""
    from bigdl_tpu.cli import resnet

    data = str(tmp_path / "cifar")
    _write_cifar(data)
    trained = resnet.main(["train", "-f", data, "-b", "8", "--maxEpoch",
                           "1", "--depth", "8", "--fusedBN",
                           "--logEvery", "100"])
    assert trained is not None


def test_resnet_cli_cifar_fused_bn_apply(tmp_path):
    """--fusedBN apply (ISSUE 2): the FULL fused BN block (stats+apply+
    absorbed-ReLU fwd, reductions+dx bwd) reachable end-to-end on the
    real training CLI."""
    from bigdl_tpu.cli import resnet

    data = str(tmp_path / "cifar")
    _write_cifar(data)
    trained = resnet.main(["train", "-f", data, "-b", "8", "--maxEpoch",
                           "1", "--depth", "8", "--fusedBN", "apply",
                           "--logEvery", "100"])
    assert trained is not None
    from bigdl_tpu.nn.norm import bn_fused_mode
    assert bn_fused_mode(trained.module) == "apply"


def test_resnet_cli_imagenet_s2d(tmp_path):
    """--dataset imagenet --s2d: space-to-depth stem on the training CLI,
    one epoch over a tiny label-by-folder image tree."""
    from PIL import Image

    from bigdl_tpu.cli import resnet

    rng = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = tmp_path / "train" / cls
        d.mkdir(parents=True)
        for i in range(4):
            Image.fromarray(rng.randint(0, 255, (64, 64, 3), np.uint8)
                            ).save(d / f"{i}.jpg")
    trained = resnet.main(["train", "-f", str(tmp_path), "-b", "4",
                           "--dataset", "imagenet", "--depth", "18",
                           "--classNum", "2", "--maxEpoch", "1",
                           "--s2d", "--fusedBN", "--logEvery", "100"])
    assert trained is not None


def test_resnet_cli_s2d_rejected_on_cifar(tmp_path):
    from bigdl_tpu.cli import resnet

    with pytest.raises(SystemExit, match="imagenet"):
        resnet.main(["train", "-f", str(tmp_path), "--s2d"])


def test_resnet_cli_depth_validation(tmp_path):
    from bigdl_tpu.cli import resnet

    with pytest.raises(SystemExit, match="invalid for imagenet"):
        resnet.main(["train", "-f", str(tmp_path), "--dataset", "imagenet",
                     "--depth", "20"])
    with pytest.raises(SystemExit, match="invalid for cifar10"):
        resnet.main(["train", "-f", str(tmp_path), "--depth", "21"])

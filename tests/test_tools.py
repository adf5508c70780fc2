"""Tools suite: im2rec packing, log parsing, local launcher
(reference tools/im2rec.py, tools/parse_log.py, tools/launch.py)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

cv2 = pytest.importorskip("cv2")

import im2rec  # noqa: E402
import parse_log  # noqa: E402


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = root / cls
        d.mkdir()
        for i in range(6):
            img = rng.randint(0, 255, (48, 64, 3), np.uint8)
            cv2.imwrite(str(d / f"{cls}{i}.jpg"), img)
    return str(root)


def test_im2rec_list_and_pack_roundtrip(image_dir, tmp_path):
    prefix = str(tmp_path / "data")
    im2rec.main(["--list", "--recursive", prefix, image_dir])
    assert os.path.exists(prefix + ".lst")
    lines = open(prefix + ".lst").read().strip().split("\n")
    assert len(lines) == 12
    labels = {float(l.split("\t")[1]) for l in lines}
    assert labels == {0.0, 1.0}

    im2rec.main([prefix, image_dir, "--resize", "32", "--quality", "90"])
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    from mxnet_tpu.image_io import ImageRecordIter

    it = ImageRecordIter(path_imgrec=prefix + ".rec", data_shape=(3, 32, 32),
                         batch_size=4, preprocess_threads=1)
    seen, label_set = 0, set()
    while True:
        try:
            b = it.next()
        except StopIteration:
            break
        seen += b.data[0].shape[0]
        label_set |= set(b.label[0].asnumpy().tolist())
    assert seen == 12
    assert label_set == {0.0, 1.0}


def test_im2rec_sharding(image_dir, tmp_path):
    prefix = str(tmp_path / "shard")
    im2rec.main(["--list", "--recursive", prefix, image_dir])
    im2rec.main([prefix, image_dir, "--num-parts", "2", "--resize", "32"])
    from mxnet_tpu import recordio

    n = 0
    for part in range(2):
        reader = recordio.MXRecordIO(f"{prefix}_{part}.rec", "r")
        while reader.read() is not None:
            n += 1
        reader.close()
    assert n == 12


def test_parse_log(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO:root:Epoch[0] Batch [50] Speed: 1234.5 samples/sec "
        "Train-accuracy=0.51\n"
        "INFO:root:Epoch[0] Train-accuracy=0.612\n"
        "INFO:root:Epoch[0] Time cost=12.5\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.633\n"
        "INFO:root:Epoch[1] Train-accuracy=0.71\n"
        "INFO:root:Epoch[1] Time cost=11.9\n"
        "INFO:root:Epoch[1] Validation-accuracy=0.725\n")
    rows = parse_log.parse(log.read_text().split("\n"))
    assert rows[0]["val-accuracy"] == 0.633
    assert rows[1]["train-accuracy"] == 0.71
    assert rows[0]["time"] == 12.5
    assert rows[0]["speed"] == 1234.5
    md = parse_log.render(rows, "markdown")
    assert "| epoch |" in md and "0.725" in md
    csv = parse_log.render(rows, "csv")
    assert csv.splitlines()[0].startswith("epoch,")


@pytest.mark.slow
def test_launch_local_spawns_ranked_processes(tmp_path):
    out = tmp_path / "ranks"
    out.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        f"open(os.path.join({str(out)!r}, os.environ['MXTPU_PROC_ID']), 'w')"
        ".write(os.environ['MXTPU_COORDINATOR'] + ' ' +"
        " os.environ['MXTPU_NUM_PROCS'])\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "3", "--", sys.executable, str(script)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    files = sorted(os.listdir(out))
    assert files == ["0", "1", "2"]
    contents = {open(out / f).read() for f in files}
    assert len(contents) == 1  # same coordinator + nprocs everywhere
    assert contents.pop().endswith(" 3")


def test_native_im2rec_roundtrip(tmp_path):
    """The C++ im2rec tool (src/im2rec.cc) packs a .lst into a .rec that
    ImageRecordIter (and the python recordio reader) consume."""
    exe = os.path.join(REPO, "tools", "im2rec")
    if not os.path.exists(exe):
        pytest.skip("native im2rec not built (no OpenCV)")

    root = tmp_path / "imgs"
    root.mkdir()
    lines = []
    for i in range(10):
        img = np.full((30 + i, 36, 3), i * 20, np.uint8)
        cv2.imwrite(str(root / f"im{i}.png"), img)
        lines.append(f"{i}\t{float(i % 4)}\tim{i}.png")
    prefix = str(tmp_path / "data")
    with open(prefix + ".lst", "w") as f:
        f.write("\n".join(lines) + "\n")

    r = subprocess.run([exe, prefix, str(root), "--resize", "32",
                        "--quality", "95"], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    assert "wrote 10/10" in r.stdout

    # python reader sees headers + decodable images
    from mxnet_tpu import recordio

    reader = recordio.MXRecordIO(prefix + ".rec", "r")
    n = 0
    while True:
        raw = reader.read()
        if raw is None:
            break
        header, img = recordio.unpack_img(raw, iscolor=1)
        assert header.label == float(n % 4)
        assert min(img.shape[:2]) == 32
        n += 1
    assert n == 10
    reader.close()

    # and the full iterator consumes it
    from mxnet_tpu.image_io import ImageRecordIter

    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         data_shape=(3, 28, 28), batch_size=5,
                         preprocess_threads=2)
    batches = list(iter(it))
    assert len(batches) == 2
    np.testing.assert_allclose(batches[0].label[0].asnumpy(),
                               [0, 1, 2, 3, 0])


def test_compare_baseline_table(tmp_path):
    """tools/compare_baseline.py renders whatever artifact subset
    exists into one markdown table."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # synthetic artifact set in an isolated dir
    (tmp_path / "BENCH_TPU_LATEST.json").write_text(json.dumps({
        "metric": "resnet50_train_throughput", "value": 2845.0,
        "unit": "images/sec/chip", "vs_baseline": 1.138,
        "platform": "tpu", "mfu": 0.358,
        "vs_baseline_per_peak_tflop": 1.80}))
    (tmp_path / "IO_BENCH.json").write_text(json.dumps({
        "metric": "image_pipeline_throughput", "value": 539.5,
        "vs_baseline_per_core": 2.158, "host_cores": 1}))
    # artifacts may be INDENTED multi-line JSON — the loader must
    # accept that format, not just one-liners
    (tmp_path / "QUANT_BENCH.json").write_text(json.dumps({
        "metric": "resnet50_int8_inference", "platform": "tpu",
        "int8_img_per_sec": 5200.0, "int8_speedup": 1.9}, indent=1))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "compare_baseline.py"),
         "--repo", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-500:]
    assert "1.138x" in r.stdout and "1.80x per peak TFLOP" in r.stdout
    assert "2.16x/core" in r.stdout
    assert "1.90x" in r.stdout  # the indented QUANT artifact parsed
    # empty dir renders the placeholder row, still exit 0
    empty = tmp_path / "empty"
    empty.mkdir()
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "compare_baseline.py"),
         "--repo", str(empty)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "no TPU artifacts" in r.stdout

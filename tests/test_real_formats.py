"""Real on-disk format ingestion (VERDICT r2 item 7).

Each dataset provider's real-data branch (``data/datasets.py``) parses
the format the reference's torchvision/torchaudio loaders consume
(``/root/reference/src/dataset/dataloader.py:61-122``); these tests
write tiny byte-exact fixtures into a temp SLT_DATA_DIR and drive every
branch in CI — a format bug must not wait for a real deployment.
"""

import pickle
import struct
import wave

import numpy as np
import pytest

from split_learning_tpu.data.datasets import get_dataset


@pytest.fixture()
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("SLT_DATA_DIR", str(tmp_path))
    return tmp_path


def test_cifar10_pickle_batches(data_dir):
    root = data_dir / "cifar-10-batches-py"
    root.mkdir()
    rng = np.random.default_rng(0)

    def write(name, n, label0):
        data = rng.integers(0, 256, size=(n, 3072), dtype=np.uint8)
        labels = [(label0 + i) % 10 for i in range(n)]
        with open(root / name, "wb") as f:
            pickle.dump({b"data": data, b"labels": labels}, f)
        return data, labels

    per_batch = 2
    train_parts = [write(f"data_batch_{i}", per_batch, i)
                   for i in range(1, 6)]
    write("test_batch", 3, 7)

    ds = get_dataset("CIFAR10", train=True)
    assert len(ds) == 5 * per_batch
    assert ds.inputs.shape == (10, 32, 32, 3)        # NHWC
    assert ds.inputs.dtype == np.float32
    # normalization applied: values no longer in [0, 255]
    assert float(np.abs(ds.inputs).max()) < 10.0
    # first sample round-trips the CHW->HWC transpose exactly
    raw0 = train_parts[0][0][0].reshape(3, 32, 32).transpose(1, 2, 0)
    mean = np.array([0.4914, 0.4822, 0.4465], np.float32)
    std = np.array([0.2470, 0.2435, 0.2616], np.float32)
    np.testing.assert_allclose(
        ds.inputs[0], (raw0.astype(np.float32) / 255.0 - mean) / std,
        rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ds.labels[:2], [1, 2])
    # normalised in place, to the bit what the one expression gives, and
    # still in the files' memory order (planes)
    raw = np.concatenate([d for d, _ in train_parts]).reshape(
        -1, 3, 32, 32).transpose(0, 2, 3, 1)
    want = (raw.astype(np.float32) / 255.0 - mean) / std
    assert ds.inputs.tobytes() == want.tobytes()
    assert ds.inputs.strides == want.strides

    val = get_dataset("CIFAR10", train=False)
    assert len(val) == 3
    np.testing.assert_array_equal(val.labels, [7, 8, 9])


def test_mnist_idx_pair(data_dir):
    root = data_dir / "MNIST" / "raw"
    root.mkdir(parents=True)
    rng = np.random.default_rng(1)
    for stem, n in (("train", 4), ("t10k", 2)):
        imgs = rng.integers(0, 256, size=(n, 28, 28), dtype=np.uint8)
        labels = np.arange(n, dtype=np.uint8)
        with open(root / f"{stem}-images-idx3-ubyte", "wb") as f:
            f.write(struct.pack(">IIII", 2051, n, 28, 28))
            f.write(imgs.tobytes())
        with open(root / f"{stem}-labels-idx1-ubyte", "wb") as f:
            f.write(struct.pack(">II", 2049, n))
            f.write(labels.tobytes())
    ds = get_dataset("MNIST", train=True)
    assert ds.inputs.shape == (4, 28, 28, 1)
    assert ds.inputs.dtype == np.float32
    np.testing.assert_array_equal(ds.labels, [0, 1, 2, 3])
    val = get_dataset("MNIST", train=False)
    assert len(val) == 2


def _write_wav(path, seconds=1.0, freq=440.0):
    n = int(16000 * seconds)
    t = np.arange(n) / 16000.0
    sig = (np.sin(2 * np.pi * freq * t) * 0.3 * 32767).astype(np.int16)
    with wave.open(str(path), "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(16000)
        w.writeframes(sig.tobytes())


def test_speechcommands_wav_walk_and_split_lists(data_dir):
    root = data_dir / "SpeechCommands" / "speech_commands_v0.02"
    (root / "yes").mkdir(parents=True)
    (root / "no").mkdir()
    _write_wav(root / "yes" / "a.wav")
    _write_wav(root / "yes" / "b.wav", seconds=0.5)   # needs padding
    _write_wav(root / "no" / "c.wav", freq=880.0)
    # b.wav is held out to the validation split
    (root / "validation_list.txt").write_text("yes/b.wav\n")
    ds = get_dataset("SPEECHCOMMANDS", train=True)
    assert ds.inputs.shape == (2, 40, 98)             # MFCC features
    assert sorted(ds.labels.tolist()) == [0, 1]       # yes=0, no=1
    val = get_dataset("SPEECHCOMMANDS", train=False)
    assert val.inputs.shape == (1, 40, 98)
    assert val.labels.tolist() == [0]


def test_emotion_on_disk_semicolon_format(data_dir):
    root = data_dir / "emotion"
    root.mkdir()
    (root / "train.txt").write_text(
        "i didnt feel humiliated;sadness\n"
        "i feel great about it; all of it;joy\n"   # ; inside text
        "im grabbing a minute to post i feel greedy wrong;3\n")
    (root / "test.txt").write_text("i am feeling calm;joy\n")
    ds = get_dataset("EMOTION", train=True)
    assert len(ds) == 3
    assert ds.inputs.shape[1] == 128
    assert ds.inputs[0, 0] == 101                      # [CLS]
    np.testing.assert_array_equal(ds.labels, [0, 1, 3])
    val = get_dataset("EMOTION", train=False)
    assert val.labels.tolist() == [1]


def test_fetch_cifar10_installs_loader_layout(data_dir, monkeypatch):
    """`python -m split_learning_tpu.data --fetch cifar10` (VERDICT r4
    missing #4, RpcClient.py:64-88 self-download parity): the fetcher
    downloads the upstream tar.gz, installs the exact layout the CIFAR
    loader reads, and the loader then returns REAL bytes instead of the
    synthetic fallback.  urlopen is injected with a local fixture so
    the install/extract logic runs on this zero-egress host."""
    import io
    import pickle
    import tarfile

    from split_learning_tpu.data import fetch as fetch_mod

    rng = np.random.default_rng(1)

    def member(tar, name, payload):
        raw = io.BytesIO()
        pickle.dump(payload, raw)
        data = raw.getvalue()
        info = tarfile.TarInfo(f"cifar-10-batches-py/{name}")
        info.size = len(data)
        tar.addfile(info, io.BytesIO(data))

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        for i in range(1, 6):
            member(tar, f"data_batch_{i}", {
                b"data": rng.integers(0, 256, size=(2, 3072),
                                      dtype=np.uint8),
                b"labels": [i % 10, (i + 1) % 10]})
        member(tar, "test_batch", {
            b"data": rng.integers(0, 256, size=(2, 3072),
                                  dtype=np.uint8),
            b"labels": [3, 4]})

    seen = []

    def fake_urlopen(url, timeout=0):
        seen.append(url)
        return io.BytesIO(buf.getvalue())

    # the fixture archive is not the upstream bytes: re-pin the spec's
    # sha256 to the fixture's digest so verification RUNS and passes
    # (the mismatch path has its own test below)
    import hashlib
    digest = hashlib.sha256(buf.getvalue()).hexdigest()
    url0, kind0, member0, _ = fetch_mod._SPECS["cifar10"]["files"][0]
    monkeypatch.setitem(fetch_mod._SPECS["cifar10"], "files",
                        [(url0, kind0, member0, digest)])

    probe = fetch_mod.fetch("cifar10", urlopen=fake_urlopen,
                            log=lambda *_: None)
    assert probe.exists()
    assert "cs.toronto.edu" in seen[0]
    ds = get_dataset("CIFAR10", train=True)
    assert len(ds) == 10          # real bytes, not the synthetic 10000
    assert ds.inputs.shape == (10, 32, 32, 3)


def test_fetch_rejects_sha256_mismatch(data_dir):
    """A tampered (or upstream-changed) archive must be refused BEFORE
    extraction and leave the live layout untouched (ADVICE r5: the
    fetcher previously installed whatever bytes arrived)."""
    import io

    from split_learning_tpu.data import fetch as fetch_mod

    def evil_urlopen(url, timeout=0):
        return io.BytesIO(b"not the published archive")

    with pytest.raises(RuntimeError, match="sha256 mismatch"):
        fetch_mod.fetch("cifar10", urlopen=evil_urlopen,
                        log=lambda *_: None)
    assert not (data_dir / "cifar-10-batches-py").exists()


def test_fetch_specs_pin_sha256_and_https():
    """Every spec entry carries a sha256 pin (agnews' mutable git-raw
    CSVs are the documented exception) and no URL is plain http —
    the speechcommands URL was the MITM-able one (ADVICE r5)."""
    from split_learning_tpu.data import fetch as fetch_mod

    for name, spec in fetch_mod._SPECS.items():
        for url, _kind, _member, sha in spec["files"]:
            assert url.startswith("https://"), (name, url)
            if name != "agnews":
                assert isinstance(sha, str) and len(sha) == 64, (name,
                                                                 url)


def test_fetch_tar_fallback_rejects_traversal(data_dir, monkeypatch):
    """On interpreters without extractall(filter=), a tampered archive
    with '..' members must be rejected, not written outside the root."""
    import io
    import tarfile

    from split_learning_tpu.data import fetch as fetch_mod

    evil = io.BytesIO()
    with tarfile.open(fileobj=evil, mode="w:gz") as tar:
        data = b"owned"
        info = tarfile.TarInfo("../../escape.txt")
        info.size = len(data)
        tar.addfile(info, io.BytesIO(data))
    payload = evil.getvalue()

    import hashlib
    digest = hashlib.sha256(payload).hexdigest()
    url0, kind0, member0, _ = fetch_mod._SPECS["cifar10"]["files"][0]
    monkeypatch.setitem(fetch_mod._SPECS["cifar10"], "files",
                        [(url0, kind0, member0, digest)])

    # force the pre-filter= fallback path regardless of interpreter
    real_extractall = tarfile.TarFile.extractall

    def no_filter_extractall(self, path=".", members=None, *,
                             numeric_owner=False, **kw):
        if "filter" in kw:
            raise TypeError("extractall() got an unexpected keyword "
                            "argument 'filter'")
        return real_extractall(self, path=path, members=members,
                               numeric_owner=numeric_owner)

    monkeypatch.setattr(tarfile.TarFile, "extractall",
                        no_filter_extractall)

    with pytest.raises(RuntimeError, match="path traversal"):
        fetch_mod.fetch("cifar10",
                        urlopen=lambda url, timeout=0: io.BytesIO(payload),
                        log=lambda *_: None)
    assert not (data_dir.parent / "escape.txt").exists()


def test_fetch_zero_egress_fails_with_guidance(data_dir, monkeypatch):
    """On a no-network host the fetch fails with the staging guidance
    instead of a bare stack trace, and never half-installs: a MID-fetch
    network drop (two of four MNIST files served, then failure) leaves
    the live layout untouched — real train files next to a synthetic
    test split would silently validate against a different
    distribution."""
    import gzip as gz
    import io

    from split_learning_tpu.data import fetch as fetch_mod

    def dead_urlopen(url, timeout=0):
        raise OSError("Network is unreachable")

    with pytest.raises(RuntimeError, match="No network egress"):
        fetch_mod.fetch("mnist", urlopen=dead_urlopen,
                        log=lambda *_: None)
    assert not (data_dir / "MNIST" / "raw"
                / "train-images-idx3-ubyte").exists()

    served = []
    payload = gz.compress(b"\x00" * 32)

    # pin the fixture bytes so the first two files pass verification
    # and the failure really is the third file's network drop
    import hashlib
    digest = hashlib.sha256(payload).hexdigest()
    monkeypatch.setitem(
        fetch_mod._SPECS["mnist"], "files",
        [(url, kind, member, digest)
         for url, kind, member, _ in fetch_mod._SPECS["mnist"]["files"]])

    def flaky_urlopen(url, timeout=0):
        if len(served) >= 2:
            raise OSError("Connection reset by peer")
        served.append(url)
        return io.BytesIO(payload)

    with pytest.raises(RuntimeError, match="No network egress"):
        fetch_mod.fetch("mnist", urlopen=flaky_urlopen,
                        log=lambda *_: None)
    assert len(served) == 2          # two files really were downloaded
    assert not (data_dir / "MNIST").exists()   # ...but none installed

    with pytest.raises(KeyError, match="fetchable"):
        fetch_mod.fetch("nope")

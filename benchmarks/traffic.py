"""The one general traffic generator: a job's data, made from the seed.

A traffic mix is a JSON file of parameters (``traffic/<name>.json``); a
configuration names its dataset's kind and sizes.  From those and
``--seed`` this writes the dataset in its standard on-disk format under
one directory, which the program is pointed at (``SLT_DATA_DIR``) and
loads through its normal loaders — and returns, as arrays, the rows the
program will get from them, so the reference can be given the same
validation rows without asking the program for anything.

Kinds: ``cifar10`` (python-pickle batches of uint8 CHW rows, as the
CIFAR-10 archive has them), ``agnews`` (``label,title,description``
CSV, as the AG-News archive has it) and ``tokens`` (``TinyStories/
{train,valid}.npy``, int32 rows of ``seq-len + 1`` ids of a packed
document stream, which the program's token loader cuts into inputs
``ids[:, :-1]`` and next-token labels ``ids[:, 1:]``).
"""

from __future__ import annotations

import csv
import pathlib
import pickle
import zlib

import numpy as np

# the per-channel statistics every CIFAR-10 pipeline normalizes with
CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)
AGNEWS_SEQ_LEN = 128
CLS_ID, SEP_ID = 101, 102
# the ``tokens`` stream: documents of log-normal length (median 600
# tokens, sigma 1: one in nine is longer than 2,048, one in 200 than
# 8,192), each ending in EOD_ID; inside a document 7 tokens of 10 come from
# its topic's band of the vocabulary and the rest from all of it, both
# with P(rank) ~ 1/rank
EOD_ID = 0
DOC_MEDIAN, DOC_SIGMA, DOC_MIN = 600.0, 1.0, 8
TOPIC_SHARE, TOPIC_BANDS = 0.7, 16


def job_sizes(traffic: dict, dataset: dict, n_clients: int,
              step_batch: int) -> dict:
    """Per-round sizes of the job: each stage-1 client's share of
    ``epoch_fraction`` of the training set, rounded down to whole
    optimizer steps; the whole validation set."""
    share = int(dataset["train"] * traffic["epoch_fraction"]) // n_clients
    per_client = max(1, share // step_batch) * step_batch
    return {"per_client": per_client,
            "steps_per_client": per_client // step_batch,
            "val": int(dataset["val"])}


def _balanced_labels(rng, n: int, n_classes: int) -> np.ndarray:
    labels = np.arange(n) % n_classes
    rng.shuffle(labels)
    return labels.astype(np.int32)


def _write_cifar10(root: pathlib.Path, rng, n_train: int, n_val: int):
    out = root / "cifar-10-batches-py"
    out.mkdir(parents=True, exist_ok=True)
    val = None
    files = [(f"data_batch_{i}", n_train // 5 + (i <= n_train % 5))
             for i in range(1, 6)] + [("test_batch", n_val)]
    for fname, n in files:
        labels = _balanced_labels(rng, n, 10)
        # a per-class tint under uniform noise: rows all differ, and a
        # class is learnable, as in the real set
        tint = (labels[:, None] * 13 % 97).astype(np.uint8)
        data = (rng.integers(0, 160, size=(n, 3072), dtype=np.uint8)
                + tint)
        with open(out / fname, "wb") as f:
            pickle.dump({b"data": data, b"labels": labels.tolist()}, f,
                        protocol=4)
        if fname == "test_batch":
            x = data.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
            x = (x.astype(np.float32) / 255.0 - CIFAR10_MEAN) / CIFAR10_STD
            val = (x, labels)
    return val


def hash_token(word: str, vocab: int) -> int:
    """The id an offline whitespace+CRC32 tokenizer gives ``word``."""
    return 1000 + zlib.crc32(word.encode()) % (vocab - 1100)


def _write_agnews(root: pathlib.Path, rng, n_train: int, n_val: int,
                  vocab: int):
    out = root / "ag_news"
    out.mkdir(parents=True, exist_ok=True)
    val = None
    for fname, n in (("train.csv", n_train), ("test.csv", n_val)):
        labels = _balanced_labels(rng, n, 4)
        lengths = rng.integers(24, AGNEWS_SEQ_LEN - 1, size=n)
        words = rng.integers(0, 50000, size=(n, AGNEWS_SEQ_LEN))
        ids = np.zeros((n, AGNEWS_SEQ_LEN), np.int32)
        with open(out / fname, "w", newline="", encoding="utf-8") as f:
            wr = csv.writer(f)
            for i in range(n):
                # a class owns a band of words, mixed with common ones
                toks = [f"c{labels[i]}w{w % 500}" if w % 3 == 0 else f"w{w}"
                        for w in words[i, :lengths[i] - 2]]
                wr.writerow([int(labels[i]) + 1, " ".join(toks[:8]),
                             " ".join(toks[8:])])
                row = [CLS_ID] + [hash_token(t, vocab) for t in toks] \
                    + [SEP_ID]
                ids[i, :len(row)] = row
        if fname == "test.csv":
            val = (ids, labels)
    return val


def token_stream(rng, n_tokens: int, vocab: int) -> np.ndarray:
    """``n_tokens`` ids in [0, vocab) of documents packed back to back,
    each closed by ``EOD_ID``; the last document is cut where the stream
    ends."""
    mean_len = DOC_MEDIAN * np.exp(DOC_SIGMA ** 2 / 2)
    lengths = np.zeros(0, np.int64)
    while lengths.sum() < n_tokens:
        more = rng.lognormal(np.log(DOC_MEDIAN), DOC_SIGMA,
                             int(1.2 * n_tokens / mean_len) + 16)
        lengths = np.concatenate(
            [lengths, np.maximum(more.astype(np.int64), DOC_MIN)])
    ends = lengths.cumsum()
    lengths = lengths[:np.searchsorted(ends, n_tokens) + 1]
    band = max(2, (vocab - 1) // TOPIC_BANDS)
    # a document's topic is where its band starts, among ids 1 .. vocab - 1
    starts = rng.integers(1, max(2, vocab - band + 1), size=len(lengths),
                          dtype=np.int32)
    topical = rng.random(n_tokens, dtype=np.float32) < TOPIC_SHARE
    # a rank 1 .. n with P(r) ~ 1 / r is a log-uniform draw, rounded down
    u = rng.random(n_tokens, dtype=np.float32)
    u *= np.where(topical, np.float32(np.log(band)),
                  np.float32(np.log(vocab - 1)))
    ids = np.exp(u, out=u).astype(np.int32)
    ids += np.where(topical, np.repeat(starts, lengths)[:n_tokens], 1) - 1
    np.minimum(ids, vocab - 1, out=ids)
    ids[ends[:len(lengths) - 1] - 1] = EOD_ID
    if ends[len(lengths) - 1] == n_tokens:
        ids[-1] = EOD_ID
    return ids


def _write_tokens(root: pathlib.Path, rng, n_train: int, n_val: int,
                  vocab: int, seq_len: int):
    out = root / "TinyStories"
    out.mkdir(parents=True, exist_ok=True)
    val = None
    for fname, n in (("train.npy", n_train), ("valid.npy", n_val)):
        # rows are cut from the stream at every seq_len + 1 ids: a
        # document runs on over a row's end, as in a packed corpus
        ids = token_stream(rng, n * (seq_len + 1), vocab).reshape(
            n, seq_len + 1)
        np.save(out / fname, ids)
        if fname == "valid.npy":
            val = (ids[:, :-1], ids[:, 1:])
    return val


def make_dataset(kind: str, root: pathlib.Path, seed: int, n_train: int,
                 n_val: int, vocab: int | None = None,
                 seq_len: int | None = None):
    """Write the dataset under ``root``; return the validation rows
    ``(inputs, labels)`` as the program's loader will present them (file
    order, normalized / tokenized)."""
    rng = np.random.default_rng(seed)
    if kind == "cifar10":
        return _write_cifar10(root, rng, n_train, n_val)
    if kind == "agnews":
        return _write_agnews(root, rng, n_train, n_val, vocab or 28996)
    if kind == "tokens":
        if not vocab:
            raise ValueError("dataset kind 'tokens' needs the vocabulary: "
                             "program.model-kwargs.vocab_size is missing")
        if not seq_len:
            raise ValueError("dataset kind 'tokens' needs dataset.seq-len")
        return _write_tokens(root, rng, n_train, n_val, int(vocab),
                             int(seq_len))
    raise ValueError(f"unknown dataset kind {kind!r}")

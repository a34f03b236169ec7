"""Data subsystem: subsetting, static-shape batching, MFCC, providers."""

import numpy as np
import pytest

from split_learning_tpu.data import (
    ArrayDataset, DataLoader, cifar_augment, get_dataset,
    label_count_subset, make_data_loader,
)
from split_learning_tpu.data.mfcc import compute_mfcc, mel_filterbank


class TestLabelCountSubset:
    def test_exact_counts(self):
        labels = np.repeat(np.arange(4), 50)
        rng = np.random.default_rng(0)
        idx = label_count_subset(labels, [10, 0, 5, 50], rng)
        got = labels[idx]
        assert (got == 0).sum() == 10
        assert (got == 1).sum() == 0
        assert (got == 2).sum() == 5
        assert (got == 3).sum() == 50

    def test_wraps_when_scarce(self):
        labels = np.array([0, 0, 1])
        idx = label_count_subset(labels, [5, 2], np.random.default_rng(0))
        assert (labels[idx] == 0).sum() == 5

    def test_deterministic_given_seed(self):
        labels = np.repeat(np.arange(3), 100)
        a = label_count_subset(labels, [7, 7, 7], np.random.default_rng(3))
        b = label_count_subset(labels, [7, 7, 7], np.random.default_rng(3))
        np.testing.assert_array_equal(a, b)


class TestDataLoader:
    def test_static_batch_shapes(self):
        ds = ArrayDataset(np.zeros((105, 4), np.float32),
                          np.zeros(105, np.int32))
        dl = DataLoader(ds, batch_size=32, seed=0)
        shapes = [x.shape for x, _ in dl]
        assert shapes == [(32, 4)] * 3  # 105 // 32, no ragged tail

    def test_wraps_small_dataset_to_one_batch(self):
        ds = ArrayDataset(np.arange(10, dtype=np.float32)[:, None],
                          np.zeros(10, np.int32))
        dl = DataLoader(ds, batch_size=32, seed=0)
        (x, y), = list(dl)
        assert x.shape == (32, 1) and y.shape == (32,)

    def test_dict_inputs(self):
        ins = {"ids": np.zeros((64, 8), np.int32),
               "mask": np.ones((64, 8), np.int32)}
        dl = DataLoader(ArrayDataset(ins, np.zeros(64, np.int32)),
                        batch_size=16, seed=0)
        x, _ = next(iter(dl))
        assert set(x) == {"ids", "mask"} and x["ids"].shape == (16, 8)


# --------------------------------------------------------------------------
# the one-pass feed against the code it replaced
# --------------------------------------------------------------------------

def oracle_augment(x, rng):
    """The pad-and-loop crop ``cifar_augment`` was before the one-pass
    gather: the oracle, byte for byte and draw for draw."""
    b, h, w, _ = x.shape
    padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect")
    out = np.empty_like(x)
    ys = rng.integers(0, 9, size=b)
    xs = rng.integers(0, 9, size=b)
    flip = rng.random(b) < 0.5
    for i in range(b):
        crop = padded[i, ys[i]:ys[i] + h, xs[i]:xs[i] + w]
        out[i] = crop[:, ::-1] if flip[i] else crop
    return out


def oracle_epoch(dataset, batch_size, shuffle, augment, rng):
    """``DataLoader.__iter__`` as it was: ``take`` (a fancy-index copy),
    then the augmentation over that copy."""
    n = len(dataset)
    num_batches = max(1, n // batch_size)
    order = np.arange(n)
    if shuffle:
        rng.shuffle(order)
    need = num_batches * batch_size
    if n < need:
        order = np.tile(order, -(-need // n))[:need]
    for b in range(num_batches):
        batch = dataset.take(order[b * batch_size:(b + 1) * batch_size])
        ins = batch.inputs
        if augment is not None:
            ins = augment(ins, rng)
        yield ins, batch.labels


class FixedDraws:
    """A generator's stand-in whose every draw is one value: the crops'
    corners and the flips cannot be reached by seeds alone."""

    def __init__(self, offset, coin):
        self.offset, self.coin = offset, coin

    def integers(self, low, high, size):
        assert low <= self.offset < high
        return np.full(size, self.offset)

    def random(self, size):
        return np.full(size, self.coin)


def _images(n, seed=0, h=32, w=32):
    return np.random.default_rng(seed).standard_normal(
        (n, h, w, 3)).astype(np.float32)


def _same_next_draw(a, b):
    assert a.bit_generator.state == b.bit_generator.state
    assert a.integers(1 << 30) == b.integers(1 << 30)


class TestCifarAugment:
    @pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
    @pytest.mark.parametrize("b", [1, 7, 512])
    def test_equals_pad_and_loop_oracle(self, seed, b):
        x = _images(b, seed=b)
        r_old, r_new = (np.random.default_rng(seed) for _ in range(2))
        want = oracle_augment(x, r_old)
        got = cifar_augment(x, r_new)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        assert got is not x and not np.shares_memory(got, x)
        _same_next_draw(r_old, r_new)

    @pytest.mark.parametrize("coin", [0.0, 0.99], ids=["flip", "noflip"])
    @pytest.mark.parametrize("offset", [0, 4, 8])
    def test_reflected_borders_and_flips(self, offset, coin):
        # offsets 0 and 8 read the whole reflected border on one side;
        # 4 is the identity crop
        x = _images(7, seed=3)
        want = oracle_augment(x, FixedDraws(offset, coin))
        got = cifar_augment(x, FixedDraws(offset, coin))
        assert got.tobytes() == want.tobytes()
        if offset == 4:
            same = x[:, :, ::-1] if coin < 0.5 else x
            assert got.tobytes() == np.ascontiguousarray(same).tobytes()

    def test_mixed_row_and_column_offsets(self):
        # rows and columns draw apart: a stand-in that answers 0 for the
        # rows and 8 for the columns (and the other way round)
        class Split(FixedDraws):
            def __init__(self, first, second):
                self.calls, self.offs, self.coin = 0, (first, second), 0.0

            def integers(self, low, high, size):
                self.calls += 1
                return np.full(size, self.offs[(self.calls - 1) % 2])

        x = _images(5, seed=4, h=32, w=24)
        for first, second in ((0, 8), (8, 0)):
            want = oracle_augment(x, Split(first, second))
            got = cifar_augment(x, Split(first, second))
            assert got.tobytes() == want.tobytes()

    @pytest.mark.parametrize("b", [1, 7, 512])
    def test_gathers_idx_into_out(self, b):
        """``idx`` and ``out``: the loader's form.  Same bytes as the
        oracle over the fancy-index copy, written where asked, and the
        data set untouched."""
        data = _images(64, seed=5)
        before = data.copy()
        idx = np.random.default_rng(6).integers(0, 64, size=b)
        r_old, r_new = (np.random.default_rng(11) for _ in range(2))
        want = oracle_augment(data[idx], r_old)
        buf = np.full((2, 3, b, 32, 32, 3), np.nan, np.float32)
        ret = cifar_augment(data, r_new, idx, buf[1, 2])
        assert np.shares_memory(ret, buf)
        assert buf[1, 2].tobytes() == want.tobytes()
        mask = np.ones((2, 3), bool)
        mask[1, 2] = False
        assert np.isnan(buf[mask]).all()
        assert data.tobytes() == before.tobytes()
        _same_next_draw(r_old, r_new)

    def test_transposed_data_set(self):
        # the real CIFAR loader's array is a transposed view (NCHW in
        # memory): same bytes out
        base = np.random.default_rng(8).standard_normal(
            (9, 3, 32, 32)).astype(np.float32)
        x = base.transpose(0, 2, 3, 1)
        assert not x.flags.c_contiguous
        want = oracle_augment(x, np.random.default_rng(2))
        got = cifar_augment(x, np.random.default_rng(2))
        assert got.tobytes() == want.tobytes()

    def test_refuses_what_it_cannot_write_in_one_pass(self):
        x = _images(4)
        with pytest.raises(IndexError):
            cifar_augment(x, np.random.default_rng(0), np.array([0, 4]))
        # an ``out`` whose pixels cannot be viewed as one flat run of
        # rows raises: it is never filled through a silent copy
        strided = np.empty((4, 33, 32, 3), np.float32)[:, :32]
        with pytest.raises(AttributeError):
            cifar_augment(x, np.random.default_rng(0), out=strided)


def _array_set(n):
    return ArrayDataset(_images(n, seed=n, h=8, w=8),
                        np.arange(n, dtype=np.int64))


def _dict_set(n):
    g = np.random.default_rng(n)
    ins = {"ids": g.integers(0, 1000, (n, 6)).astype(np.int32),
           "mask": g.integers(0, 2, (n, 6)).astype(np.int8)}
    return ArrayDataset(ins, np.arange(n, dtype=np.int32))


EPOCH_CASES = {
    "array-augment": (_array_set, cifar_augment, oracle_augment),
    "array-plain": (_array_set, None, None),
    "dict-plain": (_dict_set, None, None),
}


def _assert_batch_equal(got, want):
    (gx, gy), (wx, wy) = got, want
    assert gy.dtype == wy.dtype and gy.tobytes() == wy.tobytes()
    if isinstance(wx, dict):
        assert set(gx) == set(wx)
        pairs = [(gx[k], wx[k]) for k in wx]
    else:
        pairs = [(gx, wx)]
    for g, w in pairs:
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


class TestOnePassEpoch:
    @pytest.mark.parametrize("shuffle", [True, False])
    @pytest.mark.parametrize("n,batch", [(37, 8), (5, 8)],
                             ids=["whole", "wrapped"])
    @pytest.mark.parametrize("case", list(EPOCH_CASES))
    def test_iter_equals_old_epochs(self, case, n, batch, shuffle):
        """Two epochs through ``__iter__`` (the second starts from the
        generator state the first left) against the old ``take`` +
        augment epoch: every batch byte for byte, fresh arrays a batch,
        the generator in the same state."""
        make, augment, oracle = EPOCH_CASES[case]
        ds = make(n)
        dl = DataLoader(ds, batch, shuffle=shuffle, augment=augment,
                        seed=13)
        rng = np.random.default_rng(13)
        seen = []
        for _ in range(2):
            got = list(dl)
            want = list(oracle_epoch(ds, batch, shuffle, oracle, rng))
            assert len(got) == len(want) == len(dl)
            for g, w in zip(got, want):
                _assert_batch_equal(g, w)
            seen += got
        _same_next_draw(dl._rng, rng)
        firsts = [next(iter(b[0].values())) if isinstance(b[0], dict)
                  else b[0] for b in seen]
        for i, a in enumerate(firsts):
            assert not any(np.shares_memory(a, o) for o in firsts[i + 1:])

    @pytest.mark.parametrize("n,batch", [(37, 8), (5, 8)],
                             ids=["whole", "wrapped"])
    @pytest.mark.parametrize("case", list(EPOCH_CASES))
    def test_fill_writes_the_same_batches_into_slots(self, case, n, batch):
        """``Epoch.fill`` into the slots of one ``(2, k, ...)`` array —
        the step's batch — gives what ``__iter__`` yields for the same
        seed, labels cast to the array's dtype, ``StopIteration`` at the
        epoch's end with nothing written."""
        make, augment, _ = EPOCH_CASES[case]
        twin_a, twin_b = (DataLoader(make(n), batch, augment=augment,
                                     seed=5) for _ in range(2))
        want = list(twin_a)
        k = len(want)
        out_x, out_y = twin_b.empty((2, k), label_dtype=np.int32)
        epoch = iter(twin_b)
        for m in range(k):
            epoch.fill(out_x, out_y, (1, m))
        before = out_y.copy()
        with pytest.raises(StopIteration):
            epoch.fill(out_x, out_y, (0, 0))
        assert out_y.dtype == np.int32
        np.testing.assert_array_equal(out_y, before)
        for m, (wx, wy) in enumerate(want):
            np.testing.assert_array_equal(out_y[1, m], wy)
            if isinstance(wx, dict):
                for key in wx:
                    assert out_x[key].dtype == wx[key].dtype
                    assert out_x[key][1, m].tobytes() == wx[key].tobytes()
            else:
                assert out_x.dtype == wx.dtype
                assert out_x[1, m].tobytes() == wx.tobytes()
        _same_next_draw(twin_a._rng, twin_b._rng)

    def test_shuffle_is_drawn_at_the_first_batch(self):
        # two live epochs of one loader share its generator (the mesh
        # path's padded columns do this): the draw order is the old
        # generator function's, at the first batch and not at iter()
        ds = _array_set(24)
        dl = DataLoader(ds, 8, augment=cifar_augment, seed=3)
        rng = np.random.default_rng(3)
        a, b = iter(dl), iter(dl)
        old_a = oracle_epoch(ds, 8, True, oracle_augment, rng)
        old_b = oracle_epoch(ds, 8, True, oracle_augment, rng)
        for new, old in ((a, old_a), (a, old_a), (b, old_b), (a, old_a),
                         (b, old_b)):
            _assert_batch_equal(next(new), next(old))
        _same_next_draw(dl._rng, rng)

    def _planes(self, n=20):
        """The CIFAR reader's array: planes in memory, NHWC by strides."""
        base = np.random.default_rng(1).standard_normal(
            (n, 3, 8, 8)).astype(np.float32)
        return ArrayDataset(base.transpose(0, 2, 3, 1), np.arange(n))

    def test_augmented_set_is_made_contiguous_once(self):
        # ``np.take`` would copy a strided source whole in every batch:
        # an augmented loader holds a C-contiguous copy, made at
        # construction
        ds = self._planes()
        dl = DataLoader(ds, 4, augment=cifar_augment, seed=2)
        assert dl.dataset.inputs.flags.c_contiguous
        assert dl.dataset.labels is ds.labels
        want = oracle_epoch(ds, 4, True, oracle_augment,
                            np.random.default_rng(2))
        for g, w in zip(dl, want):
            assert g[0].flags.c_contiguous
            _assert_batch_equal(g, w)

    def test_plain_set_keeps_its_memory_order(self):
        """Without augmentation nothing is reordered: the set is not
        copied, and a batch (fresh or a slot) has the strides fancy
        indexing gave it, which is what the device upload is handed."""
        ds = self._planes()
        dl = DataLoader(ds, 4, seed=2)
        assert dl.dataset.inputs is ds.inputs
        want = list(oracle_epoch(ds, 4, True, None,
                                 np.random.default_rng(2)))
        for g, w in zip(dl, want):
            assert g[0].strides == w[0].strides
            assert not g[0].flags.c_contiguous
            _assert_batch_equal(g, w)
        twin = DataLoader(ds, 4, seed=2)
        out_x, out_y = twin.empty((2, 5))
        assert out_x.shape == (2, 5, 4, 8, 8, 3)
        assert out_x.transpose(0, 1, 2, 5, 3, 4).flags.c_contiguous
        epoch = iter(twin)
        for m in range(5):
            epoch.fill(out_x, out_y, (1, m))
            assert out_x[1, m].tobytes() == want[m][0].tobytes()

    def test_set_contiguous_in_no_order_is_copied_once(self):
        # the causal-LM reader's ``ids[:, :-1]``: rows with a gap
        ids = np.arange(21 * 9, dtype=np.int32).reshape(21, 9)
        ds = ArrayDataset(ids[:, :-1], ids[:, 1:])
        dl = DataLoader(ds, 4, seed=3)
        assert dl.dataset.inputs.flags.c_contiguous
        want = oracle_epoch(ds, 4, True, None, np.random.default_rng(3))
        for g, w in zip(dl, want):
            _assert_batch_equal(g, w)

    def test_contiguous_set_is_not_copied(self):
        ds = _array_set(12)
        assert DataLoader(ds, 4).dataset.inputs is ds.inputs
        assert DataLoader(ds, 4, augment=cifar_augment
                          ).dataset.inputs is ds.inputs
        dd = _dict_set(12)
        held = DataLoader(dd, 4).dataset.inputs
        assert all(held[k] is dd.inputs[k] for k in dd.inputs)

    def test_make_data_loader_augments_cifar_train_only(self):
        train = make_data_loader("CIFAR10", 16, train=True,
                                 synthetic_size=64)
        test = make_data_loader("CIFAR10", 16, train=False,
                                synthetic_size=64)
        assert train.augment is cifar_augment and test.augment is None
        x, y = next(iter(train))
        assert x.shape == (16, 32, 32, 3) and x.dtype == np.float32
        assert y.shape == (16,)


class TestMFCC:
    def test_shape_parity_one_second_clip(self):
        # 1 s @ 16 kHz, 25 ms / 10 ms frames -> 98 frames, 40 coeffs —
        # the reference's (40, 98) KWT input (KWT_SPEECHCOMMANDS.py:34-35)
        sig = np.sin(2 * np.pi * 440 * np.arange(16000) / 16000)
        m = compute_mfcc(sig)
        assert m.shape == (40, 98)
        assert np.all(np.isfinite(m))

    def test_filterbank_partition(self):
        fb = mel_filterbank(64, 512, 16000)
        assert fb.shape == (64, 257)
        assert fb.min() >= 0 and fb.max() <= 1.0

    def test_distinguishes_frequencies(self):
        t = np.arange(16000) / 16000
        lo = compute_mfcc(np.sin(2 * np.pi * 200 * t))
        hi = compute_mfcc(np.sin(2 * np.pi * 4000 * t))
        assert np.abs(lo - hi).mean() > 0.1


class TestProviders:
    @pytest.mark.parametrize("name,shape,n_classes", [
        ("CIFAR10", (32, 32, 3), 10),
        ("MNIST", (28, 28, 1), 10),
        ("SPEECHCOMMANDS", (40, 98), 10),
    ])
    def test_image_like_shapes(self, name, shape, n_classes):
        ds = get_dataset(name, train=True, synthetic_size=64)
        assert ds.inputs.shape[1:] == shape
        assert ds.labels.max() < n_classes

    def test_agnews_token_shape(self):
        ds = get_dataset("AGNEWS", train=True, synthetic_size=32)
        assert ds.inputs.shape == (32, 128)
        assert ds.inputs.dtype == np.int32
        assert ds.labels.max() < 4

    def test_make_data_loader_with_distribution(self):
        counts = np.array([8, 0, 8, 0, 0, 0, 0, 0, 0, 0])
        dl = make_data_loader("CIFAR10", batch_size=8, distribution=counts,
                              synthetic_size=256, seed=1)
        assert dl.dataset.labels.tolist().count(1) == 0
        assert len(dl.dataset) == 16

    def test_synthetic_train_test_disjoint_seeds(self):
        tr = get_dataset("CIFAR10", train=True, synthetic_size=32)
        te = get_dataset("CIFAR10", train=False, synthetic_size=32)
        assert not np.array_equal(tr.inputs[:8], te.inputs[:8])


class TestVocabPlumbing:
    """A model with overridden vocab_size must draw in-range token ids —
    out-of-range ids NaN-fill in nn.Embed (the bug: tiny-vocab llama
    YAMLs failed every round with 'NaN detected')."""

    def test_tinystories_vocab_kwarg_bounds_ids(self):
        from split_learning_tpu.data import get_dataset
        ds = get_dataset("TINYSTORIES", train=True, synthetic_size=16,
                         vocab=128)
        assert int(np.max(ds.inputs)) < 128
        assert int(np.max(ds.labels)) < 128

    def test_dataset_kwargs_for_model(self):
        from split_learning_tpu.runtime.validation import (
            dataset_kwargs_for_model,
        )
        assert dataset_kwargs_for_model(
            "TinyLlama_TINYSTORIES", {"vocab_size": 128}) == {"vocab": 128}
        assert dataset_kwargs_for_model(
            "BERT_AGNEWS", {"vocab_size": 99}) == {"vocab": 99}
        # image models and default-vocab models get no override
        assert dataset_kwargs_for_model("VGG16_CIFAR10",
                                        {"dtype": "x"}) == {}
        assert dataset_kwargs_for_model("TinyLlama_TINYSTORIES", {}) == {}

    def test_loader_threads_dataset_kwargs(self):
        from split_learning_tpu.data import make_data_loader
        ld = make_data_loader("TINYSTORIES", 4, train=True,
                              synthetic_size=16,
                              dataset_kwargs={"vocab": 64})
        x, y = next(iter(ld))
        assert int(np.max(x)) < 64 and int(np.max(y)) < 64


class TestSubsetSeeds:
    """Per-client subset seeding + the reference's
    ``data-distribution.refresh`` semantics (``src/RpcClient.py:108``)."""

    def _subset(self, seed):
        from split_learning_tpu.data import make_data_loader
        ld = make_data_loader("SPEECHCOMMANDS", 4, train=True, seed=seed,
                              distribution=np.full(10, 4),
                              synthetic_size=400)
        return np.asarray(ld.dataset.inputs)

    def test_identical_counts_distinct_clients_distinct_subsets(self):
        from split_learning_tpu.data import subset_seed
        a = self._subset(subset_seed(0, "client_1_0"))
        b = self._subset(subset_seed(0, "client_1_1"))
        assert a.shape == b.shape
        assert not np.array_equal(a, b), (
            "two clients with the same label counts drew the SAME subset")
        # deterministic across calls (reproducible deployments)
        np.testing.assert_array_equal(
            a, self._subset(subset_seed(0, "client_1_0")))

    def test_refresh_resamples_per_round(self):
        from split_learning_tpu.data import subset_seed
        frozen = [subset_seed(0, "c", r, refresh=False) for r in range(3)]
        fresh = [subset_seed(0, "c", r, refresh=True) for r in range(3)]
        assert len(set(frozen)) == 1            # same subset all rounds
        assert len(set(fresh)) == 3             # re-sampled each round
        a, b = self._subset(fresh[0]), self._subset(fresh[1])
        assert not np.array_equal(a, b)

    def test_mesh_loader_honors_refresh(self, tmp_path):
        from split_learning_tpu.config import from_dict
        from split_learning_tpu.runtime.context import MeshContext

        def ctx(refresh):
            return MeshContext(from_dict(dict(
                model="KWT", dataset="SPEECHCOMMANDS", clients=[1, 1],
                synthetic_size=400, compute_dtype="float32",
                model_kwargs={"embed_dim": 16, "num_heads": 2,
                              "mlp_dim": 32},
                learning={"batch_size": 4},
                distribution={"num_samples": 40, "refresh": refresh},
                log_path=str(tmp_path))))

        counts = np.full(10, 4)
        c = ctx(False)
        assert c._loader("c0", counts, 0) is c._loader("c0", counts, 1)
        c = ctx(True)
        l0, l1 = c._loader("c0", counts, 0), c._loader("c0", counts, 1)
        assert l0 is not l1
        assert not np.array_equal(np.asarray(l0.dataset.inputs),
                                  np.asarray(l1.dataset.inputs))

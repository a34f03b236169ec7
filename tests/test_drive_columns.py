"""``MeshContext._drive_columns``' host feed: the step's batch is
assembled in one pass into an array allocated once a step, and it is the
batch the old assembly (``M`` ``next`` calls a column, two ``np.stack``)
built for the same seed; an array handed to the step is never written
again."""

import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.data import ArrayDataset, DataLoader, cifar_augment
from split_learning_tpu.runtime.context import MeshContext
from split_learning_tpu.runtime.spans import Laps

MB = 4


def old_assembly(loaders, M, epochs):
    """The feed as ``_drive_columns`` built it before the one-pass fill:
    every step's ``(x_h, labels_h)``."""
    steps_per_epoch = max(1, min(len(ld) for ld in loaders) // M)
    out = []
    for _ in range(epochs):
        iters = [iter(ld) for ld in loaders]
        for _ in range(steps_per_epoch):
            xs, ys = [], []
            for it_i, it in enumerate(iters):
                bx, by = [], []
                for _ in range(M):
                    try:
                        b = next(it)
                    except StopIteration:
                        it = iters[it_i] = iter(loaders[it_i])
                        b = next(it)
                    bx.append(np.asarray(b[0]))
                    by.append(np.asarray(b[1]))
                xs.append(np.stack(bx))
                ys.append(np.stack(by))
            out.append((np.stack(xs), np.stack(ys).astype(np.int32)))
    return out


def _images(n, seed):
    g = np.random.default_rng(seed)
    return ArrayDataset(g.standard_normal((n, 8, 8, 3)).astype(np.float32),
                        g.integers(0, 10, n))          # int64 labels


def _tokens(n, seed):
    g = np.random.default_rng(seed)
    return ArrayDataset(g.integers(0, 30000, (n, 16)).astype(np.int32),
                        g.integers(0, 4, n).astype(np.int32))


def _planes(n, seed):
    """Images as the CIFAR reader holds them: planes in memory, NHWC by
    strides; fed without augmentation they keep that order."""
    g = np.random.default_rng(seed)
    return ArrayDataset(
        g.standard_normal((n, 3, 8, 8)).astype(np.float32).transpose(
            0, 2, 3, 1), g.integers(0, 10, n))


KINDS = {"images": (_images, cifar_augment), "tokens": (_tokens, None),
         "planes": (_planes, None)}


def _columns(sizes, kind):
    """One loader a size; equal sizes at the same position of two calls
    give twins (same data, same seed)."""
    make, augment = KINDS[kind]
    return [DataLoader(make(n, seed=100 + i), MB, augment=augment,
                       seed=7 + i) for i, n in enumerate(sizes)]


class RecordingStep:
    """Stands in for the compiled step: keeps every ``x`` and ``labels``
    it was handed, and a copy of each as they were at the call."""

    def __init__(self, columns):
        self.columns, self.calls = columns, []

    def __call__(self, params_c, opt_c, stats_c, x, labels, rngs):
        self.calls.append((x, labels, np.array(x), np.array(labels)))
        loss = jnp.full((self.columns,), float(len(self.calls)))
        return params_c, opt_c, stats_c, loss


class Context:
    """What ``_drive_columns`` reads of its context."""
    STEPS_AHEAD = MeshContext.STEPS_AHEAD


def _drive(loaders, M, epochs, step):
    return MeshContext._drive_columns(
        Context, step, loaders, len(loaders), M, MB, epochs, 0,
        {"w": jnp.zeros(1)}, {}, {}, Laps(None))


CASES = {
    # sizes of the columns' data sets (batches = size // 4), M, epochs
    "one-column": ((48,), 3, 1),
    "unequal-columns": ((40, 28), 3, 2),
    "restart-mid-step": ((8, 20), 3, 2),       # 2 batches < M draws
    "wrapped-set": ((3, 12), 2, 1),            # a set smaller than a batch
}


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("case", list(CASES))
def test_steps_get_the_old_assemblys_batches(case, kind):
    sizes, M, epochs = CASES[case]
    want = old_assembly(_columns(sizes, kind), M, epochs)
    loaders = _columns(sizes, kind)
    step = RecordingStep(len(sizes))
    *_, loss_h, consumed = _drive(loaders, M, epochs, step)
    assert len(step.calls) == len(want) == int(loss_h[0])
    for (x, labels, _, _), (wx, wy) in zip(step.calls, want):
        assert x.shape == wx.shape == (len(sizes), M, MB) + wx.shape[3:]
        assert x.dtype == wx.dtype and labels.dtype == np.int32
        assert np.asarray(x).tobytes() == wx.tobytes()
        assert np.asarray(labels).tobytes() == wy.tobytes()
    # data_count semantics: redraws of a restarted loader are not counted
    steps = len(want) // epochs
    assert consumed.tolist() == [
        epochs * min(steps * M * MB, ld.samples_per_epoch, len(ld.dataset))
        for ld in loaders]


def test_padded_columns_share_one_loader():
    """The host path pads a short chunk with its last client's loader:
    two live epochs draw from one generator, in the old order."""
    def cols():
        a, b = _columns((24, 24), "images")
        return [a, b, b]
    want = old_assembly(cols(), 2, 2)
    step = RecordingStep(3)
    _drive(cols(), 2, 2, step)
    assert len(step.calls) == len(want)
    for (x, labels, _, _), (wx, wy) in zip(step.calls, want):
        assert np.asarray(x).tobytes() == wx.tobytes()
        assert np.asarray(labels).tobytes() == wy.tobytes()


def test_loaders_end_in_the_old_generator_state():
    old, new = _columns((8, 20), "images"), _columns((8, 20), "images")
    old_assembly(old, 3, 2)
    _drive(new, 3, 2, RecordingStep(2))
    for a, b in zip(old, new):
        assert a._rng.bit_generator.state == b._rng.bit_generator.state


class AliasingJnp:
    """``jax.numpy`` whose ``asarray`` hands back the numpy array itself:
    the aliasing the CPU backend does when an array happens to be
    aligned, made certain."""

    asarray = staticmethod(lambda a: a)

    def __getattr__(self, name):
        return getattr(jnp, name)


@pytest.mark.parametrize("alias", ["certain", "backend"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_fed_array_is_never_written_again(kind, alias, monkeypatch):
    """On the CPU backend ``jnp.asarray`` may alias an aligned numpy
    array: a buffer that is filled again for a later step would change
    what an earlier step was handed.  After the last step every array is
    what it was at its call, and no two steps share memory."""
    if alias == "certain":
        from split_learning_tpu.runtime import context
        monkeypatch.setattr(context, "jnp", AliasingJnp())
    step = RecordingStep(2)
    _drive(_columns((64, 64), kind), 2, 2, step)
    assert len(step.calls) == 16
    for x, labels, x_then, labels_then in step.calls:
        assert np.asarray(x).tobytes() == x_then.tobytes()
        assert np.asarray(labels).tobytes() == labels_then.tobytes()
    if alias == "certain":
        fed = [a for c in step.calls for a in c[:2]]
        assert all(isinstance(a, np.ndarray) for a in fed)
        for i, a in enumerate(fed):
            assert not any(np.shares_memory(a, b) for b in fed[i + 1:])


def test_host_lead_is_held_to_steps_ahead():
    """Batch k is uploaded only when step ``k - STEPS_AHEAD - 1`` has
    finished: the device holds ``STEPS_AHEAD + 1`` batches at most,
    however fast the feed is."""
    ahead = MeshContext.STEPS_AHEAD
    assert ahead >= 1
    waited, uploaded_with = [], []

    class Loss:
        def __init__(self, i):
            self.i = i

        def block_until_ready(self):
            waited.append(self.i)

        def __array__(self, dtype=None, copy=None):
            return np.zeros(1)

    def step(params_c, opt_c, stats_c, x, labels, rngs):
        uploaded_with.append(list(waited))
        return params_c, opt_c, stats_c, Loss(len(uploaded_with) - 1)

    _drive(_columns((48,), "images"), 2, 2, step)
    assert len(uploaded_with) == 12
    for k, seen in enumerate(uploaded_with):
        assert seen == list(range(max(0, k - ahead)))

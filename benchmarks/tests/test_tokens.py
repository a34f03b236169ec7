"""What lets the harness take a causal language model as data: the
``tokens`` data set kind, cross-entropy over every labelled position, a
term of the objective that a module sows (``extra_objective``), and a
``follow`` that keeps its state on the host and still gives the numbers
the parent's gave.  Fast, on the CPU; each claim its own case."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import compare
import traffic

SEED = 2_100_000_007


# -- _ce ------------------------------------------------------------------------

def _old_ce(logits, labels):
    """The parent's expression, for logits (rows, classes)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ce_of_a_classifier_is_the_parents_bit_for_bit(dtype):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(37, 10)) * 4, dtype)
    labels = jnp.asarray(rng.integers(0, 10, size=37), jnp.int32)
    assert np.array_equal(np.asarray(compare._ce(logits, labels)),
                          np.asarray(_old_ce(logits, labels)))
    assert np.array_equal(
        np.asarray(jax.jit(compare._ce)(logits, labels)),
        np.asarray(jax.jit(_old_ce)(logits, labels)))


def test_ce_of_every_position_is_optaxs():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(3, 7, 33)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 33, size=(3, 7)), jnp.int32)
    got = compare._ce(logits, labels)
    assert got.shape == (3, 7)
    np.testing.assert_allclose(
        got, optax.softmax_cross_entropy_with_integer_labels(logits, labels),
        rtol=1e-6, atol=1e-6)


# -- the data set kinds -----------------------------------------------------------

def _digest(root, val):
    h = hashlib.sha256()
    for f in sorted(p for p in root.rglob("*") if p.is_file()):
        h.update(f.name.encode())
        h.update(f.read_bytes())
    h.update(val[0].tobytes())
    h.update(val[1].tobytes())
    return h.hexdigest()


# the digests were taken from the parent's generator (PR 27's tree)
@pytest.mark.parametrize("kind,kw,digest", [
    ("cifar10", {},
     "9a590ce3708945f2250c7eb295ea18c3967738d55113e5e51ac76f3fe66d5884"),
    ("agnews", {"vocab": 2048},
     "311a05e0ad2aead1f8359f4dc419a6c3d6820d21c22b48d0616e18322fad80eb")])
def test_the_older_kinds_write_byte_for_byte_what_they_wrote(kind, kw, digest,
                                                             tmp_path):
    val = traffic.make_dataset(kind, tmp_path, SEED, 40, 12, **kw)
    assert _digest(tmp_path, val) == digest
    # a sequence length is of no concern to them
    again = tmp_path / "again"
    val = traffic.make_dataset(kind, again, SEED, 40, 12, seq_len=99, **kw)
    assert _digest(again, val) == digest


def _tokens(root, seed=SEED, vocab=512, seq_len=48, n_train=20, n_val=6):
    return traffic.make_dataset("tokens", root, seed, n_train, n_val,
                                vocab=vocab, seq_len=seq_len)


def test_tokens_is_a_function_of_the_seed(tmp_path):
    a = _tokens(tmp_path / "a")
    b = _tokens(tmp_path / "b")
    c = _tokens(tmp_path / "c", seed=SEED + 1)
    assert _digest(tmp_path / "a", a) == _digest(tmp_path / "b", b)
    assert _digest(tmp_path / "a", a) != _digest(tmp_path / "c", c)


@pytest.mark.parametrize("vocab", [64, 512, 32000])
def test_tokens_ids_lie_inside_the_vocabulary(vocab, tmp_path):
    x, y = _tokens(tmp_path, vocab=vocab, seq_len=256, n_train=40)
    train = np.load(tmp_path / "TinyStories" / "train.npy")
    valid = np.load(tmp_path / "TinyStories" / "valid.npy")
    assert train.dtype == valid.dtype == np.int32
    assert train.shape == (40, 257) and valid.shape == (6, 257)
    for ids in (train, valid):
        assert ids.min() >= 0 and ids.max() < vocab
    assert x.shape == y.shape == (6, 256)
    assert np.array_equal(x[:, 1:], y[:, :-1])      # labels: the next token


def test_tokens_stream_is_packed_documents_with_a_skew():
    ids = traffic.token_stream(np.random.default_rng(SEED), 2_000_000, 32000)
    ends = np.flatnonzero(ids == traffic.EOD_ID)
    lengths = np.diff(ends)
    assert 450 < np.median(lengths) < 750           # log-normal, median 600
    assert (lengths > 2048).mean() > 0.05           # a tail past a row
    counts = np.sort(np.bincount(ids, minlength=32000))[::-1]
    # Zipf-like: a hundred of the 32,000 ids carry an eighth of the stream
    assert counts[:100].sum() > 0.1 * len(ids)
    assert (counts > 0).sum() > 16000               # and the tail is used
    # inside a document most ids come from its band of the vocabulary
    doc = ids[ends[3] + 1:ends[4]]
    band = (32000 - 1) // traffic.TOPIC_BANDS
    lo = np.bincount(doc // band).argmax() * band
    assert ((doc >= lo - band) & (doc < lo + 2 * band)).mean() > 0.6


def test_the_programs_provider_reads_back_the_returned_rows(tmp_path,
                                                            monkeypatch):
    x, y = _tokens(tmp_path)
    monkeypatch.setenv("SLT_DATA_DIR", str(tmp_path))
    from split_learning_tpu.data import datasets
    val = datasets.tinystories(train=False, vocab=512)
    assert np.array_equal(val.inputs, x) and np.array_equal(val.labels, y)
    train = datasets.tinystories(train=True, vocab=512)
    assert train.inputs.shape == train.labels.shape == (20, 48)


def test_tokens_without_a_vocabulary_names_the_key(tmp_path):
    with pytest.raises(ValueError, match="model-kwargs.vocab_size"):
        traffic.make_dataset("tokens", tmp_path, SEED, 4, 2, seq_len=16)


def test_tokens_without_a_sequence_length_names_the_key(tmp_path):
    with pytest.raises(ValueError, match="seq-len"):
        traffic.make_dataset("tokens", tmp_path, SEED, 4, 2, vocab=64)


def test_an_unknown_kind_raises(tmp_path):
    with pytest.raises(ValueError, match="unknown dataset kind"):
        traffic.make_dataset("imagenet", tmp_path, SEED, 4, 2)


# -- follow ------------------------------------------------------------------------
# The parent's three steps, copied from PR 27's benchmarks/compare.py: at
# its peak the device held the starting parameters, the current ones, two
# gradients and the optimizer's state.

@compare.functools.lru_cache(maxsize=8)
def _old_grad_fn(ref, cast, denom):
    def loss_sum(params, stats, x, labels, key):
        logits = ref.forward(params, stats, x, train=True, key=key,
                             cast=cast)
        return _old_ce(logits, labels).sum() / denom
    return jax.jit(jax.value_and_grad(loss_sum))


def _old_microbatch_grad(ref, params, stats, x, labels, key, cast=None):
    block = getattr(ref, "ROW_BLOCK", None) or x.shape[0]
    fn = _old_grad_fn(ref, cast, x.shape[0])
    loss, grads = 0.0, None
    for lo in range(0, x.shape[0], block):
        l, g = fn(params, stats, x[lo:lo + block], labels[lo:lo + block],
                  key)
        loss = loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads


def _old_optimizer_step(learning, params, state, grads, t):
    lr = learning["learning-rate"]
    tm = jax.tree_util.tree_map
    b1, b2, eps = compare.ADAM_B1, compare.ADAM_B2, compare.ADAM_EPS
    if learning["optimizer"] == "sgd":
        mom = learning.get("momentum", 0.9)
        trace = grads if state is None else tm(
            lambda g, s: g + mom * s, grads, state)
        return tm(lambda p, s: p - lr * s, params, trace), trace
    wd = learning.get("weight-decay", 0.0)
    mu, nu = state or (tm(jnp.zeros_like, grads), tm(jnp.zeros_like, grads))
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = tm(lambda p, m, v: p - lr * (
        (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), params, mu, nu)
    return new, (mu, nu)


def _old_follow(ref, learning, params, stats, feed, cast=None, fault=None):
    p0, state, losses, g1 = params, None, [], None
    for t, (x, labels, key_data) in enumerate(feed, start=1):
        key = jax.random.wrap_key_data(jnp.asarray(key_data))
        n_mb = x.shape[0]
        if fault == "half_batch":
            n_mb = max(1, n_mb // 2)
        loss, grads = 0.0, None
        for m in range(n_mb):
            l, g = _old_microbatch_grad(
                ref, params, stats, jnp.asarray(x[m]),
                jnp.asarray(labels[m]), jax.random.fold_in(key, m), cast)
            loss = loss + l / n_mb
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)
        if g1 is None:
            g1 = compare.leaf_norms(grads)
        params, state = _old_optimizer_step(learning, params, state, grads,
                                            t)
        losses.append(float(loss))
    return {"losses": losses, "grad1": g1,
            "dparam": compare.diff_norms(params, p0)}


class Classifier:
    """Two leaves: rows (mb, 8) -> logits (mb, 5)."""

    @staticmethod
    def init(key):
        return {"w": 0.3 * jax.random.normal(key, (8, 5)),
                "b": jnp.zeros((5,))}, {}

    @staticmethod
    def forward(params, stats, x, *, train=False, key=None, cast=None):
        return jnp.tanh(x) @ params["w"] + params["b"]


class ClassifierInBlocks(Classifier):
    ROW_BLOCK = 2     # 3 blocks to a microbatch of 6: the outer sum parks


class TokenModel:
    """Two leaves: ids (mb, S) -> next-token logits (mb, S, 11)."""
    ROW_BLOCK = 1

    @staticmethod
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"emb": jax.random.normal(k1, (11, 6)),
                "out": 0.5 * jax.random.normal(k2, (6, 11))}, {}

    @staticmethod
    def forward(params, stats, ids, *, train=False, key=None, cast=None):
        x = params["emb"][ids]
        x = x + jnp.cumsum(x, axis=1) / (1 + jnp.arange(ids.shape[1]))[:, None]
        return jnp.tanh(x) @ params["out"]


class TokenModelWithATerm(TokenModel):
    @staticmethod
    def extra_objective(params, stats, ids, key, cast):
        # stands for a load-balance term: of the whole microbatch, and
        # not linear in its rows
        use = jax.nn.softmax(params["emb"][ids] @ params["out"]).mean((0, 1))
        return 0.5 * 11 * jnp.sum(jnp.square(use))


LEARNING = {"sgd": {"optimizer": "sgd", "learning-rate": 0.05,
                    "momentum": 0.9},
            "adamw": {"optimizer": "adamw", "learning-rate": 1e-3,
                      "weight-decay": 0.01}}


def _feed(shape, classes, per_position):
    rng = np.random.default_rng(5)
    out = []
    for t in range(compare.STEPS):
        x = rng.normal(size=shape).astype(np.float32) if not per_position \
            else rng.integers(0, classes, size=shape).astype(np.int32)
        y = rng.integers(0, classes, size=shape if per_position
                         else shape[:2]).astype(np.int32)
        out.append((x, y, np.asarray(jax.random.key_data(jax.random.key(t)))))
    return out


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
@pytest.mark.parametrize("ref", [Classifier, ClassifierInBlocks],
                         ids=["whole", "in_row_blocks"])
@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_follow_gives_the_parents_numbers(ref, optimizer, fault):
    params, stats = ref.init(jax.random.key(2))
    feed = _feed((4, 6, 8), 5, per_position=False)
    old = _old_follow(ref, LEARNING[optimizer], params, stats, feed,
                      fault=fault)
    new = compare.follow(ref, LEARNING[optimizer], jax.device_get(params),
                         stats, feed, fault=fault)
    assert new == old                # every loss and every leaf's norm


def test_follow_leaves_nothing_of_its_own_on_the_device():
    params, stats = Classifier.init(jax.random.key(2))
    host = jax.device_get(params)
    del params
    before = {id(a) for a in jax.live_arrays()}
    compare.follow(Classifier, LEARNING["adamw"], host, stats,
                   _feed((2, 6, 8), 5, per_position=False))
    left = [a for a in jax.live_arrays() if id(a) not in before
            and a.dtype == jnp.float32 and a.size > 1]
    assert not left, [(a.shape, a.dtype) for a in left]


def test_follow_over_positions_is_the_mean_over_every_label():
    params, stats = TokenModel.init(jax.random.key(3))
    feed = _feed((2, 3, 7), 11, per_position=True)
    got = compare.follow(TokenModel, LEARNING["sgd"], params, stats, feed)

    def mean_ce(p):
        x, y, _ = feed[0]
        logits = TokenModel.forward(p, stats, x.reshape(6, 7))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y.reshape(6, 7)).mean()
    loss, grads = jax.value_and_grad(mean_ce)(params)
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-5)
    want = compare.leaf_norms(grads)
    for leaf, norm in got["grad1"].items():
        assert norm == pytest.approx(want[leaf], rel=1e-4)


def test_validation_loss_is_the_mean_over_every_label():
    params, stats = TokenModel.init(jax.random.key(3))
    x, y, _ = _feed((1, 6, 7), 11, per_position=True)[0]
    got = compare.val_loss(TokenModel, params, stats, x[0], y[0], batch=4)
    want = optax.softmax_cross_entropy_with_integer_labels(
        TokenModel.forward(params, stats, x[0]), y[0]).mean()
    assert got == pytest.approx(float(want), rel=1e-5)


def test_a_sown_term_moves_the_gradient_and_not_the_reported_loss():
    params, stats = TokenModel.init(jax.random.key(3))
    feed = _feed((2, 3, 7), 11, per_position=True)
    plain = compare.follow(TokenModel, LEARNING["sgd"], params, stats,
                           feed[:1])
    sown = compare.follow(TokenModelWithATerm, LEARNING["sgd"], params,
                          stats, feed[:1])
    # the first loss is taken at the same weights: CE alone, both times
    assert sown["losses"][0] == pytest.approx(plain["losses"][0], rel=1e-6)
    assert any(abs(sown["grad1"][k] - plain["grad1"][k])
               > 1e-3 * plain["grad1"][k] for k in plain["grad1"])

    # and the gradient is that of CE plus the term, the microbatch whole
    def objective(p, m):
        x, y, key = feed[0]
        ce = optax.softmax_cross_entropy_with_integer_labels(
            TokenModel.forward(p, stats, x[m]), y[m]).mean()
        return ce + TokenModelWithATerm.extra_objective(p, stats, x[m],
                                                        None, None)
    grads = jax.tree_util.tree_map(
        lambda a, b: (a + b) / 2, *(jax.grad(objective)(params, m)
                                    for m in range(2)))
    want = compare.leaf_norms(grads)
    for leaf, norm in sown["grad1"].items():
        assert norm == pytest.approx(want[leaf], rel=1e-4)


def test_a_module_with_a_term_takes_its_microbatch_whole():
    assert compare._row_block(TokenModel, 4) == 1
    assert compare._row_block(TokenModelWithATerm, 4) == 4
    assert compare._row_block(Classifier, 4) == 4
    assert compare._row_block(ClassifierInBlocks, 5) == 2

"""End-to-end system tests: the paper's full pipeline on synthetic data.

disk shards -> chunked loader -> Pallas minhash preprocessing -> b-bit
signatures -> batch SVM + online SGD training -> accuracy; plus the
online-learning load-time accounting the paper's Table 4 reports.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Hash2U, lowest_bits
from repro.data import TINY, generate
from repro.data.pipeline import ChunkedLoader, make_sharded_dataset
from repro.kernels import batch_signatures
from repro.models.linear import (LinearModel, accuracy, make_loss_fn,
                                 sgd_svm_init, sgd_svm_step)
from repro.optim import adamw, constant
from repro.train import TrainState, Trainer, make_train_step, online_epochs


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("shards"))
    paths = make_sharded_dataset(TINY, d, n_shards=3, n=320)
    return paths


def test_full_pipeline_batch_learning(sharded):
    k, b, s = 128, 8, 16
    fam = Hash2U.create(jax.random.PRNGKey(0), k, s)
    loader = ChunkedLoader(sharded, chunk_size=64)

    sigs, labels = [], []
    for chunk in loader:                       # Pallas kernel preprocessing
        sigs.append(np.asarray(batch_signatures(chunk, fam, b=b)))
        labels.append(np.asarray(chunk.labels))
    sig = jnp.asarray(np.concatenate(sigs)).astype(jnp.uint32)
    y = jnp.asarray(np.concatenate(labels))
    n_train = int(sig.shape[0] * 0.75)

    loss = make_loss_fn("svm", "hashed", b, C=1.0)
    opt = adamw(constant(0.05))
    state = TrainState.create(LinearModel.create(k * 2**b), opt)
    step = make_train_step(lambda p, batch: loss(p, *batch), opt)
    state = Trainer(step).fit(
        state, lambda: iter([(sig[:n_train], y[:n_train])] * 100), 100)
    acc = float(accuracy(state.params, sig[n_train:], y[n_train:],
                         feature_kind="hashed", b=b))
    assert acc > 0.85, acc


def test_online_learning_with_load_accounting(sharded):
    """Online SGD over epochs re-loading from disk; hashed data loads
    faster than raw data (the paper's §6 claim, directionally)."""
    k, b, s = 64, 8, 16
    fam = Hash2U.create(jax.random.PRNGKey(1), k, s)

    # Preprocess once; "hashed dataset" is the signatures on disk (here:
    # in memory as a small array -- the size ratio is what matters).
    loader = ChunkedLoader(sharded, chunk_size=64)
    chunks = list(loader)
    sig_chunks = [(jnp.asarray(batch_signatures(c, fam, b=b)), c.labels)
                  for c in chunks]
    raw_bytes = sum(c.nbytes() for c in chunks)
    hashed_bytes = sum(int(s_.size) * (b // 8 or 1) for s_, _ in sig_chunks)
    assert hashed_bytes < raw_bytes / 4   # data reduction

    sgd_state = sgd_svm_init(k * 2**b)
    step = jax.jit(functools.partial(sgd_svm_step, lam=1e-4, eta0=0.5, b=b))

    def epoch_batches():
        for s_, y in sig_chunks:
            yield (s_, y)

    def sgd_wrap(state, batch):
        return step(state, batch[0], batch[1])

    final, times, _ = online_epochs(sgd_wrap, sgd_state, epoch_batches, 3)
    assert len(times) == 3
    assert all(t.train_s > 0 for t in times)


def test_preprocessing_deterministic_across_chunk_sizes(sharded):
    """Chunk size must not change signatures (paper Figs 1-3 sweep)."""
    fam = Hash2U.create(jax.random.PRNGKey(2), 32, 16)
    outs = []
    for cs in (32, 64, 256):
        loader = ChunkedLoader(sharded, chunk_size=cs)
        sigs = np.concatenate(
            [np.asarray(batch_signatures(c, fam, b=4)) for c in loader])
        outs.append(sigs)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])

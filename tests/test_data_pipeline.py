"""Batch iterator + public pool determinism (the hash-identified public
batch of the paper's communication-efficiency argument)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import (
    BatchIterator,
    DeviceData,
    PublicPool,
    dataset_nbytes,
    fits_on_device,
)
from repro.data.synthetic import make_synthetic_text, make_synthetic_vision


def test_batch_iterator_covers_epoch():
    arrays = {"x": np.arange(10), "labels": np.arange(10)}
    it = BatchIterator(arrays, np.arange(10), batch_size=5, seed=0)
    seen = np.concatenate([it.next()["x"], it.next()["x"]])
    assert sorted(seen.tolist()) == list(range(10))


def test_batch_iterator_wraps():
    arrays = {"x": np.arange(4)}
    it = BatchIterator(arrays, np.arange(4), batch_size=3, seed=0)
    for _ in range(5):
        b = it.next()
        assert b["x"].shape == (3,)


def test_empty_indices_raise():
    with pytest.raises(ValueError):
        BatchIterator({"x": np.arange(4)}, np.array([], dtype=int), 2)


def test_public_pool_deterministic_and_unlabeled():
    arrays = {"x": np.arange(100), "labels": np.arange(100)}
    pool = PublicPool(arrays, np.arange(50), batch_size=8, seed=3)
    b1 = pool.sample(7)
    b2 = pool.sample(7)
    np.testing.assert_array_equal(b1["x"], b2["x"])  # same step, same batch
    assert "labels" not in b1  # D_* is unlabeled
    b3 = pool.sample(8)
    assert not np.array_equal(b1["x"], b3["x"])


def test_synthetic_vision_learnable_structure():
    ds = make_synthetic_vision(num_labels=4, samples_per_label=20, noise=0.2)
    # same-class samples are closer than cross-class on average
    intra, inter = [], []
    for i in range(40):
        for j in range(i + 1, 40):
            d = np.linalg.norm(ds.images[i] - ds.images[j])
            (intra if ds.labels[i] == ds.labels[j] else inter).append(d)
    assert np.mean(intra) < 0.5 * np.mean(inter)


def test_synthetic_text_shapes():
    ds = make_synthetic_text(num_domains=3, sequences_per_domain=4,
                             seq_len=16, vocab_size=32)
    assert ds.tokens.shape == (12, 16)
    assert ds.tokens.max() < 32 and ds.tokens.min() >= 0


# ---------------------------------------------------------------------------
# the dataset on the device: the same batches, gathered there
# ---------------------------------------------------------------------------

def _image_arrays(n=23, seed=0):
    rng = np.random.default_rng(seed)
    return {"images": rng.standard_normal((n, 4, 4, 3)).astype(np.float32),
            "labels": rng.integers(0, 5, size=n)}


def _assert_same_batch(host, dev):
    assert set(host) == set(dev)
    for k in host:
        got = np.array(dev[k])
        assert got.dtype == jnp.asarray(host[k]).dtype, k  # int64 -> int32
        np.testing.assert_array_equal(got, host[k], strict=False)
        assert got.tobytes() == np.asarray(host[k], got.dtype).tobytes(), k


def test_device_batch_iterator_matches_host_over_two_epochs_and_restore():
    arrays = _image_arrays()
    idx = np.array([0, 2, 3, 5, 7, 11, 13, 17, 19, 22])
    host = BatchIterator(arrays, idx, batch_size=4, seed=7)
    dev = BatchIterator(DeviceData.put(arrays), idx, batch_size=4, seed=7)
    assert dev.resident and not host.resident
    for _ in range(6):  # 24 draws of 10 indices: two wraps, one mid-batch
        b = dev.next()
        assert isinstance(b["images"], jax.Array)
        _assert_same_batch(host.next(), b)
    state = dev.state_dict()
    assert {k: v for k, v in state.items() if k != "order"} == \
        {k: v for k, v in host.state_dict().items() if k != "order"}
    np.testing.assert_array_equal(state["order"], host.state_dict()["order"])
    host2 = BatchIterator(arrays, idx, batch_size=4, seed=0)
    dev2 = BatchIterator(DeviceData.put(arrays), idx, batch_size=4, seed=0)
    host2.load_state_dict(host.state_dict())
    dev2.load_state_dict(state)
    for _ in range(5):
        _assert_same_batch(host2.next(), dev2.next())


def test_device_public_pool_matches_host_and_drops_labels():
    arrays = _image_arrays()
    data = DeviceData.put(arrays)
    host = PublicPool(arrays, np.arange(3, 20), batch_size=6, seed=3)
    dev = PublicPool(data, np.arange(3, 20), batch_size=6, seed=3)
    assert dev.resident and "labels" not in dev.arrays.rows
    # the pool shares the dataset's buffers: nothing is uploaded twice
    assert dev.arrays.rows["images"] is data.rows["images"]
    for t in (0, 1, 7, 123, 10_000):
        b = dev.sample(t)
        assert "labels" not in b
        _assert_same_batch(host.sample(t), b)
        np.testing.assert_array_equal(dev.sample_ids(t), host.sample_ids(t))


def test_device_gather_compiles_once_per_batch_shape():
    from repro.data.pipeline import _take_rows

    arrays = _image_arrays(n=31, seed=1)  # shapes no other test uses
    before = _take_rows._cache_size()
    it = BatchIterator(DeviceData.put(arrays), np.arange(31), 5, seed=2)
    pool = PublicPool(DeviceData.put(arrays), np.arange(31), 9, seed=2)
    for t in range(20):
        it.next()
        pool.sample(t)
    assert _take_rows._cache_size() - before == 2  # one per batch shape


def test_device_data_keeps_rows_and_refuses_indices_out_of_range():
    arrays = {"tokens": np.arange(24, dtype=np.int32).reshape(6, 4),
              "images": np.zeros((6, 2, 3, 5), np.float64),
              "labels": np.arange(6)}
    data = DeviceData.put(arrays)
    assert {k: v.shape for k, v in data.rows.items()} == \
        {"tokens": (6, 4), "images": (6, 30), "labels": (6,)}
    assert data.take(np.array([5, 0]))["images"].shape == (2, 2, 3, 5)
    assert data.take(np.array([5, 0]))["images"].dtype == jnp.float32
    assert dataset_nbytes(arrays) == 24 * 4 + 180 * 4 + 6 * 4
    with pytest.raises(IndexError):
        BatchIterator(data, np.array([0, 6]), 2)
    with pytest.raises(IndexError):
        PublicPool(data, np.array([-1, 2]), 2)


@pytest.mark.parametrize("limit,in_use,fits,over", [
    (None, None, 10**15, None),  # the CPU reports no limit
    (4000, 0, 400, 401),
    (4000, 2000, 200, 201),  # under a tenth of the limit, over the free
])
def test_fits_on_device_is_a_share_of_the_free_memory(monkeypatch, limit,
                                                      in_use, fits, over):
    import repro.data.pipeline as pipeline

    if limit is not None:
        monkeypatch.setattr(pipeline, "device_memory_stats",
                            lambda: {"bytes_limit": limit,
                                     "bytes_in_use": in_use})
        assert pipeline.device_memory_free() == limit - in_use
    else:
        assert pipeline.device_memory_free() is None
    assert pipeline.RESIDENT_SHARE == 0.1
    assert fits_on_device(fits)
    assert over is None or not fits_on_device(over)


# ---------------------------------------------------------------------------
# the host gather: a large batch is copied by several threads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [2, 3, 32, 45])
def test_host_take_in_threads_is_the_numpy_gather(rows):
    from repro.data.pipeline import PARALLEL_GATHER_BYTES, host_take

    rng = np.random.default_rng(rows)
    row = PARALLEL_GATHER_BYTES // 4 // 2 + 7  # two rows pass the threshold
    v = rng.standard_normal((50, row)).astype(np.float32)
    sel = rng.integers(-50, 50, size=rows)  # negatives index from the end
    got = host_take(v, sel)
    assert got.flags.c_contiguous and got.dtype == v.dtype
    assert got.tobytes() == v[sel].tobytes()
    for bad in (50, -51):
        with pytest.raises(IndexError):
            host_take(v, np.r_[sel, bad])


def test_host_take_below_the_threshold_is_numpys_own():
    from repro.data.pipeline import host_take

    v = np.arange(12).reshape(6, 2)
    np.testing.assert_array_equal(host_take(v, [5, 0, 5]), v[[5, 0, 5]])
    with pytest.raises(IndexError):
        host_take(v, [6, 0])

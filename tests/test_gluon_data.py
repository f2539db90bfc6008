"""gluon.data tests (reference model: tests/python/unittest/
test_gluon_data.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon import data as gdata
from incubator_mxnet_tpu.gluon.data.vision import transforms


def test_array_dataset_and_loader():
    X = np.random.randn(10, 3).astype(np.float32)
    y = np.arange(10, dtype=np.float32)
    ds = gdata.ArrayDataset(X, y)
    assert len(ds) == 10
    x0, y0 = ds[0]
    np.testing.assert_allclose(x0, X[0])

    loader = gdata.DataLoader(ds, batch_size=4)
    batches = list(loader)
    assert len(batches) == 3
    xb, yb = batches[0]
    assert xb.shape == (4, 3)
    assert batches[-1][0].shape == (2, 3)


def test_loader_discard_and_shuffle():
    ds = gdata.ArrayDataset(np.arange(10, dtype=np.float32))
    loader = gdata.DataLoader(ds, batch_size=4, last_batch="discard",
                              shuffle=True)
    batches = list(loader)
    assert len(batches) == 2
    seen = np.concatenate([b.asnumpy() for b in batches])
    assert len(set(seen.tolist())) == 8


def test_loader_num_workers():
    ds = gdata.ArrayDataset(np.arange(32, dtype=np.float32))
    loader = gdata.DataLoader(ds, batch_size=8, num_workers=2)
    batches = list(loader)
    assert len(batches) == 4
    all_vals = sorted(np.concatenate([b.asnumpy() for b in batches]))
    np.testing.assert_allclose(all_vals, np.arange(32))


def test_samplers():
    s = gdata.SequentialSampler(5)
    assert list(s) == [0, 1, 2, 3, 4]
    rs = gdata.RandomSampler(100)
    idx = list(rs)
    assert sorted(idx) == list(range(100))
    bs = gdata.BatchSampler(gdata.SequentialSampler(7), 3, "rollover")
    assert list(bs) == [[0, 1, 2], [3, 4, 5]]
    assert list(bs)[0] == [6, 0, 1]  # rolled over


def test_dataset_transform_and_shard():
    ds = gdata.ArrayDataset(np.arange(10, dtype=np.float32))
    ds2 = ds.transform(lambda x: x * 2)
    assert ds2[3] == 6.0
    sh = ds.shard(3, 0)
    assert len(sh) == 4  # 10 = 4+3+3


def test_transforms_totensor_normalize():
    img = mx.nd.array(np.random.randint(0, 255, (8, 6, 3)), dtype=np.uint8)
    t = transforms.ToTensor()(img)
    assert t.shape == (3, 8, 6)
    assert t.dtype == np.float32
    assert float(t.max().asscalar()) <= 1.0
    n = transforms.Normalize(mean=(0.5, 0.5, 0.5), std=(0.25, 0.5, 1.0))(t)
    assert n.shape == (3, 8, 6)


def test_transforms_resize_crop_flip():
    img = mx.nd.array(np.random.randint(0, 255, (10, 8, 3)),
                      dtype=np.uint8)
    r = transforms.Resize((4, 5))(img)   # (w, h)
    assert r.shape == (5, 4, 3)
    c = transforms.CenterCrop(4)(img)
    assert c.shape == (4, 4, 3)
    rrc = transforms.RandomResizedCrop(6)(img)
    assert rrc.shape == (6, 6, 3)
    f = transforms.RandomFlipLeftRight()(img)
    assert f.shape == img.shape


def test_compose_pipeline():
    aug = transforms.Compose([
        transforms.Resize((8, 8)),
        transforms.ToTensor(),
        transforms.Normalize(0.5, 0.5),
    ])
    img = mx.nd.array(np.random.randint(0, 255, (16, 16, 3)),
                      dtype=np.uint8)
    out = aug(img)
    assert out.shape == (3, 8, 8)


def test_loader_multiprocess_workers_are_processes():
    """num_workers>0 must run dataset access in forked worker processes
    (reference: _MultiWorkerIter), not threads."""
    import os
    parent = os.getpid()

    class PidDataset(gdata.Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, idx):
            return np.array([float(os.getpid())], np.float64)

    loader = gdata.DataLoader(PidDataset(), batch_size=4, num_workers=2)
    pids = {int(v) for b in loader for v in b.asnumpy().ravel()}
    assert parent not in pids and len(pids) >= 1


def test_loader_multiprocess_tuple_batches():
    X = np.random.randn(20, 3).astype(np.float32)
    Y = np.arange(20, dtype=np.float32)
    ds = gdata.ArrayDataset(X, Y)
    loader = gdata.DataLoader(ds, batch_size=5, num_workers=2)
    batches = list(loader)
    assert len(batches) == 4
    xs = np.concatenate([b[0].asnumpy() for b in batches])
    ys = np.concatenate([b[1].asnumpy() for b in batches])
    np.testing.assert_allclose(xs, X)
    np.testing.assert_allclose(ys, Y)


def test_loader_thread_pool_flag():
    ds = gdata.ArrayDataset(np.arange(16, dtype=np.float32))
    loader = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                              thread_pool=True)
    vals = sorted(np.concatenate([b.asnumpy() for b in loader]))
    np.testing.assert_allclose(vals, np.arange(16))


def test_loader_multiprocess_spreads_gil_bound_work():
    """CPU-bound (GIL-holding) per-item work is what worker PROCESSES are
    for (the reference's motivation for them over threads, SURVEY
    Missing#6): with four of them the items come from more than one worker
    process, none from the parent, and in the sampler's order.  What that
    gains in wall time is the host's free cores' to say, not a test's: this
    suite runs beside five other workers."""
    import os

    class BusyDataset(gdata.Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, idx):
            acc = 0
            for i in range(200_000):   # pure-python: holds the GIL
                acc += i * i
            return np.array([idx, os.getpid(), acc % 7], np.float64)

    loader = gdata.DataLoader(BusyDataset(), batch_size=2, num_workers=4)
    items = np.concatenate([b.asnumpy() for b in loader])
    np.testing.assert_array_equal(items[:, 0], np.arange(16))
    pids = {int(p) for p in items[:, 1]}
    assert os.getpid() not in pids and len(pids) > 1, pids


def test_gluon_utils_download_and_sha1(tmp_path):
    """file:// download + sha1 verification + caching (reference:
    gluon.utils.download/check_sha1)."""
    import hashlib
    from incubator_mxnet_tpu.gluon import utils as gu
    src = tmp_path / "weights.bin"
    src.write_bytes(b"payload")
    h = hashlib.sha1(b"payload").hexdigest()
    out = gu.download(f"file://{src}", path=str(tmp_path / "dl.bin"),
                      sha1_hash=h)
    assert open(out, "rb").read() == b"payload"
    assert gu.check_sha1(out, h)
    # wrong hash raises
    import pytest as _pytest
    import incubator_mxnet_tpu as mx
    with _pytest.raises(mx.MXNetError, match="sha1"):
        gu.download(f"file://{src}", path=str(tmp_path / "dl2.bin"),
                    sha1_hash="0" * 40)
    assert gu.shape_is_known((3, 4))
    assert not gu.shape_is_known((3, -1))

"""Native C++ data engine: build, correctness vs the Python path, sharding.

Mirrors the sampler contracts the reference pins in
ray_lightning/tests/test_ddp.py:52-72 (disjoint shards, shuffle flags,
rank/num_replicas), applied to the in-repo native batcher.
"""

import numpy as np
import pytest

from ray_lightning_accelerators_tpu import native
from ray_lightning_accelerators_tpu.data.loader import (ArrayDataset,
                                                        DataLoader,
                                                        ShardedSampler)


pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native build: {native.build_error()}")


def _ds(n=64, d=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d), dtype=np.float32)
    y = rng.integers(0, 10, size=(n,)).astype(np.int32)
    return x, y


def test_builds():
    assert native.available(), native.build_error()


def test_cached_library_is_keyed_on_its_sources(tmp_path, monkeypatch):
    """The cached ``.so`` is git-ignored, so one copied along with a
    tree says nothing about the sources it was built from: the name
    carries a hash of the ``.cc`` files, and a stray library under any
    other name (here: the pre-hash name) is never picked up."""
    import os
    import shutil

    for name in native._sources():
        shutil.copy(os.path.join(native._DIR, name), tmp_path / name)
    (tmp_path / "_rla_native.so").write_bytes(b"not built from these")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    before = native._out_path()
    assert os.path.basename(before) == \
        f"_rla_native.{native._source_digest()}.so"
    assert not os.path.exists(before)  # the stray file does not satisfy it
    with open(tmp_path / native._sources()[0], "a") as f:
        f.write("// edited\n")
    assert native._out_path() != before  # edited sources => rebuilt


@pytest.mark.parametrize("shuffle", [False, True])
def test_matches_python_path_bit_exact(shuffle):
    # sampling stays in Python, so native batches are bit-identical to the
    # Python path even when shuffling
    x, y = _ds()
    ds = ArrayDataset(x, y)
    py = DataLoader(ds, batch_size=8, shuffle=shuffle, seed=5,
                    use_native=False)
    nat = DataLoader(ds, batch_size=8, shuffle=shuffle, seed=5,
                     use_native=True)
    py.set_epoch(3)
    nat.set_epoch(3)
    py_batches = list(py)
    nat_batches = list(nat)
    assert len(py_batches) == len(nat_batches) == len(py)
    for (px, pyy), (nx, ny) in zip(py_batches, nat_batches):
        np.testing.assert_array_equal(px, nx)
        np.testing.assert_array_equal(pyy, ny)
        assert nx.dtype == np.float32 and ny.dtype == np.int32


def test_shuffle_is_permutation_and_deterministic():
    x, y = _ds()
    eng = native.DataEngine([x, y], batch_size=8, shuffle=True, seed=3)
    seen = np.concatenate([bx[:, 0] for bx, _ in eng.epoch(0)])
    assert sorted(seen.tolist()) == sorted(x[:, 0].tolist())
    seen2 = np.concatenate([bx[:, 0] for bx, _ in eng.epoch(0)])
    np.testing.assert_array_equal(seen, seen2)  # same (seed, epoch)
    seen3 = np.concatenate([bx[:, 0] for bx, _ in eng.epoch(1)])
    assert not np.array_equal(seen, seen3)  # new epoch reshuffles
    eng.close()


def test_rank_shards_are_disjoint_and_cover():
    x, y = _ds(n=64)
    shards = []
    for rank in range(4):
        eng = native.DataEngine([x, y], batch_size=4, shuffle=True, seed=7,
                                num_replicas=4, rank=rank)
        shards.append(np.concatenate(
            [by for _, by in eng.epoch(2)] or [np.empty(0)]))
        assert eng.num_batches() == 64 // 4 // 4
        eng.close()
    # together the 4 rank shards hold each row exactly once
    rows = np.concatenate([np.concatenate(
        [bx[:, 0] for bx, _ in native.DataEngine(
            [x, y], 4, shuffle=True, seed=7, num_replicas=4,
            rank=r).epoch(2)]) for r in range(4)])
    assert sorted(rows.tolist()) == sorted(x[:, 0].tolist())


def test_partial_batch_no_drop_last():
    x, y = _ds(n=21)
    nat = DataLoader(ArrayDataset(x, y), batch_size=8, shuffle=False,
                     drop_last=False, use_native=True)
    sizes = [len(bx) for bx, _ in nat]
    assert sizes == [8, 8, 5]


def test_single_array_dataset_yields_bare_array():
    x, _ = _ds()
    nat = DataLoader(ArrayDataset(x), batch_size=8, use_native=True)
    batch = next(iter(nat))
    assert isinstance(batch, np.ndarray) and batch.shape == (8, 5)


def test_break_mid_epoch_then_reiterate():
    x, y = _ds(n=64)
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, shuffle=True,
                        use_native=True)
    it = iter(loader)
    next(it), next(it)  # abandon mid-epoch (limit_train_batches pattern)
    batches = list(loader)
    assert len(batches) == len(loader) == 8


def test_sampler_injection_reshapes_engine():
    x, y = _ds(n=64)
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, shuffle=True,
                        use_native=True)
    assert len(list(loader)) == 8
    loader._inject_sampler(num_replicas=2, rank=1, shuffle=True)
    assert len(list(loader)) == 4  # engine rebuilt for the 2-replica shard


def test_pickle_roundtrip_drops_engine():
    import cloudpickle
    x, y = _ds()
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, use_native=True)
    list(loader)
    loader2 = cloudpickle.loads(cloudpickle.dumps(loader))
    assert loader2._engine is None
    assert len(list(loader2)) == len(loader)


def test_user_sampler_subclass_uses_its_indices():
    # custom sampler semantics flow through: the engine consumes the
    # sampler's index order verbatim
    class EveryOther(ShardedSampler):
        def __iter__(self):
            return iter(range(0, self.dataset_len, 2))

    x, y = _ds()
    loader = DataLoader(ArrayDataset(x, y), batch_size=8,
                        sampler=EveryOther(64, 1, 0, shuffle=False),
                        use_native=True)
    batches = list(loader)
    np.testing.assert_array_equal(batches[0][0], x[0:16:2])


def test_object_dtype_rejected():
    objs = np.array([object() for _ in range(16)], dtype=object)
    ds = ArrayDataset(objs, np.arange(16))
    loader = DataLoader(ds, batch_size=4)
    assert loader._native_engine() is None  # auto mode: silent fallback
    with pytest.raises(RuntimeError, match="numeric"):
        DataLoader(ds, batch_size=4, use_native=True)._native_engine()


def test_explicit_native_with_custom_collate_raises():
    x, y = _ds()
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, use_native=True,
                        collate_fn=lambda b: b)
    with pytest.raises(RuntimeError, match="collate_fn"):
        next(iter(loader))


def test_concurrent_iteration_is_safe():
    x, y = _ds(n=64)
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, shuffle=True,
                        use_native=True)
    # zip over two live iterators: second falls back to the Python path,
    # both see the full epoch in the same order
    pairs = list(zip(loader, loader))
    assert len(pairs) == 8
    for (ax, ay), (bx, by) in pairs:
        np.testing.assert_array_equal(ax, bx)
        np.testing.assert_array_equal(ay, by)


def test_many_epochs_stress():
    x, y = _ds(n=256, d=16)
    eng = native.DataEngine([x, y], batch_size=16, shuffle=True, seed=0,
                            num_threads=4, prefetch=3)
    for epoch in range(20):
        total = 0
        for bx, by in eng.epoch(epoch):
            total += len(bx)
        assert total == 256
    eng.close()

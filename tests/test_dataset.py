"""Data pipeline tests: transformers, batching, record IO, image ops."""

import numpy as np
import pytest

from bigdl_tpu.dataset import (DataSet, Sample, MiniBatch, SampleToMiniBatch,
                               FixedLength, PaddingParam)
from bigdl_tpu.dataset.image import (LabeledImage, ImgCropper, ImgRdmCropper,
                                     ImgNormalizer, HFlip, ColorJitter,
                                     Lighting, ImgToSample, RdmResizedCrop,
                                     _resize_bilinear)
from bigdl_tpu.utils import recordio


def samples(n=10):
    return [Sample.from_ndarray(np.full((3,), i, np.float32), np.int32(i))
            for i in range(n)]


def test_sample_to_minibatch():
    ds = DataSet.array(samples(10)).transform(SampleToMiniBatch(4))
    batches = list(ds.data(train=False))
    assert [b.size() for b in batches] == [4, 4, 2]
    ds2 = DataSet.array(samples(10)).transform(
        SampleToMiniBatch(4, drop_last=True))
    assert [b.size() for b in list(ds2.data(train=False))] == [4, 4]
    ds3 = DataSet.array(samples(10)).transform(
        SampleToMiniBatch(4, pad_last=True))
    batches = list(ds3.data(train=False))
    assert [b.size() for b in batches] == [4, 4, 4]
    assert batches[-1].valid == 2


def test_minibatch_slice():
    ds = DataSet.array(samples(8)).transform(SampleToMiniBatch(8))
    b = next(iter(ds.data(train=False)))
    sub = b.slice(2, 3)
    assert sub.size() == 3
    np.testing.assert_allclose(sub.get_input()[0], [2, 2, 2])


def test_variable_length_padding():
    recs = [Sample.from_ndarray(np.ones((l, 2), np.float32), np.int32(0))
            for l in (3, 5, 2)]
    ds = DataSet.array(recs).transform(
        SampleToMiniBatch(3, feature_padding=PaddingParam(0.0)))
    b = next(iter(ds.data(train=False)))
    assert b.get_input().shape == (3, 5, 2)
    ds2 = DataSet.array(recs).transform(
        SampleToMiniBatch(3, feature_padding=FixedLength(8)))
    b2 = next(iter(ds2.data(train=False)))
    assert b2.get_input().shape == (3, 8, 2)


def test_shuffle_deterministic():
    ds = DataSet.array(samples(10), seed=42)
    ds.shuffle()
    order1 = [int(s.label) for s in ds.data(train=True)]
    ds2 = DataSet.array(samples(10), seed=42)
    ds2.shuffle()
    order2 = [int(s.label) for s in ds2.data(train=True)]
    assert order1 == order2 and order1 != list(range(10))


def test_distributed_dataset_shards():
    from bigdl_tpu.dataset import DistributedDataSet
    all_seen = []
    for pi in range(4):
        ds = DistributedDataSet(samples(20), process_index=pi, process_count=4)
        assert ds.size() == 20
        local = [int(s.label) for s in ds.data(train=False)]
        assert len(local) == 5
        all_seen += local
    assert sorted(all_seen) == list(range(20))


def test_transformer_chaining():
    imgs = [LabeledImage(np.ones((8, 8, 3), np.float32), float(i))
            for i in range(4)]
    chain = (ImgCropper(4, 4)
             >> ImgNormalizer([0.5, 0.5, 0.5], [0.5, 0.5, 0.5])
             >> ImgToSample())
    out = list(chain(iter(imgs)))
    assert len(out) == 4
    assert out[0].feature.shape == (4, 4, 3)
    np.testing.assert_allclose(out[0].feature, 1.0)


def test_image_augmentations_shapes():
    imgs = [LabeledImage(np.random.default_rng(0).random((16, 12, 3))
                         .astype(np.float32), 1.0)]
    for t in (ImgRdmCropper(8, 8, padding=2), HFlip(1.0), ColorJitter(),
              Lighting(), RdmResizedCrop(8, 8)):
        out = list(t(iter([imgs[0]])))
        assert out[0].data.shape[2] == 3


def test_resize_bilinear_golden():
    img = np.asarray([[0.0, 1.0], [2.0, 3.0]], np.float32)[:, :, None]
    out = _resize_bilinear(img, 4, 4)
    assert out.shape == (4, 4, 1)
    np.testing.assert_allclose(out[0, 0, 0], 0.0)
    np.testing.assert_allclose(out.mean(), img.mean(), atol=0.1)


def test_recordio_roundtrip(tmp_path):
    recs = samples(13)
    path = str(tmp_path / "data.rec")
    recordio.write_records(path, recs)
    back = list(recordio.read_records(path))
    assert len(back) == 13
    np.testing.assert_allclose(back[5].feature, recs[5].feature)


def test_recordio_sharded(tmp_path):
    path = str(tmp_path / "shards")
    paths = recordio.write_records(path, samples(10), shards=4)
    assert len(paths) == 4
    back = list(recordio.read_records(path))
    assert sorted(int(s.label) for s in back) == list(range(10))


def test_recordio_corruption_detected(tmp_path):
    path = str(tmp_path / "data.rec")
    recordio.write_records(path, samples(2))
    raw = bytearray(open(path, "rb").read())
    raw[20] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises((IOError, Exception)):
        list(recordio.read_records(path))


def test_crc32c_golden():
    # known CRC32C test vector: "123456789" -> 0xE3069283
    from bigdl_tpu.utils.recordio import _crc32c_py
    assert _crc32c_py(b"123456789") == 0xE3069283


def test_dataset_record_file_builder(tmp_path):
    path = str(tmp_path / "ds.rec")
    recordio.write_records(path, samples(6))
    ds = DataSet.record_file(path)
    assert ds.size() == 6


def test_dataset_record_files_glob(tmp_path):
    """Sharded SeqFileFolder role: glob over BDRecord shards, sorted order."""
    for shard in range(3):
        recordio.write_records(str(tmp_path / f"part-{shard}.bdr"),
                               samples(4))
    ds = DataSet.record_files(str(tmp_path / "part-*.bdr"))
    assert ds.size() == 12
    import pytest
    with pytest.raises(FileNotFoundError):
        DataSet.record_files(str(tmp_path / "nope-*.bdr"))


def test_movielens_provider(tmp_path):
    from bigdl_tpu.dataset.providers import load_movielens
    (tmp_path / "ratings.dat").write_text(
        "1::1193::5::978300760\n1::661::3::978302109\n2::1357::5::978298709\n")
    r = load_movielens(str(tmp_path))
    assert r.shape == (3, 3) and r.dtype.name == "float32"
    assert r[0].tolist() == [1.0, 1193.0, 5.0]
    # ml-latest CSV with header; half-star ratings must survive
    (tmp_path / "ratings.csv").write_text(
        "userId,movieId,rating,timestamp\n7,2,4.0,123\n8,3,3.5,456\n")
    r2 = load_movielens(str(tmp_path), "ratings.csv")
    assert r2.tolist() == [[7.0, 2.0, 4.0], [8.0, 3.0, 3.5]]


def test_sorted_array_group_shuffle():
    """DataSet.sortRDD + groupSize role: records sorted by length, shuffle
    permutes groups only — batches stay length-homogeneous."""
    recs = [np.zeros(n) for n in [7, 3, 9, 1, 5, 8, 2, 6]]
    ds = DataSet.sorted_array(recs, key=len, group_size=2, seed=3)
    for _ in range(5):
        ds.shuffle()
        lens = [len(r) for r in ds.data(train=True)]
        assert sorted(lens) == [1, 2, 3, 5, 6, 7, 8, 9]
        # each adjacent pair must be one of the sorted-order groups
        pairs = {(lens[i], lens[i + 1]) for i in range(0, 8, 2)}
        assert pairs <= {(1, 2), (3, 5), (6, 7), (8, 9)}, lens
    # eval order is the sorted order, untouched by shuffling
    assert [len(r) for r in ds.data(train=False)] == [1, 2, 3, 5, 6, 7, 8, 9]


def test_mt_sample_to_minibatch_matches_single_threaded():
    import numpy as np
    from bigdl_tpu.dataset import (MTSampleToMiniBatch, Sample,
                                   SampleToMiniBatch)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal((7, 3)).astype(np.float32),
                      np.float32(i)) for i in range(50)]
    ref = list(SampleToMiniBatch(16, pad_last=True)(iter(samples)))
    got = list(MTSampleToMiniBatch(16, pad_last=True, num_threads=4)(
        iter(samples)))
    assert len(got) == len(ref)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r.get_input()),
                                      np.asarray(g.get_input()))
        np.testing.assert_array_equal(np.asarray(r.get_target()),
                                      np.asarray(g.get_target()))
        assert r.valid == g.valid


def test_mt_batcher_with_upstream_transformer():
    import numpy as np
    from bigdl_tpu.dataset import MTSampleToMiniBatch, Sample, Transformer

    class Scale(Transformer):
        def __call__(self, it):
            for s in it:
                yield Sample(s.feature * 2.0, s.label)

    samples = [Sample(np.full((2, 2), i, np.float32), np.float32(i))
               for i in range(10)]
    got = list(MTSampleToMiniBatch(4, transformer=Scale(), drop_last=True,
                                   num_threads=2)(iter(samples)))
    assert len(got) == 2
    np.testing.assert_array_equal(
        np.asarray(got[0].get_input())[3], np.full((2, 2), 6.0))


def test_thread_pool_api():
    from bigdl_tpu.utils import ThreadPool
    pool = ThreadPool(4)
    results = pool.invoke_and_wait([lambda i=i: i * i for i in range(8)])
    assert results == [i * i for i in range(8)]
    futs = pool.invoke([lambda: 42])
    assert pool.sync(futs) == [42]
    import pytest as _p
    import time as _t
    with _p.raises(Exception):
        pool.invoke_and_wait([lambda: _t.sleep(0.3)], timeout=0.05)
    pool.shutdown()


def test_mt_batcher_rejects_filtering_transformer():
    import numpy as np
    import pytest
    from bigdl_tpu.dataset import MTSampleToMiniBatch, Sample, Transformer

    class DropOdd(Transformer):
        def __call__(self, it):
            for s in it:
                if int(s.label) % 2 == 0:
                    yield s

    samples = [Sample(np.zeros(3, np.float32), np.float32(i))
               for i in range(8)]
    mt = MTSampleToMiniBatch(4, transformer=DropOdd(), num_threads=2)
    with pytest.raises(ValueError, match="1:1"):
        list(mt(iter(samples)))


def test_gather_rows_heterogeneous_matches_np_stack():
    import numpy as np
    from bigdl_tpu.utils import native
    rows = [np.zeros((2, 2), np.float32), np.zeros((2, 2), np.float64)]
    got = native.gather_rows(rows)
    ref = np.stack(rows)
    assert got.dtype == ref.dtype
    np.testing.assert_array_equal(got, ref)


class TestStreamingRecordDataSet:
    """Out-of-core shard streaming (DataSet.record_stream)."""

    def _shards(self, tmp_path, n_shards=4, per_shard=25):
        from bigdl_tpu.utils.recordio import write_records
        paths = []
        k = 0
        for s in range(n_shards):
            p = str(tmp_path / f"s{s}.bd")
            write_records(p, list(range(k, k + per_shard)))
            k += per_shard
            paths.append(p)
        return paths

    def test_streams_all_records_every_epoch(self, tmp_path):
        from bigdl_tpu.dataset import DataSet

        paths = self._shards(tmp_path)
        ds = DataSet.record_stream(paths)
        assert ds.size() == 100
        first = list(ds.data(train=True))
        assert sorted(first) == list(range(100))
        ds.shuffle()
        second = list(ds.data(train=True))
        assert sorted(second) == list(range(100))
        # shard-granular shuffle: different shard order is possible, but
        # within-shard order is preserved
        for s in range(4):
            blk = [x for x in second if s * 25 <= x < (s + 1) * 25]
            assert blk == list(range(s * 25, (s + 1) * 25))

    def test_eval_pass_is_deterministic(self, tmp_path):
        from bigdl_tpu.dataset import DataSet

        paths = self._shards(tmp_path)
        ds = DataSet.record_stream(paths)
        ds.shuffle()
        assert list(ds.data(train=False)) == list(range(100))

    def test_native_threads_same_multiset(self, tmp_path):
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.utils import native

        # a checkout carries no binary: build it as Engine.init would
        if not (native.build() and native.has_prefetch()):
            pytest.skip("native prefetch unavailable")
        paths = self._shards(tmp_path)
        ds = DataSet.record_stream(paths, num_threads=3)
        assert sorted(ds.data(train=True)) == list(range(100))

    def test_distributed_strided_disjoint(self, tmp_path):
        """Real sharding path via explicit process_index/process_count:
        ranks stream disjoint shard subsets covering the corpus."""
        from bigdl_tpu.dataset import StreamingRecordDataSet

        paths = self._shards(tmp_path, n_shards=6)
        seen = []
        for rank in range(3):
            ds = StreamingRecordDataSet(paths, distributed=True,
                                        process_index=rank,
                                        process_count=3)
            seen.append(sorted(ds.data(train=True)))
        flat = [x for part in seen for x in part]
        assert sorted(flat) == sorted(set(flat))  # disjoint
        assert len(flat) == 150  # 6 shards x 25, all covered

    def test_distributed_indivisible_shards_rejected(self, tmp_path):
        from bigdl_tpu.dataset import StreamingRecordDataSet

        paths = self._shards(tmp_path, n_shards=5)
        ds = StreamingRecordDataSet(paths, distributed=True,
                                    process_index=0, process_count=3)
        with pytest.raises(ValueError, match="not.*divisible|divisible"):
            list(ds.data(train=True))

    def test_distributed_unequal_shards_equal_steps(self, tmp_path):
        """Unequal shard sizes: every rank truncates to the smallest
        rank's record count for the epoch (collective-step safety)."""
        from bigdl_tpu.dataset import StreamingRecordDataSet
        from bigdl_tpu.utils.recordio import write_records

        paths = []
        for s, n in enumerate([30, 20]):  # rank0 shard bigger than rank1
            p = str(tmp_path / f"u{s}.bd")
            write_records(p, list(range(n)))
            paths.append(p)
        lens = []
        for rank in range(2):
            ds = StreamingRecordDataSet(paths, distributed=True,
                                        process_index=rank, process_count=2)
            lens.append(len(list(ds.data(train=True))))
        assert lens[0] == lens[1] == 20

    def test_eval_pass_sequential_even_with_threads(self, tmp_path):
        """train=False must preserve input order (Predictor aligns outputs
        positionally) even when num_threads requests the interleaving
        prefetcher for training passes."""
        from bigdl_tpu.dataset import DataSet

        paths = self._shards(tmp_path)
        ds = DataSet.record_stream(paths, num_threads=4)
        assert list(ds.data(train=False)) == list(range(100))

    def test_size_counts_without_decoding(self, tmp_path):
        from bigdl_tpu.utils.recordio import count_records

        paths = self._shards(tmp_path, n_shards=2, per_shard=7)
        assert [count_records(p) for p in paths] == [7, 7]

    def test_trains_through_optimizer(self, tmp_path):
        """End-to-end: stream shards -> transform -> train (the dataset is
        re-read from disk each epoch)."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
        from bigdl_tpu.models import LeNet5
        from bigdl_tpu.optim import Adam, Optimizer, Trigger
        from bigdl_tpu.utils.engine import Engine
        from bigdl_tpu.utils.recordio import write_records
        from test_e2e_lenet import synthetic_mnist

        Engine.reset()
        Engine.init()
        samples = synthetic_mnist(256)
        write_records(str(tmp_path / "mnist.bd"), samples, shards=4)
        paths = sorted(str(p) for p in tmp_path.glob("mnist.bd-*"))
        ds = DataSet.record_stream(paths).transform(
            SampleToMiniBatch(64, drop_last=True))
        opt = (Optimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
               .set_optim_method(Adam(1e-3))
               .set_end_when(Trigger.max_epoch(6)))
        opt.optimize()
        # shard-granular shuffle mixes less than record-level, so allow
        # a couple more epochs than the in-memory path needs
        assert opt.optim_method.hyper["loss"] < 1.0


class TestCorruptRecordQuarantine:
    """Corrupt-record tolerance on the BDRecord streaming path: typed
    CorruptRecord with path+offset, opt-in bounded skip budget
    (BIGDL_TPU_DATA_SKIP_BUDGET / skip_budget=), default fail-loud."""

    def _shard(self, tmp_path, n=20):
        from bigdl_tpu.utils.recordio import write_records
        p = str(tmp_path / "c.bd")
        write_records(p, list(range(n)))
        return p

    def test_chaos_corruption_skip_budget(self, tmp_path):
        from bigdl_tpu.dataset import StreamingRecordDataSet
        from bigdl_tpu.utils import chaos
        from bigdl_tpu.utils import recordio

        p = self._shard(tmp_path)
        recordio.reset_quarantine_stats()
        with chaos.scoped("data.record=truncate@4,9"):
            ds = StreamingRecordDataSet([p], skip_budget=2)
            out = list(ds.data(train=False))
        assert len(out) == 18
        assert ds.last_quarantined == 2
        assert recordio.quarantine_stats()["records"] == 2

    def test_chaos_corruption_default_fails_loud(self, tmp_path):
        from bigdl_tpu.dataset import StreamingRecordDataSet
        from bigdl_tpu.utils import chaos
        from bigdl_tpu.utils.recordio import CorruptRecord

        p = self._shard(tmp_path)
        with chaos.scoped("data.record=truncate@4"):
            ds = StreamingRecordDataSet([p])
            with pytest.raises(CorruptRecord) as ei:
                list(ds.data(train=False))
        assert ei.value.path == p and ei.value.offset is not None

    def test_on_disk_bitflip_quarantined_with_offset(self, tmp_path):
        """Real bit-rot: one flipped byte mid-payload is caught by the
        frame CRC, quarantined under budget with its byte offset."""
        from bigdl_tpu.utils.recordio import (CorruptRecord, SkipBudget,
                                              write_records, read_records)

        # fat payloads so a mid-record flip lands in PAYLOAD bytes (a
        # flipped length header is untrusted-length, fatal by design)
        p = str(tmp_path / "c.bd")
        write_records(p, ["x" * 64] * 19 + ["y" * 64])
        data = bytearray(open(p, "rb").read())
        data[30] ^= 0xFF  # inside the first record's payload
        open(p, "wb").write(bytes(data))
        with pytest.raises(CorruptRecord):
            list(read_records(p))
        skip = SkipBudget(1)
        out = list(read_records(p, skip=skip))
        assert len(out) == 19 and skip.count == 1
        path_, offset, reason = skip.quarantined[0]
        assert path_ == p and offset is not None and "crc" in reason

    def test_budget_exhaustion_reraises(self, tmp_path):
        from bigdl_tpu.utils import chaos
        from bigdl_tpu.utils.recordio import (CorruptRecord, SkipBudget,
                                              read_records)

        p = self._shard(tmp_path)
        with chaos.scoped("data.record=truncate@2,5,8"):
            skip = SkipBudget(2)
            with pytest.raises(CorruptRecord):
                list(read_records(p, skip=skip))
        assert skip.count == 2  # absorbed two, the third was over budget

    def test_env_knob_default(self, tmp_path, monkeypatch):
        from bigdl_tpu.dataset import StreamingRecordDataSet
        from bigdl_tpu.utils import chaos

        monkeypatch.setenv("BIGDL_TPU_DATA_SKIP_BUDGET", "1")
        p = self._shard(tmp_path)
        with chaos.scoped("data.record=truncate@3"):
            ds = StreamingRecordDataSet([p])  # budget from the env knob
            out = list(ds.data(train=False))
        assert len(out) == 19 and ds.last_quarantined == 1

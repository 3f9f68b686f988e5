"""Out-of-core file datasets: InMemoryDataset
load/shuffle semantics, shared-filesystem global shuffle covering all
trainers disjointly, QueueDataset streaming with bounded memory, and the
pipe_command filter. Reference fluid/dataset.py + data_feed.cc roles."""

import os
import threading
import time

import numpy as np
import pytest

from paddle1_tpu.io import (DataLoader, DatasetFactory, InMemoryDataset,
                            QueueDataset)


@pytest.fixture()
def files(tmp_path):
    paths = []
    v = 0
    for i in range(4):
        p = tmp_path / f"part-{i}.txt"
        lines = []
        for _ in range(25):
            lines.append(f"{v} {v + 0.5}")
            v += 1
        p.write_text("\n".join(lines) + "\n")
        paths.append(str(p))
    return paths  # 100 samples total, sample j = [j, j+0.5]


class TestInMemoryDataset:
    def test_factory_and_load(self, files):
        ds = DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.load_into_memory()
        assert len(ds) == 100
        assert ds.get_memory_data_size() == 100
        np.testing.assert_allclose(ds[7], [7.0, 7.5])
        ds.release_memory()
        assert len(ds) == 0

    def test_file_sharding_two_trainers(self, files):
        sizes = []
        for rank in range(2):
            ds = InMemoryDataset()
            ds.set_filelist(files)
            ds.set_rank_world(rank, 2)
            ds.load_into_memory()
            sizes.append(len(ds))
        assert sizes == [50, 50]

    def test_local_shuffle(self, files):
        ds = InMemoryDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.load_into_memory()
        before = [float(ds[i][0]) for i in range(100)]
        ds.local_shuffle(seed=0)
        after = [float(ds[i][0]) for i in range(100)]
        assert sorted(after) == sorted(before) and after != before

    def test_global_shuffle_disjoint_cover(self, files):
        """Every trainer's shard after global_shuffle: union = corpus,
        pairwise disjoint, and genuinely shuffled."""
        shards = []
        for rank in range(4):
            ds = InMemoryDataset()
            ds.set_filelist(files)
            ds.set_rank_world(rank, 4)
            ds.global_shuffle(seed=7)
            assert ds.get_shuffle_data_size() == len(ds) == 25
            shards.append({float(s[0]) for s in ds._samples})
        union = set().union(*shards)
        assert union == {float(i) for i in range(100)}
        for a in range(4):
            for b in range(a + 1, 4):
                assert not (shards[a] & shards[b])

    def test_dataloader_integration(self, files):
        ds = InMemoryDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.load_into_memory()
        loader = DataLoader(ds, batch_size=10, shuffle=False)
        batches = list(loader)
        assert len(batches) == 10
        assert list(batches[0].shape) == [10, 2]

    def test_pipe_command_filter(self, files):
        ds = InMemoryDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.set_pipe_command("grep -v '^1 '")   # drop sample 1
        ds.load_into_memory()
        vals = {float(s[0]) for s in ds._samples}
        assert 1.0 not in vals and len(ds) == 99

    def test_pipe_command_failure_raises(self, files):
        from paddle1_tpu.core.errors import PreconditionNotMetError
        ds = InMemoryDataset()
        ds.set_filelist(files[:1])
        ds.set_rank_world(0, 1)
        ds.set_pipe_command("false")
        with pytest.raises(PreconditionNotMetError):
            ds.load_into_memory()


class TestQueueDataset:
    def test_streams_all_samples(self, files):
        ds = QueueDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        got = [float(s[0]) for s in ds]
        assert got == [float(i) for i in range(100)]

    def test_bounded_memory(self, files):
        """The reader must BLOCK at queue capacity — out-of-core, not a
        hidden load_into_memory."""
        parsed = []

        def counting_parse(line):
            parsed.append(1)
            parts = line.split()
            return np.asarray([float(p) for p in parts], np.float32)

        ds = QueueDataset(capacity=8)
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.set_parse_fn(counting_parse)
        it = iter(ds)
        next(it)
        time.sleep(0.3)  # give the reader thread time to run ahead
        # reader can be at most capacity + in-flight ahead of the consumer
        assert len(parsed) <= 8 + 2, len(parsed)
        rest = sum(1 for _ in it)
        assert rest == 99 and len(parsed) == 100

    def test_parse_error_propagates(self, files):
        ds = QueueDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)

        def bad_parse(line):
            raise ValueError("boom")

        ds.set_parse_fn(bad_parse)
        with pytest.raises(ValueError):
            for _ in ds:
                pass

    def test_custom_parse_drops_none(self, files):
        ds = QueueDataset()
        ds.set_filelist(files)
        ds.set_rank_world(0, 1)
        ds.set_parse_fn(lambda l: None if l.startswith("2 ")
                        else np.float32(l.split()[0]))
        got = [float(s) for s in ds]
        assert 2.0 not in got and len(got) == 99

    def test_early_break_releases_reader(self, files):
        """Review finding: breaking out of iteration must not leave the
        reader thread blocked on a full queue forever."""
        before = threading.active_count()
        for _ in range(5):
            ds = QueueDataset(capacity=4)
            ds.set_filelist(files)
            ds.set_rank_world(0, 1)
            for i, _s in enumerate(ds):
                if i == 2:
                    break   # abandons the iterator mid-stream
        time.sleep(0.5)
        assert threading.active_count() <= before + 1, (
            "reader threads leaked after early break")

    def test_factory_unknown_raises(self):
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError):
            DatasetFactory().create_dataset("NopeDataset")

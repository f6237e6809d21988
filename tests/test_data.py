"""Data pipeline tests: determinism, partition semantics, prefetch loader."""
import time

import numpy as np
import pytest

from repro.core import virtual
from repro.data.pipeline import DataConfig, HostLoader, synth_batch


def test_synth_batch_deterministic():
    cfg = DataConfig(kind="lm", vocab_size=100, seq_len=8, global_batch=4)
    b1 = synth_batch(cfg, 3)
    b2 = synth_batch(cfg, 3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = synth_batch(cfg, 4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_synth_batch_kinds():
    for kind, keys in [("lm", {"tokens"}),
                       ("image", {"images", "labels"}),
                       ("audio", {"audio_embeds", "tokens"}),
                       ("vlm", {"tokens", "image_embeds"})]:
        cfg = DataConfig(kind=kind, vocab_size=50, seq_len=16,
                         global_batch=2, d_model=8, encoder_seq_len=6,
                         num_image_tokens=4, image_size=32)
        assert set(synth_batch(cfg, 0)) == keys


def test_partition_is_row_partition():
    cfg = DataConfig(kind="lm", vocab_size=100, seq_len=8, global_batch=8)
    batch = synth_batch(cfg, 0)
    shards = virtual.partition_minibatch(batch, 4)
    assert len(shards) == 4
    recon = np.concatenate([np.asarray(s["tokens"]) for s in shards], 0)
    np.testing.assert_array_equal(recon, batch["tokens"])


def test_host_loader_prefetch_and_order():
    cfg = DataConfig(kind="lm", vocab_size=100, seq_len=4, global_batch=2)
    loader = HostLoader(cfg, prefetch=2)
    try:
        for step in range(3):
            got = next(loader)
            np.testing.assert_array_equal(got["tokens"],
                                          synth_batch(cfg, step)["tokens"])
    finally:
        loader.close()


def test_host_loader_latency_simulation():
    cfg = DataConfig(kind="lm", vocab_size=10, seq_len=2, global_batch=1)
    loader = HostLoader(cfg, prefetch=1, io_latency_s=0.05)
    try:
        next(loader)                       # may be already prefetched
        t0 = time.time()
        next(loader)
        next(loader)
        assert time.time() - t0 > 0.04     # latency is really applied
    finally:
        loader.close()


def test_host_loader_counts_waits_on_an_empty_queue():
    """The first batch takes the worker 0.3 s to make: ``next`` blocks
    on the empty queue for about that long, and wait_s counts it."""
    cfg = DataConfig(kind="lm", vocab_size=10, seq_len=2, global_batch=1)
    with HostLoader(cfg, prefetch=1, io_latency_s=0.3) as loader:
        next(loader)
        assert loader.batches == 1
        assert loader.wait_s > 0.1


def test_host_loader_no_wait_on_a_full_queue():
    """A batch already in the queue is handed out without waiting:
    wait_s does not move."""
    cfg = DataConfig(kind="lm", vocab_size=10, seq_len=2, global_batch=1)
    with HostLoader(cfg, prefetch=2) as loader:
        deadline = time.time() + 10.0
        while loader._q.qsize() < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert loader._q.qsize() == 2
        before = loader.wait_s
        next(loader)
        next(loader)
        assert loader.wait_s == before
        assert loader.batches == 2

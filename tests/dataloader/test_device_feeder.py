"""DeviceFeeder: the async host→device pipeline must be invisible to training —
bit-identical losses vs the synchronous inline path — while its lifecycle
(prompt error propagation, producer join on early exit) and the Trainer's
wall/device throughput split stay observable."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from modalities_tpu.batch import DatasetBatch
from modalities_tpu.dataloader.device_feeder import DeviceBatchIterator, DeviceFeeder
from modalities_tpu.logging_broker.message_broker import MessageBroker
from modalities_tpu.logging_broker.messages import Message, MessageTypes
from modalities_tpu.logging_broker.publisher import MessagePublisher
from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.trainer import Trainer
from modalities_tpu.training.training_progress import TrainingProgress
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _builder


def _microbatches(n, seed=0, mb=8, seq=16, vocab=128):
    for i in range(n):
        rng = np.random.default_rng(seed + i)
        tokens = rng.integers(0, vocab, size=(mb, seq + 1))
        yield DatasetBatch(
            samples={"input_ids": tokens[:, :-1].astype(np.int32)},
            targets={"target_ids": tokens[:, 1:].astype(np.int32)},
        )


def _train_losses(prefetch, n_steps=4, acc=2):
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh, acc=acc, clip=1.0).build(seed=0)
    state = fns.app_state_handle.state
    feed = DeviceFeeder(prefetch_to_device=prefetch).feed_train(
        _microbatches(n_steps * acc), fns.put_batch, gradient_acc_steps=acc
    )
    losses = []
    try:
        for device_batch in feed:
            state, metrics = fns.train_step(state, device_batch)
            losses.append(float(metrics["loss"]))
    finally:
        feed.close()
    assert feed.counters["dropped_microbatches"] == 0
    return losses


def test_feeder_async_bitwise_matches_sync():
    """N real optimizer steps through the background pipeline vs the inline path:
    same model seed, same data stream — the losses must be BIT-identical, because
    the feeder only relocates when stack+transfer happen, never what they compute."""
    sync = _train_losses(prefetch=0)
    async_ = _train_losses(prefetch=2)
    assert len(sync) == 4 and np.isfinite(sync).all()
    assert async_ == sync, (async_, sync)


def test_feeder_stacks_acc_dim_and_counts_dropped_tail():
    # 5 microbatches at acc=2 -> two stacked steps, one dropped trailing microbatch
    feeder = DeviceFeeder(prefetch_to_device=0)
    feed = feeder.feed_train(
        _microbatches(5), lambda host, has_acc_dim=True: host, gradient_acc_steps=2
    )
    steps = list(feed)
    assert len(steps) == 2
    assert steps[0]["samples"]["input_ids"].shape == (2, 8, 16)
    assert feed.counters["dropped_microbatches"] == 1


@pytest.mark.parametrize("prefetch", [0, 2])
def test_poisoned_dataset_raises_promptly(prefetch):
    """A loader that blows up mid-epoch must surface its exception out of the
    consumer's `__next__` — not hang the queue, not vanish in the thread."""

    def poisoned():
        yield from _microbatches(2)
        raise RuntimeError("poisoned dataset")

    feed = DeviceFeeder(prefetch_to_device=prefetch).feed_train(
        poisoned(), lambda host, has_acc_dim=True: host, gradient_acc_steps=1
    )
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="poisoned dataset"):
        for _ in range(10):
            next(feed)
    assert time.perf_counter() - t0 < 30.0
    feed.close()


def test_close_joins_producer_on_early_exit():
    """Bailing out mid-epoch (target steps reached) must stop and join the
    producer even while it is blocked on a full prefetch queue."""

    def endless():
        i = 0
        while True:
            yield from _microbatches(1, seed=i)
            i += 1

    feed = DeviceFeeder(prefetch_to_device=2).feed_train(
        endless(), lambda host, has_acc_dim=True: host, gradient_acc_steps=1
    )
    next(feed)  # consume one, leave the producer parked on a full queue
    assert feed._thread is not None
    feed.close()
    assert not feed._thread.is_alive()
    assert threading.active_count() >= 1  # no deadlock reaching here is the point


def test_negative_prefetch_rejected():
    with pytest.raises(ValueError, match="prefetch_to_device"):
        DeviceFeeder(prefetch_to_device=-1)


def test_sync_mode_accounts_inline_transfer_as_stall():
    def slow_put(host, has_acc_dim=True):
        time.sleep(0.05)
        return host

    feed = DeviceBatchIterator(iter([{"x": 1}, {"x": 2}]), slow_put, prefetch=0)
    next(feed)
    assert feed.take_stall_s() >= 0.05
    assert feed.take_stall_s() == 0.0  # drained


class _Recorder:
    def __init__(self):
        self.messages = []

    def consume_message(self, message: Message):
        self.messages.append(message)


class _FakeTrainLoader:
    dataloader_tag = "train"

    def __init__(self, batches):
        self._batches = batches

    def __iter__(self):
        return iter(self._batches)

    def __len__(self):
        return len(self._batches)


def test_trainer_publishes_wall_device_split_and_stalls():
    """The interval publish must carry BOTH throughput variants plus both stall
    scalars (the perf-opt contract: wall-clock is the scoreboard, device-time is
    the bench-comparable number, and the stalls explain the gap)."""
    broker = MessageBroker()
    results = _Recorder()
    broker.add_subscriber(MessageTypes.EVALUATION_RESULT, results)
    pub = MessagePublisher(broker)

    def fake_train_step(state, batch):
        return state + 1, {"loss": 1.0, "grad_norm": 0.5, "lr": 1e-3}

    fns = SimpleNamespace(
        app_state_handle=SimpleNamespace(state=0),
        train_step=fake_train_step,
        put_batch=lambda batch, has_acc_dim=True: batch,
    )

    class _MFU:
        def compute(self, tokens_per_second):
            return tokens_per_second / 1e6

    from modalities_tpu.telemetry import Telemetry

    trainer = Trainer(
        progress_publisher=pub,
        evaluation_result_publisher=pub,
        gradient_acc_steps=1,
        global_num_tokens_per_train_step=128,
        training_log_interval_in_steps=2,
        mfu_calculator=_MFU(),
        gc_frequency=0,
        telemetry=Telemetry(watchdog_deadline_s=0),  # enabled, sinkless, no watchdog
    )
    progress = TrainingProgress(
        num_seen_steps_current_run=0, num_seen_tokens_current_run=0,
        num_target_steps=4, num_target_tokens=512,
    )
    trainer.train(
        fns, _FakeTrainLoader(list(_microbatches(4))), progress,
        evaluation_callback=lambda step: time.sleep(0.01),
        checkpointing_callback=lambda p: None,
    )

    assert len(results.messages) == 2  # 4 steps / interval 2
    for msg in results.messages:
        tp = msg.payload.throughput_metrics
        for key in ("tokens/s", "tokens/s (wall)", "tokens/s (device)", "host stall [s]",
                    "boundary stall [s]", "MFU", "MFU (wall)", "MFU (device)",
                    "goodput [%]", "goodput/train_step [s]", "goodput/data_stall [s]"):
            assert key in tp, (key, sorted(tp))
        assert 0.0 <= tp["goodput [%]"].value <= 100.0
        # the explicit wall aliases are the same measurements as the bare keys
        # (kept for dashboards), never a third timing source
        assert tp["tokens/s (wall)"].value == tp["tokens/s"].value
        assert tp["MFU (wall)"].value == tp["MFU"].value
        # device-time rate excludes the measured stalls, so it can only be faster
        assert tp["tokens/s (device)"].value >= tp["tokens/s"].value
        assert tp["boundary stall [s]"].value > 0.0  # the sleeping eval callback
        assert tp["host stall [s]"].value >= 0.0
    assert fns.app_state_handle.state == 4

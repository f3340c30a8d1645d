"""Evaluation over N dataloaders (reference: src/modalities/evaluator.py:88)."""

from __future__ import annotations

import time

import numpy as np

from typing import Optional

from modalities_tpu.batch import EvaluationResultBatch, ResultItem
from modalities_tpu.dataloader.device_feeder import DeviceFeeder
from modalities_tpu.logging_broker.messages import ExperimentStatus, MessageTypes, ProgressUpdate
from modalities_tpu.logging_broker.publisher import MessagePublisher
from modalities_tpu.telemetry import span
from modalities_tpu.training.train_step import StepFunctions


class Evaluator:
    def __init__(
        self,
        progress_publisher: MessagePublisher,
        evaluation_result_publisher: MessagePublisher,
        device_feeder: Optional[DeviceFeeder] = None,
    ) -> None:
        self.progress_publisher = progress_publisher
        self.evaluation_result_publisher = evaluation_result_publisher
        self.device_feeder = device_feeder if device_feeder is not None else DeviceFeeder()

    def evaluate(
        self,
        step_functions: StepFunctions,
        data_loaders: list,
        num_train_steps_done: int,
    ) -> dict[str, EvaluationResultBatch]:
        result_dict: dict[str, EvaluationResultBatch] = {}
        state = step_functions.app_state_handle.state
        for data_loader in data_loaders:
            with span(f"eval/{data_loader.dataloader_tag}"):
                start = time.perf_counter()
                losses = []
                num_samples = 0
                # device-ready batches from the feeder pipeline: the transfer for
                # batch N+1 overlaps the device evaluating batch N (same path as the
                # Trainer, minus the acc-dim stacking)
                feed = self.device_feeder.feed_eval(data_loader, step_functions.put_batch)
                try:
                    for batch_id, (device_batch, batch_samples) in enumerate(feed):
                        metrics = step_functions.eval_step(state, device_batch)
                        losses.append(metrics["loss"])
                        num_samples += batch_samples
                        self.progress_publisher.publish_message(
                            ProgressUpdate(batch_id + 1, ExperimentStatus.EVALUATION, data_loader.dataloader_tag),
                            MessageTypes.BATCH_PROGRESS_UPDATE,
                        )
                finally:
                    feed.close()
                # fetch BEFORE reading the clock: dispatch returns early, so an elapsed
                # taken pre-sync times the host loop, not the device work — the same
                # honest-clock rule the trainer follows (hard_sync lesson)
                losses_np = np.asarray([np.asarray(loss) for loss in losses], dtype=np.float64)
                elapsed = max(time.perf_counter() - start, 1e-9)
                result = EvaluationResultBatch(
                    dataloader_tag=data_loader.dataloader_tag,
                    num_train_steps_done=num_train_steps_done,
                    losses={"loss avg": ResultItem(losses_np.mean() if len(losses_np) else np.nan, 5)},
                    throughput_metrics={"eval samples/s": ResultItem(num_samples / elapsed, 2)},
                )
                self.evaluation_result_publisher.publish_message(result, MessageTypes.EVALUATION_RESULT)
                result_dict[data_loader.dataloader_tag] = result
        return result_dict

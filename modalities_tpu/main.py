"""Main: config -> component graph -> jitted step functions -> Gym.run
(reference: src/modalities/main.py:39-274).

Differences by design: after the factory builds the declarative components
(AppStateSpec, clipper/profiler descriptors, loaders), `run` assembles ONE
TrainStepBuilder from them — the point where the reference's in-place wrapper chain
becomes a composed jit program — and restores the warmstart checkpoint into the
sharded state if the app_state spec carries a checkpoint path.
"""

from __future__ import annotations

import shutil
from pathlib import Path
from typing import Optional, Type

import yaml

from modalities_tpu.config.component_factory import ComponentFactory
from modalities_tpu.config.instantiation_models import TrainingComponentsInstantiationModel
from modalities_tpu.config.yaml_interp import Resolver, load_app_config_dict
from modalities_tpu.evaluator import Evaluator
from modalities_tpu.gym import Gym
from modalities_tpu.logging_broker.message_broker import MessageBroker
from modalities_tpu.logging_broker.messages import MessageTypes
from modalities_tpu.logging_broker.publisher import MessagePublisher
from modalities_tpu.registry.components import COMPONENTS
from modalities_tpu.registry.registry import ComponentEntity, Registry
from modalities_tpu.telemetry import Telemetry, set_active_telemetry, span
from modalities_tpu.trainer import Trainer
from modalities_tpu.training.train_step import TrainStepBuilder
from modalities_tpu.training.training_progress import TrainingProgress
from modalities_tpu.util import get_synced_experiment_id_of_run, get_total_number_of_trainable_parameters
from modalities_tpu.utils.logging import get_logger, print_rank_0

logger = get_logger(__name__)


class Main:
    def __init__(
        self,
        config_path: Path,
        experiments_root_path: Optional[Path] = None,
        additional_resolver_funs: Optional[dict[str, Resolver]] = None,
        experiment_id: Optional[str] = None,
    ) -> None:
        self.config_path = Path(config_path)
        if experiment_id is None:
            experiment_id = get_synced_experiment_id_of_run(self.config_path)
        self.experiment_id = experiment_id
        self.experiments_root_path = Path(experiments_root_path) if experiments_root_path else None
        self.config_dict = load_app_config_dict(
            self.config_path,
            experiments_root_path=self.experiments_root_path,
            experiment_id=self.experiment_id,
            additional_resolver_funs=additional_resolver_funs,
        )
        self.registry = Registry(COMPONENTS)
        self.component_factory = ComponentFactory(self.registry)

    def add_custom_component(self, component_key: str, variant_key: str, custom_component, custom_config) -> None:
        """Library-extension hook (reference main.py:61)."""
        self.registry.add_entity(
            ComponentEntity(component_key, variant_key, custom_component, custom_config)
        )

    def build_components(self, components_model_type: Type = TrainingComponentsInstantiationModel):
        # the whole config factory: model object, mesh, datasets, tokenizer. Spanned here,
        # where the work is, so that every caller (the CLI, the benchmark's modes,
        # `chip_smoke.py`) leaves it on the process's timeline
        with span("build_components"):
            return self.component_factory.build_components(self.config_dict, components_model_type)

    @staticmethod
    def build_step_functions(
        components, expose_grads: bool = False, stop_consensus: bool = False, materialize: bool = True
    ):
        """The one TrainStepBuilder assembled from the declarative components:
        what `run` trains with, what `validate_recipe` lowers with an abstract
        state (`materialize=False`), and what `chip_smoke.py` times. The span `init`
        is opened here and not round the call, so every caller gets it; the
        builder's `state_init` is its child."""
        app_state_spec = components.app_state
        clipper = components.gradient_clipper
        resilience = getattr(components, "resilience", None)
        with span("init"):
            return TrainStepBuilder(
                model=app_state_spec.model,
                loss_fn=components.loss_fn,
                optimizer_spec=app_state_spec.optimizer,
                scheduler_spec=app_state_spec.lr_scheduler,
                mesh_handle=components.device_mesh,
                gradient_acc_steps=components.settings.step_profile.gradient_accumulation_steps,
                grad_clip_norm=getattr(clipper, "max_norm", None),
                grad_clipper=clipper if hasattr(clipper, "build_transform") else None,
                expose_grads=expose_grads,
                anomaly_policy=resilience.anomaly_policy if resilience is not None else None,
                stop_consensus=stop_consensus,
            ).build(materialize=materialize)

    def run(self, components: TrainingComponentsInstantiationModel) -> None:
        # telemetry is on by default: use the configured component when present,
        # otherwise a default instance. The sink/artifact folder rides with the
        # experiment folder so every run leaves its goodput record next to its
        # results. Activated process-globally so deep call sites (checkpointing,
        # evaluator) reach it via the free `span()` — restored in `finally`.
        # chaos faults arm once per process from $MODALITIES_TPU_FAULTS so
        # subprocess chaos tests (and real drills) need no config change
        from modalities_tpu.resilience.faults import load_faults_from_env

        load_faults_from_env()
        telemetry = getattr(components, "telemetry", None) or Telemetry()
        # the sink lands next to evaluation_results.jsonl: prefer the explicit
        # constructor root, else the config's settings.paths.experiments_root_path
        # (the CLI `run` path, where Main gets no experiments_root_path argument)
        experiments_root = self.experiments_root_path
        if experiments_root is None:
            configured = (self.config_dict.get("settings", {}).get("paths", {}) or {}).get(
                "experiments_root_path"
            )
            experiments_root = Path(configured) if configured else None
        if experiments_root is not None:
            telemetry.set_output_folder(experiments_root / self.experiment_id / "telemetry")
        previous_telemetry = set_active_telemetry(telemetry)
        try:
            self._run_training(components, telemetry)
        finally:
            # seal the telemetry record on BOTH the success and the crash path —
            # a killed run with no goodput summary is the failure mode this PR
            # exists to prevent — and restore the previous active telemetry so
            # in-process back-to-back runs (tests) don't leak a closed sink.
            # This finally covers build/init failures too, not just gym.run.
            try:
                telemetry.close()
            except Exception:
                logger.exception("closing telemetry failed during shutdown")
            set_active_telemetry(previous_telemetry)

    def _run_training(self, components: TrainingComponentsInstantiationModel, telemetry: Telemetry) -> None:
        settings = components.settings

        # persist resolved config into the experiment folder (reference main.py:134-143)
        import jax

        if jax.process_index() == 0 and self.experiments_root_path is not None:
            exp_folder = self.experiments_root_path / self.experiment_id
            exp_folder.mkdir(parents=True, exist_ok=True)
            shutil.copy(self.config_path, exp_folder / self.config_path.name)
            with open(exp_folder / (self.config_path.name + ".resolved"), "w") as f:
                yaml.safe_dump(_to_plain(self.config_dict), f, sort_keys=False)

        app_state_spec = components.app_state
        step_profile = settings.step_profile
        resilience = getattr(components, "resilience", None)

        # stop-flag consensus resolved ONCE here so the builder (which compiles
        # the ballot read into the step) and the trainer (which injects the
        # vote) can never disagree. Probe ballot construction up front: if it
        # fails on this topology, run uncoordinated rather than crash at step 1.
        consensus_enabled = resilience is not None and resilience.consensus_enabled()
        if consensus_enabled:
            from modalities_tpu.resilience.coordination import VOTE_CONTINUE, make_ballot

            try:
                make_ballot(VOTE_CONTINUE, components.device_mesh)
            except Exception:
                logger.warning(
                    "stop-flag consensus disabled: ballot construction failed on "
                    "this topology — preemption falls back to local-only handling",
                    exc_info=True,
                )
                consensus_enabled = False

        # out-of-band peer-health heartbeat: detects the peers that can NEVER
        # vote in the stop ballot (dead or wedged processes) and converts the
        # otherwise-infinite collective hang into a diagnosed resumable exit
        heartbeat = None
        if resilience is not None:
            from modalities_tpu.resilience.heartbeat import cluster_context, set_active_monitor

            artifact_dir = (
                self.experiments_root_path / self.experiment_id / "telemetry"
                if self.experiments_root_path is not None
                else None
            )
            heartbeat = resilience.build_heartbeat(artifact_dir=artifact_dir)
            if heartbeat is not None:
                heartbeat.start()
                set_active_monitor(heartbeat)
            # the cluster view (rank/world/phase/peer ages) rides every watchdog
            # dump even when the heartbeat transport resolves disabled
            telemetry.register_watchdog_state_provider(lambda: {"cluster": cluster_context()})

        # debugging_enriched model variant -> per-rank stats logger + grads exposure
        debug_cfg = getattr(app_state_spec.model, "debugging_config", None)
        debug_stats_logger = None
        if debug_cfg is not None:
            from modalities_tpu.utils.debug_components import DebugStatsLogger

            debug_dir = debug_cfg.get("logging_dir_path")
            if debug_dir is None and self.experiments_root_path is not None:
                debug_dir = self.experiments_root_path / self.experiment_id / "debug"
            if debug_dir is not None:
                debug_stats_logger = DebugStatsLogger(
                    logging_dir_path=debug_dir,
                    tracked_ranks=debug_cfg.get("tracked_ranks"),
                    log_interval_steps=debug_cfg.get("log_interval_steps", 1),
                )
            else:
                logger.warning(
                    "debugging_enriched model requested but no logging_dir_path configured "
                    "and no experiments_root_path to derive one — debug stats are DISABLED"
                )

        step_functions = self.build_step_functions(
            components,
            expose_grads=debug_stats_logger is not None,
            stop_consensus=consensus_enabled,
        )

        if app_state_spec.checkpoint_dir_path is not None:
            with telemetry.span("checkpoint_restore"):
                loader = app_state_spec.checkpoint_loading
                if loader is None:
                    from modalities_tpu.checkpointing.orbax.orbax_checkpoint_loading import (
                        OrbaxCheckpointLoading,
                    )

                    loader = OrbaxCheckpointLoading()
                loader.load_app_state(
                    step_functions.app_state_handle, app_state_spec.checkpoint_dir_path
                )

        num_params = get_total_number_of_trainable_parameters(step_functions.app_state_handle.state)
        print_rank_0(f"experiment {self.experiment_id}: {num_params:,} trainable parameters")

        # message broker + publishers (reference main.py:234-274)
        message_broker = MessageBroker()
        message_broker.add_subscriber(MessageTypes.BATCH_PROGRESS_UPDATE, components.progress_subscriber)
        message_broker.add_subscriber(MessageTypes.EVALUATION_RESULT, components.evaluation_subscriber)
        progress_publisher = MessagePublisher(message_broker)
        results_publisher = MessagePublisher(message_broker)

        tokens_per_step = (
            step_profile.local_train_micro_batch_size
            * step_profile.sequence_length
            * step_profile.gradient_accumulation_steps
            * step_profile.dp_degree
        )
        progress_settings = settings.training_progress
        training_progress = TrainingProgress(
            num_seen_steps_current_run=0,
            num_seen_tokens_current_run=0,
            num_target_steps=settings.training_target.num_target_steps,
            num_target_tokens=settings.training_target.num_target_tokens,
            num_seen_steps_previous_run=progress_settings.num_seen_steps,
            num_seen_tokens_previous_run=progress_settings.global_num_seen_tokens,
        )

        trainer = Trainer(
            progress_publisher=progress_publisher,
            evaluation_result_publisher=results_publisher,
            gradient_acc_steps=step_profile.gradient_accumulation_steps,
            global_num_tokens_per_train_step=tokens_per_step,
            num_seen_train_steps=progress_settings.num_seen_steps,
            global_num_seen_tokens=progress_settings.global_num_seen_tokens,
            training_log_interval_in_steps=settings.intervals.training_log_interval_in_steps,
            mfu_calculator=components.mfu_calculator,
            profiler=components.profiler,
            debug_stats_logger=debug_stats_logger,
            device_feeder=components.device_feeder,
            telemetry=telemetry,
            anomaly_tracker=resilience.anomaly if resilience is not None else None,
            preemption=resilience.preemption if resilience is not None else None,
            stop_consensus=consensus_enabled,
        )
        evaluator = Evaluator(
            progress_publisher=progress_publisher,
            evaluation_result_publisher=results_publisher,
            device_feeder=components.device_feeder,
        )
        gym = Gym(trainer=trainer, evaluator=evaluator, loss_fun=components.loss_fn)
        if resilience is not None and resilience.preemption is not None:
            # installed for the training window only; `finally` restores the
            # previous handlers so in-process back-to-back runs (tests) and the
            # surrounding CLI keep their own SIGTERM/SIGINT semantics
            resilience.preemption.install()
        try:
            gym.run(
                step_functions=step_functions,
                train_data_loader=components.train_dataloader,
                evaluation_data_loaders=components.eval_dataloaders,
                checkpoint_saving=components.checkpoint_saving,
                training_progress=training_progress,
                evaluation_interval_in_steps=settings.intervals.evaluation_interval_in_steps,
                checkpointing_interval_in_steps=settings.intervals.checkpointing_interval_in_steps,
            )
        finally:
            if heartbeat is not None:
                from modalities_tpu.resilience.heartbeat import set_active_monitor

                set_active_monitor(None)
                heartbeat.stop()
            if resilience is not None and resilience.preemption is not None:
                resilience.preemption.uninstall()
            # the rich live display is process-global; leaving it running after a
            # crashed (or finished) run blocks every later live display in-process
            stop = getattr(components.progress_subscriber, "stop", None)
            if callable(stop):
                stop()


def _to_plain(obj):
    from pathlib import Path as _P

    if isinstance(obj, dict):
        return {k: _to_plain(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_to_plain(v) for v in obj]
    if isinstance(obj, _P):
        return str(obj)
    return obj

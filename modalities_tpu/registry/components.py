"""The built-in component catalog (reference: src/modalities/registry/components.py:187-531).

Same two-level keys (component_key.variant_key) as the reference wherever a component
exists on TPU; torch-only variants keep their names as aliases onto the TPU-native
equivalents (fsdp1_wrapped -> GSPMD sharding, dcp -> orbax) so reference YAMLs load.
"""

from __future__ import annotations

from modalities_tpu.checkpointing.checkpoint_saving import CheckpointSaving
from modalities_tpu.checkpointing.checkpoint_saving_strategies import (
    SaveEveryKStepsCheckpointingStrategy,
    SaveKMostRecentCheckpointsStrategy,
)
from modalities_tpu.checkpointing.orbax.orbax_checkpoint_loading import OrbaxCheckpointLoading
from modalities_tpu.checkpointing.orbax.orbax_checkpoint_saving import OrbaxCheckpointSaving
from modalities_tpu.checkpointing.stateful.app_state_factory import AppStateFactory
from modalities_tpu.config import config as cfg
from modalities_tpu.dataloader.collate_fns.collator_fn_wrapper_for_loss_masking import (
    LossMaskingCollateFnWrapper,
)
from modalities_tpu.dataloader.dataloader_factory import DataloaderFactory
from modalities_tpu.dataloader.device_feeder import DeviceFeeder
from modalities_tpu.telemetry import Telemetry
from modalities_tpu.resilience import Resilience
from modalities_tpu.running_env.xla_flags import XlaPerformanceFlags
from modalities_tpu.dataloader.dataset import DummyDataset, DummyDatasetConfig
from modalities_tpu.dataloader.dataset_factory import DatasetFactory
from modalities_tpu.dataloader.sampler_factory import BatchSamplerFactory, SamplerFactory
from modalities_tpu.dataloader.samplers import RandomSampler, SequentialSampler
from modalities_tpu.loss_functions import CLMCrossEntropyLoss, LoopedExitLoss, NCELoss
from modalities_tpu.logging_broker.subscriber_impl.progress_subscriber import (
    DummyProgressSubscriber,
    ProgressSubscriberFactory,
    RichProgressSubscriber,
)
from modalities_tpu.logging_broker.subscriber_impl.results_subscriber import (
    DummyResultSubscriber,
    EvaluationResultToDiscSubscriber,
    RichResultSubscriber,
    WandBEvaluationResultSubscriber,  # noqa: F401 — re-exported for library users
    get_wandb_result_subscriber,
)
from modalities_tpu.models.components import layer_norms as _ln
from modalities_tpu.models.gpt2.collator import GPT2LLMCollateFn
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
from modalities_tpu.models.huggingface.huggingface_model import HuggingFacePretrainedModel
from modalities_tpu.models.model_factory import ModelFactory
from modalities_tpu.nn.model_initialization.composed_initialization import ComposedModelInitialization
from modalities_tpu.nn.model_initialization.llama3_initialization import Llama3Initializer
from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory
from modalities_tpu.optimizers.scheduler_factory import (
    ConstantLRScheduler,
    CosineAnnealingLRScheduler,
    DummyLRScheduler,
    LinearLRScheduler,
    LinearWarmupCosineAnnealingLRScheduler,
    OneCycleLRScheduler,
    StepLRScheduler,
)
from modalities_tpu.parallel import pipeline_components as _pl
from modalities_tpu.registry.registry import ComponentEntity
from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.utils.debug_components import Debugging, HookRegistration
from modalities_tpu.tokenization.tokenizer_wrapper import PreTrainedHFTokenizer, PreTrainedSPTokenizer
from modalities_tpu.training.gradient_clipping import (
    DummyGradientClipper,
    GradientClipper,
    LoggingOnlyGradientClipper,
)
from modalities_tpu.utils.mfu import GPT2MFUCalculator
from modalities_tpu.utils.number_conversion import (
    LocalNumBatchesFromNumSamplesConfig,
    LocalNumBatchesFromNumTokensConfig,
    NumberConversion,
    NumberConversionFromCheckpointPathConfig,
    NumSamplesFromNumTokensConfig,
    NumStepsFromNumSamplesConfig,
    NumStepsFromNumTokensConfig,
    NumStepsFromRawDatasetIndexConfig,
    NumTokensFromNumStepsConfig,
    NumTokensFromPackedMemMapDatasetContinuousConfig,
)
from modalities_tpu.utils.profilers.profilers import (
    SteppableCombinedProfiler,
    SteppableKernelProfiler,
    SteppableMemoryProfiler,
    SteppableNoProfiler,
)


def _fsdp1_checkpointed_guard(**kwargs):
    """reference model/optimizer `fsdp1_checkpointed` variants load FSDP1-era state
    at build time; whole-state restore here is `app_state` variant `dcp` with
    `checkpoint_loading` variant `orbax` (see configs/config_lorem_ipsum_tpu_warmstart.yaml)."""
    from modalities_tpu.exceptions import ConfigError

    raise ConfigError(
        "fsdp1_checkpointed has no SPMD analogue: restore model+optimizer state via "
        "app_state.dcp + checkpoint_loading.orbax (warmstart path), not a build-time "
        "FSDP1 state load. See configs/config_lorem_ipsum_tpu_warmstart.yaml."
    )


def _fsdp1_alias_checkpoint_loading(
    global_rank=0, elastic=True, block_names=None, mixed_precision_settings=None,
    sharding_strategy=None,
):
    """checkpoint_loading.fsdp1: Orbax loader behind the reference's name; the
    FSDP1 wrapper-rebuild knobs are config-parity only (see
    FSDP1AliasCheckpointLoadingConfig)."""
    del block_names, mixed_precision_settings, sharding_strategy
    return OrbaxCheckpointLoading(global_rank=global_rank, elastic=elastic)


def _torch_alias_checkpoint_loading(global_rank=0, elastic=True, device=None, precision=None):
    """checkpoint_loading.torch: Orbax loader behind the reference's name; the
    torch-only device/precision knobs were already warned about at config
    validation (TorchAliasCheckpointLoadingConfig) and are dropped here."""
    del device, precision
    return OrbaxCheckpointLoading(global_rank=global_rank, elastic=elastic)


def _random_batch_generator(**kwargs):
    from modalities_tpu.utils.profilers.steppable_components import RandomDatasetBatchGenerator

    return RandomDatasetBatchGenerator(**kwargs)


def _steppable_kernel_profiler(**kwargs):
    """Drops the torch.profiler-only knobs the config accepted (and warned about)
    for reference-YAML compat before constructing the jax.profiler-backed tracer."""
    for torch_only in ("profiler_activities", "profile_memory", "record_shapes", "with_flops",
                       "with_modules", "tracked_ranks"):
        kwargs.pop(torch_only, None)
    return SteppableKernelProfiler(**kwargs)


def _steppable_forward_pass(model, batch_generator, loss_fn=None, optimizer=None, device_mesh=None,
                            include_backward=None, gradient_accumulation_steps=1):
    from modalities_tpu.training.train_step import TrainStepBuilder
    from modalities_tpu.utils.profilers.steppable_components import SteppableForwardPass

    # reference semantics (steppable_components.py:12): no optimizer -> forward-only
    if include_backward is None:
        include_backward = optimizer is not None
    if loss_fn is None:
        loss_fn = CLMCrossEntropyLoss(
            target_key=getattr(batch_generator, "target_key", "target_ids"),
            prediction_key=model.prediction_key,
        )
    if optimizer is None:
        # state init needs an optimizer tree even when only the forward is stepped
        optimizer = OptimizerFactory.get_adam_w(
            lr=1e-4, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.0,
            weight_decay_groups_excluded=[], wrapped_model=model,
        )
    def build_step_functions():
        return TrainStepBuilder(
            model=model,
            loss_fn=loss_fn,
            optimizer_spec=optimizer,
            mesh_handle=device_mesh,
            gradient_acc_steps=gradient_accumulation_steps,
        ).build()

    return SteppableForwardPass(
        build_step_functions,  # thunk: state materializes at the first profiled step
        batch_generator,
        include_backward=include_backward,
        gradient_accumulation_steps=gradient_accumulation_steps,
    )


def _repeating_dataloader(**kwargs):
    from modalities_tpu.dataloader.repeating_dataloader import RepeatingDataLoader

    return RepeatingDataLoader(**kwargs)


def _coca_config():
    from modalities_tpu.models.coca.coca_model import CoCaConfig

    return CoCaConfig


def _vit_config():
    from modalities_tpu.models.vision_transformer.vision_transformer_model import VisionTransformerConfig

    return VisionTransformerConfig


def _coca(**kwargs):
    from modalities_tpu.models.coca.coca_model import CoCa

    return CoCa(**kwargs)


def _vision_transformer(**kwargs):
    from modalities_tpu.models.vision_transformer.vision_transformer_model import VisionTransformer

    return VisionTransformer(**kwargs)


def _coca_collator(**kwargs):
    from modalities_tpu.models.coca.coca_model import CoCaCollateFn

    return CoCaCollateFn(**kwargs)


def _scheduler_entity(variant: str, scheduler_cls, config_cls) -> ComponentEntity:
    def build(**kwargs):
        return scheduler_cls(name=variant, **kwargs)

    return ComponentEntity("scheduler", variant, build, config_cls)


COMPONENTS: list[ComponentEntity] = [
    # models (reference components.py: models section)
    ComponentEntity("model", "gpt2", GPT2LLM, GPT2LLMConfig),
    ComponentEntity("model", "gpt2_tp", lambda model, device_mesh: model, cfg.GPT2TPModelConfig),
    ComponentEntity(
        "model", "huggingface_pretrained_model", HuggingFacePretrainedModel, cfg.HuggingFacePretrainedModelConfig
    ),
    ComponentEntity("model", "coca", _coca, _coca_config()),
    ComponentEntity("model", "vision_transformer", _vision_transformer, _vit_config()),
    ComponentEntity("model", "fsdp2_wrapped", ModelFactory.get_fsdp2_wrapped_model, cfg.FSDP2WrappedModelConfig),
    ComponentEntity("model", "fsdp1_wrapped", ModelFactory.get_fsdp1_wrapped_model, cfg.FSDP1WrappedModelConfig),
    ComponentEntity("model", "model_initialized", ModelFactory.get_weight_initialized_model, cfg.WeightInitializedModelConfig),
    ComponentEntity(
        "model", "activation_checkpointed", ModelFactory.get_activation_checkpointed_model, cfg.ActivationCheckpointedModelConfig
    ),
    ComponentEntity(
        "model", "activation_checkpointed_fsdp1", ModelFactory.get_activation_checkpointed_model, cfg.ActivationCheckpointedModelConfig
    ),
    ComponentEntity("model", "compiled", ModelFactory.get_compiled_model, cfg.CompiledModelConfig),
    ComponentEntity(
        "model", "debugging_enriched", ModelFactory.get_debugging_enriched_model, cfg.DebuggingEnrichedModelConfig
    ),
    ComponentEntity("model", "pipelined", ModelFactory.get_pipelined_model, cfg.PipelinedModelConfig),
    # device mesh
    ComponentEntity("device_mesh", "default", get_device_mesh, cfg.DeviceMeshConfig),
    # model initialization
    ComponentEntity("model_initialization", "composed", ComposedModelInitialization, cfg.ComposedInitializationConfig),
    ComponentEntity(
        "model_initialization", "gpt2_llama3_like", Llama3Initializer, cfg.Llama3InitializerConfig
    ),
    # losses
    ComponentEntity("loss", "clm_cross_entropy_loss", CLMCrossEntropyLoss, cfg.CLMCrossEntropyLossConfig),
    ComponentEntity("loss", "looped_exit_loss", LoopedExitLoss, cfg.LoopedExitLossConfig),
    ComponentEntity("loss", "nce_loss", NCELoss, cfg.NCELossConfig),
    # optimizers
    ComponentEntity("optimizer", "adam", OptimizerFactory.get_adam, cfg.AdamOptimizerConfig),
    ComponentEntity("optimizer", "adam_w", OptimizerFactory.get_adam_w, cfg.AdamWOptimizerConfig),
    # app state
    ComponentEntity("app_state", "raw", AppStateFactory.get_raw_app_state, cfg.RawAppStateConfig),
    ComponentEntity("app_state", "dcp", AppStateFactory.get_dcp_checkpointed_app_state_, cfg.DCPAppStateConfig),
    # schedulers
    _scheduler_entity("dummy_lr", DummyLRScheduler, cfg.DummyLRSchedulerConfig),
    _scheduler_entity("step_lr", StepLRScheduler, cfg.StepLRSchedulerConfig),
    _scheduler_entity("constant_lr", ConstantLRScheduler, cfg.ConstantLRSchedulerConfig),
    _scheduler_entity("linear_lr", LinearLRScheduler, cfg.LinearLRSchedulerConfig),
    _scheduler_entity("onecycle_lr", OneCycleLRScheduler, cfg.OneCycleLRSchedulerConfig),
    _scheduler_entity("cosine_annealing_lr", CosineAnnealingLRScheduler, cfg.CosineAnnealingLRSchedulerConfig),
    _scheduler_entity(
        "linear_warmup_cosine_annealing_lr",
        LinearWarmupCosineAnnealingLRScheduler,
        cfg.LinearWarmupCosineAnnealingLRSchedulerConfig,
    ),
    # tokenizers
    ComponentEntity("tokenizer", "pretrained_hf_tokenizer", PreTrainedHFTokenizer, cfg.PreTrainedHFTokenizerConfig),
    ComponentEntity("tokenizer", "pretrained_sp_tokenizer", PreTrainedSPTokenizer, cfg.PreTrainedSPTokenizerConfig),
    # datasets
    ComponentEntity("dataset", "dummy_dataset", DatasetFactory.get_dummy_dataset, DummyDatasetConfig),
    ComponentEntity("dataset", "mem_map_dataset", DatasetFactory.get_mem_map_dataset, cfg.MemMapDatasetConfig),
    ComponentEntity(
        "dataset",
        "packed_mem_map_dataset_continuous",
        DatasetFactory.get_packed_mem_map_dataset_continuous,
        cfg.PackedMemMapDatasetContinuousConfig,
    ),
    ComponentEntity(
        "dataset",
        "packed_mem_map_dataset_megatron",
        DatasetFactory.get_packed_mem_map_dataset_megatron,
        cfg.PackedMemMapDatasetMegatronConfig,
    ),
    ComponentEntity("dataset", "combined", DatasetFactory.get_combined_dataset, cfg.CombinedDatasetConfig),
    # samplers
    ComponentEntity(
        "sampler", "resumable_distributed_sampler", SamplerFactory.create_resumable_sampler, cfg.ResumableDistributedSamplerConfig
    ),
    ComponentEntity(
        "sampler",
        "resumable_distributed_multi_dim_sampler",
        SamplerFactory.create_resumable_distributed_multi_dim_sampler,
        cfg.ResumableDistributedMultiDimSamplerConfig,
    ),
    ComponentEntity("sampler", "sequential_sampler", SequentialSampler, cfg.SequentialSamplerConfig),
    ComponentEntity("sampler", "random_sampler", RandomSampler, cfg.RandomSamplerConfig),
    ComponentEntity("batch_sampler", "default", BatchSamplerFactory.create_batch_sampler, cfg.BatchSamplerConfig),
    # collators
    ComponentEntity("collate_fn", "gpt_2_llm_collator", GPT2LLMCollateFn, cfg.GPT2LLMCollateFnConfig),
    ComponentEntity(
        "collate_fn", "mask_loss_collator_wrapper", LossMaskingCollateFnWrapper, cfg.LossMaskingCollateFnWrapperConfig
    ),
    ComponentEntity("collate_fn", "coca_collator", _coca_collator, cfg.CoCaCollatorConfig),
    # dataloaders
    ComponentEntity("data_loader", "default", DataloaderFactory.get_dataloader, cfg.LLMDataLoaderConfig),
    ComponentEntity("data_loader", "repeating_data_loader", _repeating_dataloader, cfg.RepeatingDataLoaderConfig),
    ComponentEntity("device_feeder", "default", DeviceFeeder, cfg.DeviceFeederConfig),
    # telemetry (spans + goodput + watchdog + sink; on by default via Main)
    ComponentEntity("telemetry", "default", Telemetry, cfg.TelemetryConfig),
    # resilience (anomaly policy + preemption shutdown + supervisor knobs)
    ComponentEntity("resilience", "default", Resilience, cfg.ResilienceConfig),
    # performance (XLA latency-hiding / async-collective flags; the CLI applies the
    # same block pre-backend-init, this entity validates it and exposes it to code)
    ComponentEntity("performance", "xla_flags", XlaPerformanceFlags, cfg.XlaFlagsConfig),
    # checkpointing
    ComponentEntity(
        "checkpoint_saving_strategy",
        "save_every_k_steps_checkpointing_strategy",
        SaveEveryKStepsCheckpointingStrategy,
        cfg.SaveEveryKStepsCheckpointingStrategyConfig,
    ),
    ComponentEntity(
        "checkpoint_saving_strategy",
        "save_k_most_recent_checkpoints_strategy",
        SaveKMostRecentCheckpointsStrategy,
        cfg.SaveKMostRecentCheckpointsStrategyConfig,
    ),
    ComponentEntity("checkpoint_saving_execution", "dcp", OrbaxCheckpointSaving, cfg.OrbaxCheckpointSavingConfig),
    ComponentEntity("checkpoint_saving_execution", "orbax", OrbaxCheckpointSaving, cfg.OrbaxCheckpointSavingConfig),
    ComponentEntity("checkpoint_saving", "default", CheckpointSaving, cfg.CheckpointSavingConfig),
    ComponentEntity("checkpoint_loading", "dcp", OrbaxCheckpointLoading, cfg.OrbaxCheckpointLoadingConfig),
    ComponentEntity("checkpoint_loading", "orbax", OrbaxCheckpointLoading, cfg.OrbaxCheckpointLoadingConfig),
    # gradient clippers (fsdp* names kept as aliases)
    ComponentEntity("gradient_clipper", "fsdp2", GradientClipper, cfg.GradientClipperConfig),
    ComponentEntity("gradient_clipper", "fsdp1", GradientClipper, cfg.GradientClipperConfig),
    ComponentEntity(
        "gradient_clipper", "fsdp2_logging_only", LoggingOnlyGradientClipper, cfg.LoggingOnlyGradientClipperConfig
    ),
    ComponentEntity("gradient_clipper", "dummy", DummyGradientClipper, None),
    # progress subscribers
    ComponentEntity("progress_subscriber", "dummy", DummyProgressSubscriber, None),
    ComponentEntity(
        "progress_subscriber",
        "rich",
        ProgressSubscriberFactory.get_rich_progress_subscriber,
        cfg.RichProgressSubscriberConfig,
    ),
    # results subscribers
    ComponentEntity("results_subscriber", "dummy", DummyResultSubscriber, None),
    ComponentEntity("results_subscriber", "rich", RichResultSubscriber, cfg.RichResultSubscriberConfig),
    ComponentEntity(
        "results_subscriber",
        "save_to_disc",
        EvaluationResultToDiscSubscriber,
        cfg.EvaluationResultToDiscSubscriberConfig,
    ),
    ComponentEntity(
        "results_subscriber", "wandb", get_wandb_result_subscriber, cfg.WandBEvaluationResultSubscriberConfig
    ),
    # layer norms (referenced via norm wrapper configs inside model configs)
    # mfu
    ComponentEntity("mfu_calculator", "gpt2", GPT2MFUCalculator, cfg.GPT2MFUCalculatorConfig),
    # profiler harness steppables
    ComponentEntity("batch_generator", "random_dataset_batch_generator", _random_batch_generator,
                    cfg.RandomDatasetBatchGeneratorConfig),
    ComponentEntity("steppable_component", "forward_pass", _steppable_forward_pass,
                    cfg.SteppableForwardPassConfig),
    # profilers
    ComponentEntity("profiler", "no_profiler", SteppableNoProfiler, None),
    ComponentEntity("profiler", "kernel_profiler", _steppable_kernel_profiler, cfg.SteppableKernelProfilerConfig),
    ComponentEntity("profiler", "memory_profiler", SteppableMemoryProfiler, cfg.SteppableMemoryProfilerConfig),
    ComponentEntity("profiler", "combined_profiler", SteppableCombinedProfiler, cfg.SteppableCombinedProfilerConfig),
    # number conversion (13 variants, reference components.py number_conversion section)
    ComponentEntity(
        "number_conversion",
        "local_num_batches_from_num_samples",
        NumberConversion.get_local_num_batches_from_num_samples,
        LocalNumBatchesFromNumSamplesConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "local_num_batches_from_num_tokens",
        NumberConversion.get_local_num_batches_from_num_tokens,
        LocalNumBatchesFromNumTokensConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_samples_from_num_tokens",
        NumberConversion.get_num_samples_from_num_tokens,
        NumSamplesFromNumTokensConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_steps_from_num_samples",
        NumberConversion.get_num_steps_from_num_samples,
        NumStepsFromNumSamplesConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_steps_from_num_tokens",
        NumberConversion.get_num_steps_from_num_tokens,
        NumStepsFromNumTokensConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_tokens_from_num_steps",
        NumberConversion.get_num_tokens_from_num_steps,
        NumTokensFromNumStepsConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "last_step_from_checkpoint_path",
        NumberConversion.get_last_step_from_checkpoint_path,
        NumberConversionFromCheckpointPathConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_seen_steps_from_checkpoint_path",
        NumberConversion.get_num_seen_steps_from_checkpoint_path,
        NumberConversionFromCheckpointPathConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "global_num_seen_tokens_from_checkpoint_path",
        NumberConversion.get_global_num_seen_tokens_from_checkpoint_path,
        NumberConversionFromCheckpointPathConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "global_num_target_tokens_from_checkpoint_path",
        NumberConversion.get_global_num_target_tokens_from_checkpoint_path,
        NumberConversionFromCheckpointPathConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_target_steps_from_checkpoint_path",
        NumberConversion.get_num_target_steps_from_checkpoint_path,
        NumberConversionFromCheckpointPathConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_tokens_from_packed_mem_map_dataset_continuous",
        NumberConversion.get_num_tokens_from_packed_mem_map_dataset_continuous,
        NumTokensFromPackedMemMapDatasetContinuousConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "num_steps_from_raw_dataset_index",
        NumberConversion.get_num_steps_from_raw_dataset_index,
        NumStepsFromRawDatasetIndexConfig,
    ),
    ComponentEntity(
        "number_conversion",
        "parallel_degree",
        NumberConversion.get_parallel_degree,
        cfg.ParallelDegreeConfig,
    ),
    # ---------------- reference pipeline config surface (pipeline_components.py:
    # the torch module-splitting graph re-expressed as SPMD descriptors; the
    # scheduled node is the observable one — it applies the schedule to the model
    # spec that TrainStepBuilder compiles)
    ComponentEntity(
        "pipeline", "staged", _pl.PipelineFactory.get_staged_pipeline, cfg.StagedPipelineConfig
    ),
    ComponentEntity(
        "pipeline", "scheduled", _pl.PipelineFactory.get_scheduled_pipeline, cfg.ScheduledPipelineConfig
    ),
    ComponentEntity(
        "pipeline",
        "selector",
        _pl.ComponentSelectorFromPipeline.select,
        cfg.ComponentSelectorFromPipelineConfig,
    ),
    ComponentEntity("pipeline", "builder", _pl.PipelineFactory.get_pipeline, cfg.PipelineBuilderConfig),
    ComponentEntity(
        "stages_generator",
        "gpt2_stages_generator",
        _pl.GPT2LLMStagesGenerator,
        cfg.GPT2LLMStagesGeneratorConfig,
    ),
    # ---------------- layer norms (reference components.py:396-398; resolve to the
    # NormSpec the linen modules consume — for custom-model component graphs)
    ComponentEntity("layer_norm", "rms_norm", _ln.build_rms_norm_spec, _ln.RMSLayerNormConfig),
    ComponentEntity("layer_norm", "layer_norm", _ln.build_layer_norm_spec, _ln.LayerNormConfig),
    ComponentEntity(
        "layer_norm", "pytorch_rms_norm", _ln.build_pytorch_rms_norm_spec, _ln.PytorchRMSLayerNormConfig
    ),
    # ---------------- debugging components (reference debug_components.py)
    ComponentEntity("debugging", "settings", Debugging, cfg.DebuggingConfig),
    ComponentEntity(
        "model_debugging_hook", "nan_hook", HookRegistration.register_nan_hooks, cfg.NaNHookConfig
    ),
    ComponentEntity(
        "model_debugging_hook",
        "print_forward_hook",
        HookRegistration.register_print_forward_hooks,
        cfg.PrintForwardHookConfig,
    ),
    # ---------------- reference-name aliases (same machinery, reference variant
    # names, so reference YAMLs resolve unchanged)
    ComponentEntity("steppable_profiler", "no_profiler", SteppableNoProfiler, None),
    ComponentEntity(
        "steppable_profiler", "kernel_tracing", _steppable_kernel_profiler, cfg.SteppableKernelProfilerConfig
    ),
    ComponentEntity(
        "steppable_profiler", "memory_tracing", SteppableMemoryProfiler, cfg.SteppableMemoryProfilerConfig
    ),
    ComponentEntity(
        "steppable_profiler", "combined", SteppableCombinedProfiler, cfg.SteppableCombinedProfilerConfig
    ),
    ComponentEntity(
        "dataset_batch_generator",
        "random",
        _random_batch_generator,
        cfg.RandomDatasetBatchGeneratorConfig,
    ),
    ComponentEntity(
        "results_subscriber",
        "to_disc",
        EvaluationResultToDiscSubscriber,
        cfg.EvaluationResultToDiscSubscriberConfig,
    ),
    # the reference's plain (non-resumable) DistributedSampler is the resumable one
    # with skip_num_global_samples=0 (its config default)
    ComponentEntity(
        "sampler",
        "distributed_sampler",
        SamplerFactory.create_resumable_sampler,
        cfg.ResumableDistributedSamplerConfig,
    ),
    ComponentEntity(
        "gradient_clipper",
        "fsdp1_logging_only",
        LoggingOnlyGradientClipper,
        cfg.LoggingOnlyGradientClipperConfig,
    ),
    # FSDP1/torch checkpoint IO names: the checkpoint format in this framework is
    # Orbax regardless of the sharding era the name comes from — the aliases load/
    # save the same sharded checkpoints (reference fsdp_checkpoint_saving.py:32-176,
    # torch_checkpoint_loading.py)
    ComponentEntity(
        "checkpoint_loading",
        "fsdp1",
        _fsdp1_alias_checkpoint_loading,
        cfg.FSDP1AliasCheckpointLoadingConfig,
    ),
    # `torch` alias: accepts the reference's device/precision fields but warns that
    # they are ignored (format is Orbax) — see TorchAliasCheckpointLoadingConfig
    ComponentEntity(
        "checkpoint_loading",
        "torch",
        _torch_alias_checkpoint_loading,
        cfg.TorchAliasCheckpointLoadingConfig,
    ),
    ComponentEntity(
        "checkpoint_saving_execution", "fsdp1", OrbaxCheckpointSaving, cfg.OrbaxCheckpointSavingConfig
    ),
    # FSDP1 build-time state loading has no SPMD analogue — whole-state restore is
    # app_state.dcp + checkpoint_loading.orbax; fail loudly with that guidance
    ComponentEntity("model", "fsdp1_checkpointed", _fsdp1_checkpointed_guard, cfg.FSDP1CheckpointedGuardConfig),
    ComponentEntity(
        "optimizer", "fsdp1_checkpointed", _fsdp1_checkpointed_guard, cfg.FSDP1CheckpointedGuardConfig
    ),
]

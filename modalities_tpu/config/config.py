"""Pydantic config schemas for every registry component variant
(reference: src/modalities/config/config.py — ~60 models).

Field names mirror the reference so its YAML configs translate directly; torch-only
knobs (foreach/fused, block_names, ...) are accepted and ignored by the TPU
implementations, documented per-field.
"""

from __future__ import annotations

import warnings
from enum import Enum
from pathlib import Path
from typing import Annotated, Any, Literal, Optional

from pydantic import AliasChoices, BaseModel, Field, field_validator, model_validator

from modalities_tpu.config.pydantic_if_types import (
    PydanticAppStateType,
    PydanticLossIFType,
    PydanticBatchSamplerIFType,
    PydanticCheckpointLoadingIFType,
    PydanticCheckpointSavingExecutionIFType,
    PydanticCheckpointSavingStrategyIFType,
    PydanticCollateFnIFType,
    PydanticDatasetIFType,
    PydanticDeviceMeshIFType,
    PydanticLLMDataLoaderIFType,
    PydanticModelIFType,
    PydanticModelInitializationIFType,
    PydanticOptimizerIFType,
    PydanticPipelineIFType,
    PydanticSamplerIFType,
    PydanticStagesGeneratorIFType,
    PydanticTokenizerIFType,
)

# ---------------------------------------------------------------------------- misc


class ProcessGroupBackendType(str, Enum):
    nccl = "nccl"  # accepted for config compat; TPU uses XLA collectives
    xla = "xla"


class PassType(str, Enum):
    BY_REFERENCE = "BY_REFERENCE"
    BY_VALUE = "BY_VALUE"


class ReferenceConfig(BaseModel):
    instance_key: str
    pass_type: PassType


class MixedPrecisionSettings(str, Enum):
    """Reference env_utils.py:72-88 mixed-precision enums; on TPU these select the
    param/compute dtype pair for the train step."""

    BF_16 = "BF_16"
    BF_16_WORKING = "BF_16_WORKING"
    FP_16 = "FP_16"
    FP_32 = "FP_32"
    MIXED_PRECISION_MEGATRON = "MIXED_PRECISION_MEGATRON"


# ---------------------------------------------------------------------- device mesh


class DeviceMeshConfig(BaseModel):
    device_type: str = "tpu"
    data_parallel_replicate_degree: Annotated[int, Field(strict=True, ge=-1)] = 1
    data_parallel_shard_degree: Annotated[int, Field(strict=True, ge=-1)] = -1
    tensor_parallel_degree: Annotated[int, Field(strict=True, gt=0)] = 1
    pipeline_parallel_degree: Annotated[int, Field(strict=True, gt=0)] = 1
    context_parallel_degree: Annotated[int, Field(strict=True, gt=0)] = 1
    enable_loss_parallel: Optional[bool] = False
    # ZeRO-1 optimizer-state sharding over dp_replicate (see running_env/device_mesh.py)
    zero_stage: Annotated[int, Field(strict=True, ge=0, le=1)] = 0
    # cross-slice data parallelism over DCN: -1 auto-infers the degree from the
    # devices' slice structure (multi-slice pods get the outer dcn axis, everything
    # else resolves to 1); an explicit degree > 1 emulates multi-slice on one slice
    dcn_parallel_degree: Annotated[int, Field(strict=True, ge=-1)] = -1
    world_size: Annotated[int, Field(strict=True, gt=0)]


# -------------------------------------------------------------------------- models


class FSDP2WrappedModelConfig(BaseModel):
    model: PydanticModelIFType
    device_mesh: Optional[PydanticDeviceMeshIFType] = None
    mixed_precision_settings: Optional[dict | str] = None
    block_names: Optional[list[str]] = None  # torch knob; sharding is rule-based here
    layers_per_fsdp_unit: Optional[int] = None  # torch knob
    reshard_after_forward: bool = True  # torch knob; XLA schedules resharding


class FSDP1WrappedModelConfig(BaseModel):
    """reference FSDPWrappedModelConfig (config.py:264-285) — the deprecated FSDP1
    wrap schema its fsdp1/coca YAMLs still use. The enum *names* are validated here;
    the mapping onto the GSPMD path (strategy → mesh rules, MixedPrecisionSettings →
    param/reduce dtypes, fp16 → bf16 on TPU) happens in
    ModelFactory.get_fsdp1_wrapped_model. `sync_module_states` is torch-only
    (GSPMD's jitted init is identical across ranks by construction) and ignored."""

    model: PydanticModelIFType
    sync_module_states: bool = False
    mixed_precision_settings: Optional[str] = None
    sharding_strategy: str = "FULL_SHARD"
    block_names: Optional[list[str]] = None

    @model_validator(mode="after")
    def _validate_enum_names(self) -> "FSDP1WrappedModelConfig":
        known_mp = {"FP_16", "BF_16", "BF_16_WORKING", "MIXED_PRECISION_MEGATRON", "FP_32", "NO_MIXED_PRECISION"}
        if self.mixed_precision_settings is not None and self.mixed_precision_settings not in known_mp:
            raise ValueError(
                f"unknown mixed_precision_settings {self.mixed_precision_settings!r}; known: {sorted(known_mp)}"
            )
        known_strategies = {"FULL_SHARD", "SHARD_GRAD_OP", "NO_SHARD", "HYBRID_SHARD", "_HYBRID_SHARD_ZERO2"}
        if self.sharding_strategy not in known_strategies:
            raise ValueError(
                f"unknown sharding_strategy {self.sharding_strategy!r}; known: {sorted(known_strategies)}"
            )
        return self


class CompiledModelConfig(BaseModel):
    model: PydanticModelIFType
    block_names: Optional[list[str]] = None
    fullgraph: Optional[bool] = None
    debug: Optional[bool] = None


class ActivationCheckpointedModelConfig(BaseModel):
    model: PydanticModelIFType
    activation_checkpointing_variant: str = "full_activation_checkpointing"
    layers_fqn: Optional[str] = None
    ac_freq: Annotated[int, Field(strict=True, ge=1)] = 1
    save_list: Optional[list[str]] = None
    device_mesh: Optional[PydanticDeviceMeshIFType] = None


class WeightInitializedModelConfig(BaseModel):
    model: PydanticModelIFType
    model_initializer: PydanticModelInitializationIFType


class GPT2TPModelConfig(BaseModel):
    """TP variant: under GSPMD the TP plan is the sharding rule set; this variant just
    asserts the mesh has a tp axis (reference model_factory.py:657-766)."""

    model: PydanticModelIFType
    device_mesh: PydanticDeviceMeshIFType


class DebuggingEnrichedModelConfig(BaseModel):
    model: PydanticModelIFType
    logging_dir_path: Optional[Path] = None
    tracked_ranks: Optional[list[int]] = None
    log_interval_steps: Annotated[int, Field(strict=True, ge=1)] = 1


class PipelinedModelConfig(BaseModel):
    """Pipeline schedule selection (reference ScheduledPipelineConfig)."""

    model: PydanticModelIFType
    pp_schedule_name: str = "1f1b"
    num_microbatches: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    batch_size: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    microbatch_size: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    num_virtual_stages: Optional[Annotated[int, Field(strict=True, ge=1)]] = None

    @model_validator(mode="after")
    def _validate_schedule_virtual_stages(self) -> "PipelinedModelConfig":
        """Schedule/num_virtual_stages compatibility at CONFIG-build time: the same
        rules parallel/pipeline_schedules.py enforces, surfaced before any component
        is built (a bad YAML used to die as a ValueError deep inside trace time).
        Unknown schedule names pass through — the model factory owns that error."""
        name = self.pp_schedule_name.strip().lower()
        if name in ("zbvzerobubble", "zb_v", "zbv_zero_bubble"):
            name = "zbv"
        if name in ("dualpipe_v", "dual_pipe_v", "scheduledualpipev"):
            name = "dualpipev"
        if name in ("zbv", "dualpipev") and self.num_virtual_stages not in (None, 1, 2):
            raise ValueError(
                f"pp_schedule_name: {self.pp_schedule_name!r} uses exactly 2 virtual "
                f"chunks (the V shape); set num_virtual_stages to 2 or leave it unset "
                f"(got num_virtual_stages: {self.num_virtual_stages})"
            )
        if name == "interleaved_1f1b" and (
            self.num_virtual_stages is not None and self.num_virtual_stages < 2
        ):
            raise ValueError(
                "pp_schedule_name: 'interleaved_1f1b' requires num_virtual_stages >= 2 "
                f"(got num_virtual_stages: {self.num_virtual_stages})"
            )
        if (
            name in ("gpipe", "1f1b")
            and self.num_virtual_stages is not None
            and self.num_virtual_stages != 1
        ):
            raise ValueError(
                f"num_virtual_stages: {self.num_virtual_stages} requires "
                f"pp_schedule_name: 'interleaved_1f1b' (got pp_schedule_name: "
                f"{self.pp_schedule_name!r})"
            )
        return self


class HuggingFacePretrainedModelConfig(BaseModel):
    model_type: str
    model_name: str
    sample_key: str
    prediction_key: str
    huggingface_prediction_subscription_key: Optional[str] = None
    kwargs: Optional[dict] = None


# ----------------------------------------------------------------- initialization


class ComposedInitializationConfig(BaseModel):
    model_type: str
    weight_init_type: str
    mean: float = 0.0
    std: float | str = 0.02
    num_layers: Optional[int] = None
    hidden_dim: Optional[int] = None


class GPT2LLMStagesGeneratorConfig(BaseModel):
    """reference GPT2LLMStagesGeneratorConfig (stages_generator_configs.py:10-13).
    `num_model_layers` is optional here (the staged model's n_layer is authoritative;
    when given it is cross-checked), accepting both reference YAMLs and bare nodes."""

    num_model_layers: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    input_layer_equivalence: Annotated[int, Field(strict=True, ge=1)] = 1
    output_layer_equivalence: Annotated[int, Field(strict=True, ge=1)] = 1


class Llama3InitializerConfig(BaseModel):
    """reference Llama3InitializerConfig (llama3_like_initialization.py:15-18)."""

    num_layers: Annotated[int, Field(strict=True, gt=0)]
    n_embd: Annotated[int, Field(strict=True, gt=0)]
    depth_init: bool = True


# ---------------------------------------------------------------------- optimizers


class AdamOptimizerConfig(BaseModel):
    lr: float
    wrapped_model: PydanticModelIFType
    betas: tuple[float, float]
    eps: float
    weight_decay: float
    weight_decay_groups_excluded: list[str]
    foreach: Optional[bool] = None  # torch knob
    fused: Optional[bool] = None  # torch knob


class AdamWOptimizerConfig(AdamOptimizerConfig):
    pass


# ---------------------------------------------------------------------- schedulers


class DummyLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType


class StepLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    step_size: Annotated[int, Field(strict=True, gt=0)]
    gamma: Annotated[float, Field(ge=0.0)]
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


class ConstantLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    factor: Annotated[float, Field(ge=0.0, le=1.0)]
    total_iters: Annotated[int, Field(strict=True, gt=0)]
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


class LinearLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    start_factor: Annotated[float, Field(gt=0.0, le=1.0)]
    end_factor: Annotated[float, Field(ge=0.0, le=1.0)]
    total_iters: Annotated[int, Field(strict=True, gt=0)]
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


class OneCycleLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    max_lr: float | list[float]
    total_steps: Optional[int] = None
    epochs: Optional[int] = None
    steps_per_epoch: Optional[int] = None
    pct_start: Annotated[float, Field(gt=0.0, le=1.0)] = 0.3
    anneal_strategy: str = "cos"
    cycle_momentum: bool = False
    base_momentum: float | list[float] = 0.85
    max_momentum: float | list[float] = 0.95
    div_factor: float = 25.0
    final_div_factor: float = 1e4
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


class CosineAnnealingLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    t_max: Annotated[int, Field(strict=True, gt=0)]
    eta_min: Annotated[float, Field(ge=0.0)]
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


class LinearWarmupCosineAnnealingLRSchedulerConfig(BaseModel):
    optimizer: PydanticOptimizerIFType
    warmup_steps: Annotated[int, Field(strict=True, gt=0)]
    total_steps: Annotated[int, Field(strict=True, gt=0)]
    initial_lr: Annotated[float, Field(ge=0.0)]
    final_lr: Annotated[float, Field(ge=0.0)]
    max_lr: Annotated[float, Field(ge=0.0)]
    last_epoch: Annotated[int, Field(strict=True, ge=-1)] = -1


# -------------------------------------------------------------------------- losses


class CLMCrossEntropyLossConfig(BaseModel):
    target_key: str
    prediction_key: str
    tag: str = "CLMCrossEntropyLoss"
    ignore_index: int = -100


class LoopedExitLossConfig(CLMCrossEntropyLossConfig):
    tag: str = "LoopedExitLoss"


class NCELossConfig(BaseModel):
    prediction_key1: str
    prediction_key2: str
    is_asymmetric: bool = True
    temperature: float = 1.0
    tag: str = "NCELoss"


# ------------------------------------------------------------------------ datasets


class MemMapDatasetConfig(BaseModel):
    raw_data_path: Path
    tokenizer: PydanticTokenizerIFType
    sample_key: str
    index_path: Optional[Path] = None
    jq_pattern: str = ".text"


class PackedMemMapDatasetContinuousConfig(BaseModel):
    raw_data_path: Path
    sequence_length: Annotated[int, Field(strict=True, gt=1)]
    sample_key: str
    reuse_last_target: bool = True


class PackedMemMapDatasetMegatronConfig(BaseModel):
    raw_data_path: Path
    sequence_length: Annotated[int, Field(strict=True, gt=1)]
    sample_key: str


class CombinedDatasetConfig(BaseModel):
    datasets: list[PydanticDatasetIFType]


# ------------------------------------------------------------------------ samplers


class ResumableDistributedSamplerConfig(BaseModel):
    dataset: PydanticDatasetIFType
    rank: Annotated[int, Field(strict=True, ge=0)]
    num_replicas: Annotated[int, Field(strict=True, ge=1)]
    epoch: Annotated[int, Field(strict=True, ge=0)] = 0
    shuffle: Optional[bool] = False
    seed: Optional[int] = 0
    drop_last: Optional[bool] = False
    skip_num_global_samples: Annotated[int, Field(strict=True, ge=0)] = 0


class ResumableDistributedMultiDimSamplerConfig(BaseModel):
    dataset: PydanticDatasetIFType
    device_mesh: PydanticDeviceMeshIFType
    data_parallel_key: str = "dp_shard"
    epoch: Annotated[int, Field(strict=True, ge=0)] = 0
    shuffle: Optional[bool] = False
    seed: Optional[int] = 0
    drop_last: Literal[True] = True
    skip_num_global_samples: Annotated[int, Field(strict=True, ge=0)] = 0


class SequentialSamplerConfig(BaseModel):
    dataset: PydanticDatasetIFType


class RandomSamplerConfig(BaseModel):
    dataset: PydanticDatasetIFType
    seed: int = 0


class BatchSamplerConfig(BaseModel):
    sampler: PydanticSamplerIFType
    batch_size: Annotated[int, Field(strict=True, gt=0)]  # per-dp-rank micro batch size
    drop_last: Literal[True] = True
    device_mesh: Optional[PydanticDeviceMeshIFType] = None  # scales to the process batch


# ----------------------------------------------------------------------- collators


class GPT2LLMCollateFnConfig(BaseModel):
    sample_key: str
    target_key: str


class CoCaCollatorConfig(BaseModel):
    sample_keys: list[str]
    target_keys: list[str]
    text_sample_key: str
    text_target_key: str


class LossMaskingCollateFnWrapperConfig(BaseModel):
    wrapped_collate_fn: PydanticCollateFnIFType
    target_keys_to_mask: list[str]
    loss_ignore_index: int
    mask_tokens: dict
    tokenizer: PydanticTokenizerIFType


# ---------------------------------------------------------------------- dataloader


class LLMDataLoaderConfig(BaseModel):
    dataloader_tag: str
    dataset: PydanticDatasetIFType
    batch_sampler: PydanticBatchSamplerIFType
    collate_fn: Optional[PydanticCollateFnIFType] = None
    num_prefetch_batches: int = 2
    # torch DataLoader knobs accepted + ignored (host prefetch thread instead)
    num_workers: Optional[int] = None
    pin_memory: Optional[bool] = None


class RepeatingDataLoaderConfig(BaseModel):
    dataloader: PydanticLLMDataLoaderIFType
    reshuffle_after_epoch: Optional[bool] = False


class DeviceFeederConfig(BaseModel):
    """Async host→device input pipeline (device_feeder.default).

    prefetch_to_device is the queue depth of device-resident batches staged
    ahead of the step loop; 0 restores the synchronous inline path."""

    prefetch_to_device: Annotated[int, Field(strict=True, ge=0)] = 2


class TelemetryConfig(BaseModel):
    """Telemetry subsystem (telemetry.default): span tracing + goodput ledger +
    hang watchdog + per-rank JSONL sink.

    enabled=False swaps every call for an allocation-free no-op.
    output_folder_path defaults to <experiment folder>/telemetry (set by Main).
    watchdog_deadline_s: no completed step within this budget dumps a crash
    artifact (all-thread stacks, device memory, feeder queue); 0 disables.
    watchdog_first_step_factor stretches the first deadline (trace + compile).
    """

    enabled: bool = True
    output_folder_path: Optional[Path] = None
    watchdog_deadline_s: Annotated[float, Field(ge=0)] = 1800.0
    watchdog_first_step_factor: Annotated[float, Field(ge=1)] = 4.0
    use_jax_annotations: bool = True
    # step-time / goodput-bucket anomaly detection (PR 13): robust z-score
    # threshold over a rolling window of per-step wall times; an anomalous step
    # bumps training_step_time_anomaly_total and emits an anomaly/step_time event
    anomaly_zscore: Annotated[float, Field(gt=0)] = 6.0
    anomaly_window: Annotated[int, Field(ge=2)] = 64
    # declarative SLOs (PR 15, telemetry/slo.py): {"objectives": [{"name", "expr",
    # + burn-rate overrides}]} judged at each interval publish; a breaching
    # goodput/MFU-floor objective counts against the anomaly skip budget.
    # None (default) is a no-op fast path: no slo_* series, no extra work.
    slo: Optional[dict] = None


class ResilienceConfig(BaseModel):
    """Resilience subsystem (resilience.default): anomaly policy, preemption-aware
    shutdown, and supervisor knobs (see modalities_tpu/resilience/).

    anomaly_policy: "raise" (default, bit-identical to the raise-only guard),
    "skip_step" (jnp.where no-ops anomalous optimizer updates, bounded by
    skip_budget per trailing anomaly_window_steps), or "rollback" (budget
    exhaustion exits resumable for a supervisor warmstart from the newest
    verified checkpoint).
    loss_spike_zscore: arm the running z-score loss-spike detector (None: off);
    spikes feed the same policy/budget.
    install_signal_handlers: SIGTERM/SIGINT -> graceful out-of-schedule
    checkpoint + resumable exit.
    max_restarts/backoff_base_s: crash-loop cap and backoff for `run --resilient`.

    Cluster coordination (multi-host; all "auto" modes resolve to no-ops in a
    single process so the default single-host program is unchanged):
    stop_consensus: "auto" folds local stop/rollback votes into the jitted step
    as ONE replicated scalar all-reduce when process_count > 1, so every host
    exits at the same step boundary; "on"/"off" force it.
    heartbeat: out-of-band peer-health transport — "auto" (KV store when
    jax.distributed is up, else UDP when MODALITIES_TPU_HB_PORT is set, else
    off), "kv", "udp", or "off".
    heartbeat_interval_s / peer_deadline_s: beat cadence and how long a peer may
    stay silent before this process exits resumable with a peer-failure dump.
    rendezvous_deadline_s: bound on cross-host rendezvous (checkpoint
    save/drain/restore) before declaring a wedged peer; 0 disables.
    resume_quorum / resume_vote_deadline_s: multi-host supervisor resume
    agreement — how many hosts must vote (default: all) and how long to wait.
    min_hosts: elastic degraded-quorum floor — when the vote deadline expires
    with fewer voters than the quorum but at least min_hosts, the supervisor
    recomputes a feasible mesh for the surviving host set, rewrites the
    warmstart config, and resumes on the reduced topology instead of failing
    (None: disabled — quorum timeout fails fast as before).
    """

    anomaly_policy: Literal["raise", "skip_step", "rollback"] = "raise"
    skip_budget: Annotated[int, Field(strict=True, ge=0)] = 2
    anomaly_window_steps: Annotated[int, Field(strict=True, gt=0)] = 100
    loss_spike_zscore: Optional[Annotated[float, Field(gt=0)]] = None
    loss_spike_min_history: Annotated[int, Field(strict=True, gt=0)] = 8
    install_signal_handlers: bool = True
    max_restarts: Annotated[int, Field(strict=True, ge=0)] = 3
    backoff_base_s: Annotated[float, Field(ge=0)] = 1.0
    stop_consensus: Literal["auto", "on", "off"] = "auto"
    heartbeat: Literal["auto", "kv", "udp", "off"] = "auto"
    heartbeat_interval_s: Annotated[float, Field(gt=0)] = 5.0
    peer_deadline_s: Annotated[float, Field(gt=0)] = 30.0
    rendezvous_deadline_s: Annotated[float, Field(ge=0)] = 300.0
    resume_quorum: Optional[Annotated[int, Field(strict=True, gt=0)]] = None
    resume_vote_deadline_s: Annotated[float, Field(gt=0)] = 120.0
    min_hosts: Optional[Annotated[int, Field(strict=True, gt=0)]] = None


class XlaFlagsConfig(BaseModel):
    """XLA performance-flag component (performance.xla_flags): assembles the
    latency-hiding-scheduler / async-collective / collective-combining settings
    into LIBTPU_INIT_ARGS (+ optional XLA_FLAGS extras) BEFORE backend init —
    see running_env/xla_flags.py. All TPU-runtime flags ride LIBTPU_INIT_ARGS
    because this jaxlib's XLA_FLAGS parser hard-aborts on flags the current
    backend does not know (CPU runs must stay untouched).

    latency_hiding_scheduler: enable XLA's LHS so the reduce-scatter/all-gather
    pairs the ZeRO update inserts overlap with compute.
    async_collectives: async all-gather/reduce-scatter + collective fusion.
    *_combine_threshold_bytes: gate below which small collectives are combined
    into one (None: leave the compiler default).
    extra_libtpu_args / extra_xla_flags: escape hatches appended verbatim.

    extra="forbid": a typo'd knob must fail the run, not silently leave the
    scheduler at its default while the operator believes it is tuned.
    """

    model_config = {"extra": "forbid"}

    latency_hiding_scheduler: bool = True
    async_collectives: bool = True
    # multi-slice: async fusion + scheduling for the cross-slice (DCN) grad
    # all-reduce the hierarchical reduction emits once per step — off by default
    # (single-slice runs have no DCN collective to overlap)
    dcn_collective_overlap: bool = False
    all_gather_combine_threshold_bytes: Optional[Annotated[int, Field(strict=True, ge=0)]] = None
    reduce_scatter_combine_threshold_bytes: Optional[Annotated[int, Field(strict=True, ge=0)]] = None
    all_reduce_combine_threshold_bytes: Optional[Annotated[int, Field(strict=True, ge=0)]] = None
    extra_libtpu_args: list[str] = []
    extra_xla_flags: list[str] = []


# ---------------------------------------------------------------------- tokenizers


class PreTrainedHFTokenizerConfig(BaseModel):
    pretrained_model_name_or_path: str
    truncation: Optional[bool] = False
    padding: Optional[bool | str] = False
    max_length: Optional[int] = None
    # reference config.py:397: values may be a single token or a list/tuple
    # (additional_special_tokens)
    special_tokens: Optional[dict[str, str | list[str] | tuple[str, ...]]] = None


class PreTrainedSPTokenizerConfig(BaseModel):
    tokenizer_model_file: str


# ------------------------------------------------------------------- checkpointing


class SaveEveryKStepsCheckpointingStrategyConfig(BaseModel):
    k: Annotated[int, Field(strict=True, gt=0)]


class SaveKMostRecentCheckpointsStrategyConfig(BaseModel):
    k: Annotated[int, Field(strict=True, ge=-1)]


class OrbaxCheckpointSavingConfig(BaseModel):
    checkpoint_path: Path
    experiment_id: str
    global_rank: Annotated[int, Field(strict=True, ge=0)] = 0
    use_async: bool = False


class CheckpointSavingConfig(BaseModel):
    checkpoint_saving_strategy: PydanticCheckpointSavingStrategyIFType
    checkpoint_saving_execution: PydanticCheckpointSavingExecutionIFType


class OrbaxCheckpointLoadingConfig(BaseModel):
    """elastic (default on): compare the checkpoint's sealed topology.json
    against the current mesh at restore; on mismatch reshard onto the current
    mesh's NamedShardings and emit an `elastic/reshard` telemetry event instead
    of failing. Off: the topology record is never read — the same-topology
    restore path is byte-identical to the pre-elastic loader."""

    global_rank: Annotated[int, Field(strict=True, ge=0)] = 0
    elastic: bool = True


class FSDP1CheckpointedGuardConfig(BaseModel):
    """Accepts the union of the reference's FSDP1CheckpointedModelConfig /
    FSDP1CheckpointedOptimizerConfig fields so the build reaches the
    fsdp1_checkpointed guard, which raises the actionable no-SPMD-analogue
    ConfigError instead of a generic invalid-keys failure."""

    model: Optional[Any] = None
    optimizer: Optional[Any] = None
    wrapped_model: Optional[Any] = None
    checkpoint_loading: Optional[Any] = None
    checkpoint_path: Optional[Path] = None


class FSDP1AliasCheckpointLoadingConfig(OrbaxCheckpointLoadingConfig):
    """Config for the `checkpoint_loading.fsdp1` alias (reference
    FSDP1CheckpointLoadingConfig: global_rank, block_names, mixed_precision_settings,
    sharding_strategy). The torch-era knobs describe how to REBUILD the FSDP1 wrapper
    at load time; Orbax restores into the existing sharded state, so they are
    accepted for YAML compatibility and unused."""

    block_names: Optional[list[str]] = None
    mixed_precision_settings: Optional[str] = None
    sharding_strategy: Optional[str] = None


class TorchAliasCheckpointLoadingConfig(OrbaxCheckpointLoadingConfig):
    """Config for the `checkpoint_loading.torch` alias (reference
    TorchCheckpointLoadingConfig, config.py:95-101). The checkpoint format in this
    framework is Orbax regardless of the alias name, so the reference's torch-only
    knobs (`device`, `precision`) are accepted for YAML compatibility but have no
    effect — sharding/placement comes from the mesh, dtypes from the model's mixed-
    precision spec. A torch `.bin` checkpoint cannot be restored through this alias;
    the warning makes that surface at config time instead of as an Orbax error."""

    device: Optional[Any] = None
    precision: Optional[Any] = None

    @model_validator(mode="after")
    def _warn_ignored_torch_fields(self) -> "TorchAliasCheckpointLoadingConfig":
        ignored = [name for name in ("device", "precision") if getattr(self, name) is not None]
        if ignored:
            warnings.warn(
                f"checkpoint_loading.torch: field(s) {ignored} are torch-specific and "
                "ignored — checkpoints are Orbax-format (device placement comes from "
                "the mesh, dtype from the mixed-precision spec). A torch .bin "
                "checkpoint cannot be restored through this alias.",
                stacklevel=2,
            )
        return self


class RawAppStateConfig(BaseModel):
    model: PydanticModelIFType
    optimizer: PydanticOptimizerIFType
    lr_scheduler: Optional[Any] = None


class DCPAppStateConfig(BaseModel):
    raw_app_state: PydanticAppStateType
    checkpoint_dir_path: Path
    checkpoint_loading: Optional[PydanticCheckpointLoadingIFType] = None


# ----------------------------------------------------------------- grad clipping


class GradientClipperConfig(BaseModel):
    """Covers the reference's FSDP1 and FSDP2 clipper schemas
    (fsdp_gradient_clipper_config.py): `wrapped_model`/`device_mesh` are torch
    handles for its per-shard norm walk + PP-mesh all-reduce; the jitted global
    norm here spans all mesh axes by construction, so both are accepted and unused."""

    max_norm: float
    norm_type: str = "p2_norm"
    error_if_nonfinite: bool = False
    wrapped_model: Optional[PydanticModelIFType] = None
    device_mesh: Optional[PydanticDeviceMeshIFType] = None


class LoggingOnlyGradientClipperConfig(BaseModel):
    """reference FSDP1DummyGradientClipperConfig (fsdp_gradient_clipper_config.py:61):
    carries the wrapped model for torch's per-shard norm walk; the jit global-norm
    computation here needs no model handle, so the field is accepted and unused."""

    wrapped_model: Optional[PydanticModelIFType] = None
    norm_type: str = "p2_norm"


# ------------------------------------------------------------------- subscribers


class RichProgressSubscriberConfig(BaseModel):
    """reference RichProgressSubscriberConfig (config.py:477-482): dataloader-level
    fields the factory converts into per-tag progress-bar specs."""

    eval_dataloaders: Optional[list[PydanticLLMDataLoaderIFType]] = Field(default_factory=list)
    train_dataloader_tag: str
    num_seen_steps: Annotated[int, Field(strict=True, ge=0)]
    num_target_steps: Annotated[int, Field(strict=True, gt=0)]
    global_rank: Annotated[int, Field(strict=True, ge=0)]


class RichResultSubscriberConfig(BaseModel):
    num_ranks: int = 1
    global_rank: int = 0


class EvaluationResultToDiscSubscriberConfig(BaseModel):
    """Either this repo's output_folder_path (results land in
    <folder>/evaluation_results.jsonl) or the reference's output_file_path
    (subscriber_factory.py:60 — an explicit jsonl file)."""

    output_folder_path: Optional[Path] = None
    output_file_path: Optional[Path] = None

    @model_validator(mode="after")
    def _exactly_one(self) -> "EvaluationResultToDiscSubscriberConfig":
        if (self.output_folder_path is None) == (self.output_file_path is None):
            raise ValueError(
                "results_subscriber to_disc/save_to_disc needs exactly one of "
                "output_folder_path (repo form) or output_file_path (reference form)"
            )
        return self


class WandBEvaluationResultSubscriberConfig(BaseModel):
    """reference WandBEvaluationResultSubscriberConfig (config.py:493-500), plus the
    legacy `experiment_path` alias for `directory` kept for earlier TPU configs."""

    global_rank: Annotated[int, Field(strict=True, ge=0)] = 0
    entity: Optional[str] = None
    project: str
    experiment_id: str
    mode: str = "OFFLINE"
    directory: Optional[Path] = None
    experiment_path: Optional[Path] = None
    config_file_path: Optional[Path] = None

    @model_validator(mode="after")
    def _validate_mode(self) -> "WandBEvaluationResultSubscriberConfig":
        if self.mode.upper() not in ("ONLINE", "OFFLINE", "DISABLED"):
            raise ValueError(f"unknown wandb mode {self.mode!r} (ONLINE | OFFLINE | DISABLED)")
        return self


# -------------------------------------------------------------------------- MFU


class GPT2MFUCalculatorConfig(BaseModel):
    n_layer: Annotated[int, Field(strict=True, gt=0)]
    sequence_length: Annotated[int, Field(strict=True, gt=0)]
    n_embd: Annotated[int, Field(strict=True, gt=0)]
    world_size: Annotated[int, Field(strict=True, gt=0)]
    num_parameters: Optional[int] = None
    model_parts: Optional[Any] = Field(default=None, validation_alias="wrapped_model")
    device_mesh: Optional[PydanticDeviceMeshIFType] = None

    model_config = {"populate_by_name": True, "protected_namespaces": ()}


# ---------------------------------------------------------------------- profilers


class SteppableKernelProfilerConfig(BaseModel):
    """Accepts both this repo's field names and the reference's
    (profiler_configs.py:14-27: num_wait_steps/num_warmup_steps/num_active_steps +
    torch.profiler knobs). Torch-only knobs are accepted and ignored with a warning
    — the kernel trace here is a jax.profiler trace, which always records device
    kernels, shapes, and flops."""

    model_config = {"populate_by_name": True}

    output_folder_path: Path
    wait_steps: int = Field(1, validation_alias="num_wait_steps")
    warmup_steps: int = Field(1, validation_alias="num_warmup_steps")
    active_steps: int = Field(3, validation_alias="num_active_steps")
    repeat: int = 1
    with_python_stack: bool = Field(False, validation_alias="with_stack")
    # torch-only (reference) knobs — validated, then ignored
    profiler_activities: Optional[list[str]] = None
    profile_memory: Optional[bool] = None
    record_shapes: Optional[bool] = None
    with_flops: Optional[bool] = None
    with_modules: Optional[bool] = None
    tracked_ranks: Optional[list[int]] = None

    @model_validator(mode="after")
    def _warn_torch_only(self) -> "SteppableKernelProfilerConfig":
        ignored = [
            n
            for n in (
                "profiler_activities",
                "profile_memory",
                "record_shapes",
                "with_flops",
                "with_modules",
                "tracked_ranks",
            )
            if getattr(self, n) is not None
        ]
        if ignored:
            warnings.warn(
                f"steppable_profiler.kernel_tracing: field(s) {ignored} are torch.profiler-"
                "specific and ignored — the jax.profiler trace always includes device "
                "kernels, shapes and flops."
            )
        return self


class SteppableMemoryProfilerConfig(BaseModel):
    output_folder_path: Path
    max_steps: int = 0


class SteppableCombinedProfilerConfig(BaseModel):
    profilers: list[Any]


# ---------------------------------------------------------------- profiler harness


class RandomDatasetBatchGeneratorConfig(BaseModel):
    """Two accepted shapes: the repo's named-field token-batch schema, or the
    reference's dims-style schema (batch_generator.py:21-25 — dims/data_type/
    min_val/max_val) used by the profiling tutorial configs."""

    # named-field schema
    sample_key: str = "input_ids"
    target_key: str = "target_ids"
    micro_batch_size: Annotated[int, Field(strict=True, gt=0)] = 1
    sequence_length: Annotated[int, Field(strict=True, gt=0)] = 128
    vocab_size: Annotated[int, Field(strict=True, gt=0)] = 256
    seed: int = 0
    # reference dims-style schema
    dims: Optional[dict[str, int]] = None
    data_type: Optional[str] = None
    min_val: int = 0
    max_val: int = 256

    @model_validator(mode="after")
    def _one_schema_explicit(self) -> "RandomDatasetBatchGeneratorConfig":
        named = {"micro_batch_size", "sequence_length", "vocab_size"}
        if self.dims is None and not named <= self.model_fields_set:
            raise ValueError(
                "dataset_batch_generator.random needs either the reference dims-style "
                "schema (dims/data_type/min_val/max_val) or ALL of the named fields "
                f"{sorted(named)} — got only {sorted(self.model_fields_set & named)}; "
                "a typo'd field name would otherwise silently profile a default-shaped batch"
            )
        return self


class SteppableForwardPassConfig(BaseModel):
    """Builds a jitted train/eval step over random batches for the profiler harness
    (reference steppable_components.py:12; its schema steppable_component_configs.py:11-15
    names the generator `dataset_batch_generator` and makes loss_fn/optimizer
    optional — forward-only profiling when no optimizer is given)."""

    model_config = {"populate_by_name": True}

    model: PydanticModelIFType
    batch_generator: Any = Field(validation_alias="dataset_batch_generator")
    loss_fn: Optional[PydanticLossIFType] = None
    optimizer: Optional[PydanticOptimizerIFType] = None
    device_mesh: Optional[PydanticDeviceMeshIFType] = None
    include_backward: Optional[bool] = None
    gradient_accumulation_steps: Annotated[int, Field(strict=True, ge=1)] = 1


# ------------------------------------------------------- reference pipeline surface
# (reference: pipeline_parallelism_configs.py — the pipeline.{staged, scheduled,
# selector, builder} registry nodes; see parallel/pipeline_components.py for the
# SPMD re-expression)

class StagedPipelineConfig(BaseModel):
    whole_model: PydanticModelIFType
    stages_generator: PydanticStagesGeneratorIFType
    device_mesh: PydanticDeviceMeshIFType
    pp_schedule_name: str
    num_layers_per_stage: Annotated[int, Field(strict=True, ge=1)]
    local_rank: Annotated[int, Field(strict=True, ge=0)] = 0


class ScheduledPipelineConfig(BaseModel):
    loss_fn: PydanticLossIFType
    pp_schedule_name: str
    batch_size: Annotated[int, Field(strict=True, ge=1)]
    microbatch_size: Annotated[int, Field(strict=True, ge=1)]
    pp_degree: Annotated[int, Field(strict=True, ge=1)]
    pipeline: PydanticPipelineIFType


class ComponentSelectorFromPipelineConfig(BaseModel):
    pipeline: PydanticPipelineIFType
    selection_type: str  # PP_STAGE | MODEL_PART | PP_SCHEDULE


class PipelineBuilderConfig(BaseModel):
    """reference PipelineConfig (pipeline_parallelism_configs.py:44-49): the
    deprecated singular aliases (`pp_stage`, `model_part`) accept a single item and
    lift it to a list — the reference's add_deprecated_alias + maybe_list pattern,
    which its own pp_tp YAML uses."""

    pp_stages: list[Any] = Field(validation_alias=AliasChoices("pp_stages", "pp_stage"))
    model_parts: list[Any] = Field(validation_alias=AliasChoices("model_parts", "model_part"))
    pp_schedule: Optional[Any] = None

    @field_validator("pp_stages", "model_parts", mode="before")
    @classmethod
    def _lift_single_to_list(cls, value: Any) -> Any:
        return value if isinstance(value, list) else [value]


# ------------------------------------------------------------- debugging components
# (reference: utils/debugging_configs.py)


class NaNHookConfig(BaseModel):
    model: Optional[PydanticModelIFType] = None  # check is process-wide under jit
    raise_exception: bool = True


class PrintForwardHookConfig(BaseModel):
    model: PydanticModelIFType
    print_shape_only: bool = False


class DebuggingConfig(BaseModel):
    forward_hooks: list[Any] = []
    enable_determinism: bool = False


class ParallelDegreeConfig(BaseModel):
    device_mesh: PydanticDeviceMeshIFType
    parallelism_methods: list[str]

"""The jitted train/eval step builder — the execution core of the framework.

This replaces the reference's eager micro-batch loop internals (trainer.py:129-189):
forward, backward, grad clip, optimizer and schedule all fuse into ONE donated
``jax.jit`` program. Gradient accumulation runs as a ``lax.scan`` over microbatches
*inside* the step (one dispatch per optimizer step instead of one per microbatch).
GSPMD lowers the logical-axis shardings (parallel/sharding.py) into FSDP-style
all-gather/reduce-scatter and TP all-reduces; the loss all-reduce that the reference
does explicitly via `Reducer` (running_env/fsdp/reducer.py:7) is just the mean here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.core import meta as nn_meta

from modalities_tpu.checkpointing.stateful.app_state import AppState, AppStateHandle
from modalities_tpu.loss_functions import Loss
from modalities_tpu.models.model import NNModel
from modalities_tpu.parallel.sharding import (
    batch_sharding,
    default_logical_axis_rules,
    fit_spec_to_shape,
    logical_to_mesh_spec,
    replicated,
    zero_params_shardings,
)
from modalities_tpu.running_env.device_mesh import DeviceMeshHandle
from modalities_tpu.telemetry import scopes, span
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# a step metric under this prefix is a scalar the model counted (`NNModel.counted`), not a loss:
# the trainer publishes each under its name, the mean of an interval (the largest where the name ends in `_max`)
COUNTER_PREFIX = "counter/"


def _unbox(tree):
    return nn_meta.unbox(tree)


def _substitute_param_subtrees(node, param_treedef, param_shardings, replicated_sharding):
    """Map an abstract optax state to shardings: any subtree structurally equal to the
    param tree (mu/nu) gets the param shardings; everything else is replicated."""
    try:
        if jax.tree.structure(node) == param_treedef:
            return param_shardings
    except Exception:
        pass
    if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple state
        return type(node)(*[
            _substitute_param_subtrees(c, param_treedef, param_shardings, replicated_sharding) for c in node
        ])
    if isinstance(node, (list, tuple)):
        return type(node)(
            _substitute_param_subtrees(c, param_treedef, param_shardings, replicated_sharding) for c in node
        )
    if isinstance(node, dict):
        return {
            k: _substitute_param_subtrees(v, param_treedef, param_shardings, replicated_sharding)
            for k, v in node.items()
        }
    return replicated_sharding


def _under_scope(tx: optax.GradientTransformation, name: str) -> optax.GradientTransformation:
    """`tx` with its update traced under the named scope `name`: metadata on the
    operations it emits, the same program (telemetry/scopes.py)."""
    return optax.GradientTransformation(tx.init, jax.named_scope(name)(tx.update))


class KeptAttention:
    """What a rematerialized block keeps beside its input in this build's step (the flash kernel's o and lse, the gated delta
    rule's o and group states): the plan as the last trace of the step made it
    (`training/activation_checkpointing.attention_keep_plan`; None before the first trace), and the way down for a preflight
    that finds the keeping step over budget: `drop()` has the next trace plan from the rung below the one it took and forgets
    the step's traces, so that the next lowering or dispatch builds the step that keeps less."""

    def __init__(self):
        self.plan: Optional[dict] = None
        self.first_rung = 0  # of the plan's ladder: the rungs above it the preflight has found over the device's limit
        self.jitted: list = []  # the build's jitted steps: their caches hold the spec the model had when they were traced

    def drop(self) -> None:
        self.first_rung = self.plan["rung"] + 1 if self.plan else 1
        for step in self.jitted:
            step.clear_cache()


@dataclass
class StepFunctions:
    """The compiled training surface handed to Trainer/Evaluator."""

    train_step: Callable[[AppState, Any], tuple[AppState, dict]]
    eval_step: Callable[[AppState, Any], dict]
    # put_batch(batch_dict, has_acc_dim=True): pass has_acc_dim=False for flat
    # (batch, ...) eval batches without the leading gradient-accumulation dim
    put_batch: Callable[..., dict]
    app_state_handle: AppStateHandle
    mesh_handle: DeviceMeshHandle
    # debugging_enriched: same step but with grads in metrics — used by the Trainer
    # ONLY on logging ticks so the grad tree isn't materialized on every step
    train_step_debug: Optional[Callable[[AppState, Any], tuple[AppState, dict]]] = None
    # lower_train_step(batch_abstract) -> jax.stages.Lowered for the full sharded
    # step program (AOT partitioning check without executing); present whenever a
    # mesh is attached, and the only executable surface in materialize=False mode
    lower_train_step: Optional[Callable[[Any], Any]] = None
    # build-time config memscope needs to rank memory levers (zero_stage=1 sheds
    # nothing if already on; accumulation halves the live microbatch only if raisable)
    zero_stage: int = 0
    gradient_acc_steps: int = 1
    # what the step's rematerialized blocks keep of their attention, and the preflight's fall-back (trainer.py)
    kept_attention: Optional[KeptAttention] = None

    def perfscope_report(self, batch_abstract, hw=None) -> dict:
        """Lower + compile the sharded step and bucket its optimized-HLO cost by
        op class (telemetry/perfscope.py) — the static half of performance
        attribution: where the step's FLOPs/bytes go before a profiler ever runs."""
        if self.lower_train_step is None:
            raise ValueError(
                "perfscope_report needs the AOT lowering surface; this StepFunctions "
                "was built without lower_train_step"
            )
        from modalities_tpu.telemetry.perfscope import perfscope_from_compiled

        mesh_axis_sizes = (
            {k: int(v) for k, v in self.mesh_handle.mesh.shape.items()}
            if self.mesh_handle is not None
            else None
        )
        return perfscope_from_compiled(
            self.lower_train_step(batch_abstract).compile(), mesh_axis_sizes, hw
        )

    def scope_table(self, batch_abstract) -> dict[str, str]:
        """{instruction name: op_name} of the compiled step (telemetry/perfscope.py,
        the vocabulary in telemetry/scopes.py): what a trace reader joins a device
        event's instruction to, to read its pass and component. The compile is a
        cache hit in a process that has run the step."""
        if self.lower_train_step is None:
            raise ValueError(
                "scope_table needs the AOT lowering surface; this StepFunctions "
                "was built without lower_train_step"
            )
        from modalities_tpu.telemetry.perfscope import scope_table

        return scope_table(self.lower_train_step(batch_abstract).compile().as_text())

    def memscope_report(self, batch_abstract) -> dict:
        """Lower + compile the sharded step and carve its memory_analysis() bytes
        into semantic buckets (telemetry/memscope.py) — the static half of memory
        attribution, the bytes-sibling of perfscope_report."""
        if self.lower_train_step is None:
            raise ValueError(
                "memscope_report needs the AOT lowering surface; this StepFunctions "
                "was built without lower_train_step"
            )
        from modalities_tpu.telemetry.memscope import memscope_from_compiled, train_step_known_bytes

        known = train_step_known_bytes(self.app_state_handle, self.mesh_handle)
        degrees = getattr(self.mesh_handle, "degrees", None) or {}
        context = {
            "kind": "train",
            "zero_stage": self.zero_stage,
            "gradient_accumulation_steps": self.gradient_acc_steps,
            "dp_replicate": int(degrees.get("dp_replicate", 1) or 1),
            "remat_variant": getattr(
                getattr(self.app_state_handle.model, "config_spec", None),
                "remat_variant", None,
            ),
        }
        # The lowering below stays on the line it had (184): a traced frame's line is part of the compile cache's key.
        # On a mesh of several devices the compiled step is handed on, for its collectives (`Trainer._preflight_memscope`
        # reads them off it and drops it: `telemetry/collective_plan.py`); a program on one device holds none.
        compiled = (
            self.lower_train_step(batch_abstract).compile()
        )
        self.preflight_compiled = compiled if self.mesh_handle is not None and self.mesh_handle.mesh.size > 1 else None
        return memscope_from_compiled(compiled, known, context)


class TrainStepBuilder:
    """Assembles model + loss + optimizer + schedule + mesh into jitted step functions.

    This is where the registry's model-transform descriptors (sharding, remat, mixed precision) are applied — the JAX
    counterpart of the reference's in-place wrapper chain fsdp2_wrapped -> activation_checkpointed -> compiled (model_factory.py)."""

    def __init__(
        self,
        model: NNModel,
        loss_fn: Loss,
        optimizer_spec,
        scheduler_spec=None,
        mesh_handle: Optional[DeviceMeshHandle] = None,
        gradient_acc_steps: int = 1,
        grad_clip_norm: Optional[float] = None,
        grad_clipper=None,
        expose_grads: bool = False,
        anomaly_policy: Optional[str] = None,
        stop_consensus: bool = False,
        zero_stage: Optional[int] = None,
    ):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer_spec = optimizer_spec
        self.scheduler_spec = scheduler_spec
        self.mesh_handle = mesh_handle
        self.gradient_acc_steps = gradient_acc_steps
        self.grad_clip_norm = grad_clip_norm
        self.grad_clipper = grad_clipper  # full descriptor (norm_type, error_if_nonfinite)
        self.expose_grads = expose_grads  # debugging_enriched: return grads in metrics
        # "skip_step"/"rollback" compile the branch-free optimizer-update skip into
        # the step; None/"raise" leaves the program bit-identical to before
        self.anomaly_policy = anomaly_policy
        # stop-flag consensus: the step reduces a per-device "stop ballot" riding
        # the batch dict into one replicated scalar metric (resilience/
        # coordination.py). False leaves the batch structure AND the compiled
        # program byte-identical to a build without the feature.
        self.stop_consensus = stop_consensus
        # ZeRO-1 optimizer-state sharding over dp_replicate: None inherits the mesh
        # handle's configured stage; 0 keeps the program byte-identical to a build
        # without the feature (the knob compiles to nothing, like stop_consensus)
        resolved_zero = (
            zero_stage
            if zero_stage is not None
            else (getattr(mesh_handle, "zero_stage", 0) if mesh_handle is not None else 0)
        )
        if resolved_zero not in (0, 1):
            raise ValueError(f"zero_stage must be 0 or 1, got {resolved_zero}")
        self.zero_stage = resolved_zero
        self.rules = (
            default_logical_axis_rules(mesh_handle) if mesh_handle is not None else ()
        )

    # ------------------------------------------------------------------ build
    def build(self, seed: Optional[int] = None, materialize: bool = True) -> StepFunctions:
        """`materialize=False`: compile-only mode — the AppState stays an abstract
        ShapeDtypeStruct tree (no parameter buffers allocated) and the returned
        StepFunctions carries `lower_train_step(batch_abstract)` for AOT
        lowering/compilation. Validates that XLA can partition and compile the
        full-size step program (v5p readiness checks for configs too large to
        materialize on the host)."""
        model = self.model
        mesh_handle = self.mesh_handle
        seed = seed if seed is not None else model.seed
        rng = jax.random.PRNGKey(seed)

        # enable ring-attention CP / GPipe PP when the mesh has those axes
        if mesh_handle is not None and hasattr(model, "with_spec_updates"):
            if mesh_handle.degrees.get("cp", 1) > 1:
                model.with_spec_updates(context_parallel_axis="cp")
            if mesh_handle.degrees.get("pp", 1) > 1:
                model.with_spec_updates(pipeline_axis="pp")

        # honor the mixed-precision policy (reference model_factory.py:201): the
        # param/compute dtypes recorded by the fsdp2_wrapped variant flow into the
        # module's static spec, reduce_dtype governs grad accumulation below
        mixed_precision = getattr(model.train_spec, "mixed_precision", None)
        if (
            mixed_precision is not None
            and hasattr(model, "with_spec_updates")
            and hasattr(getattr(model, "config_spec", None), "param_dtype")
        ):
            model.with_spec_updates(
                param_dtype=mixed_precision.param_dtype,
                compute_dtype=mixed_precision.compute_dtype,
            )
        reduce_dtype = (
            jnp.dtype(mixed_precision.reduce_dtype) if mixed_precision is not None else jnp.float32
        )

        init_fn = lambda r: model.init_params(r)  # noqa: E731

        # --- shardings from flax logical-axis metadata
        boxed_abstract = jax.eval_shape(init_fn, rng)
        logical_specs = nn.get_partition_spec(boxed_abstract)

        if mesh_handle is not None:
            mesh = mesh_handle.mesh
            from jax.sharding import NamedSharding, PartitionSpec as P

            def to_sharding(spec, leaf):
                return NamedSharding(mesh, fit_spec_to_shape(logical_to_mesh_spec(tuple(spec), self.rules), leaf.shape, mesh))

            param_shardings = jax.tree.map(
                to_sharding, logical_specs, _unbox(boxed_abstract), is_leaf=lambda x: isinstance(x, P)
            )
            replicated_sharding = replicated(mesh_handle)
            data_sharding = batch_sharding(mesh_handle)
        else:
            param_shardings = None
            replicated_sharding = None
            data_sharding = None

        # --- optimizer over unboxed abstract params
        abstract_params = _unbox(boxed_abstract)

        # ZeRO-1 (arXiv 2004.13336): grads and Adam moments carry the dp_replicate
        # axis on their largest divisible dim, so the grad reduction lowers to a
        # reduce-scatter and tx.update runs on 1/dp_replicate-sized slices; the
        # updated params re-materialize with one all-gather below. Inactive (None)
        # means zero new ops — the program stays byte-identical to stage 0.
        zero_active = (
            self.zero_stage >= 1
            and mesh_handle is not None
            and mesh_handle.degrees.get("dp_replicate", 1) > 1
        )
        zero_grad_shardings = (
            zero_params_shardings(abstract_params, param_shardings, mesh_handle)
            if zero_active
            else None
        )

        # --- multi-slice hierarchical gradient reduction (dcn axis present).
        # Each microbatch is reshaped into [dcn, mb/dcn, ...] per-slice groups and
        # the loss/grad computation runs under jax.vmap(spmd_axis_name="dcn"), so
        # every in-model collective stays within a slice on ICI (the per-microbatch
        # grad reduction — the ZeRO reduce-scatter included — has within-slice
        # replica groups). The gradient accumulator carries a leading dcn dim
        # constrained P("dcn", ...) through the scan; the mean over that dim AFTER
        # the scan is the ONE point where accumulated grads cross DCN per optimizer
        # step — GSPMD lowers it to cross-slice all-reduces outside the microbatch
        # loop (pinned by tests/training/test_dcn_hierarchical.py). The loss rides
        # the carry as a per-group [dcn] vector for the same reason: a scalar mean
        # inside the loop body would emit a per-microbatch DCN collective.
        dcn_degree = mesh_handle.dcn_degree if mesh_handle is not None else 1
        hierarchical_dcn = dcn_degree > 1
        dcn_grad_shardings = dcn_loss_sharding = to_dcn_groups = None
        if hierarchical_dcn:
            from jax.sharding import NamedSharding, PartitionSpec as P

            dcn_mesh = mesh_handle.mesh
            acc_base = zero_grad_shardings if zero_active else param_shardings
            dcn_grad_shardings = jax.tree.map(
                lambda s: NamedSharding(dcn_mesh, P("dcn", *tuple(s.spec))), acc_base
            )
            dcn_loss_sharding = NamedSharding(dcn_mesh, P("dcn"))
            data_spec = tuple(data_sharding.spec)
            inner_batch_axes = tuple(a for a in (data_spec[0] or ()) if a != "dcn")
            dcn_seq_axis = data_spec[1] if len(data_spec) > 1 else None
            dcn_seq_keys = {
                k
                for k in (
                    getattr(self.model, "sample_key", None),
                    getattr(self.loss_fn, "target_key", None),
                )
                if k is not None
            }

            def to_dcn_groups(batch_tree):
                """[mb, ...] leaves -> [dcn, mb/dcn, ...] per-slice groups, with the
                same per-leaf layout put_batch established (token leaves keep cp on
                the seq dim) so the constraint is a relabel, not a reshard."""

                def one(path, x):
                    if x.shape[0] % dcn_degree:
                        raise ValueError(
                            f"batch dim {x.shape[0]} of leaf "
                            f"{jax.tree_util.keystr(path)} is not divisible by "
                            f"dcn_parallel_degree {dcn_degree}: every slice must own "
                            "an equal share of each microbatch"
                        )
                    g = x.reshape(dcn_degree, x.shape[0] // dcn_degree, *x.shape[1:])
                    leaf_key = getattr(path[-1], "key", None) if path else None
                    tail = [None] * (g.ndim - 2)
                    if g.ndim == 3 and leaf_key in dcn_seq_keys:
                        tail[0] = dcn_seq_axis
                    return jax.lax.with_sharding_constraint(
                        g, NamedSharding(dcn_mesh, P("dcn", inner_batch_axes, *tail))
                    )

                return jax.tree_util.tree_map_with_path(one, batch_tree)

        schedule = self.scheduler_spec.absolute_lr_schedule() if self.scheduler_spec is not None else None
        tx = self.optimizer_spec.build(abstract_params, schedule)
        from modalities_tpu.training.gradient_clipping import (
            GradientClippingMode,
            global_norm_by_mode,
        )

        norm_mode = GradientClippingMode.P2_NORM
        error_if_nonfinite = False
        if self.grad_clipper is not None:
            norm_mode = self.grad_clipper.norm_type
            error_if_nonfinite = bool(getattr(self.grad_clipper, "error_if_nonfinite", False))
            clip_tx = self.grad_clipper.build_transform()
            if clip_tx is not None:
                tx = optax.chain(_under_scope(clip_tx, scopes.CLIP), tx)
        elif self.grad_clip_norm is not None:
            tx = optax.chain(_under_scope(optax.clip_by_global_norm(self.grad_clip_norm), scopes.CLIP), tx)
        lr_fn = schedule if schedule is not None else (lambda step: self.optimizer_spec.lr)

        init_routines = tuple(getattr(model.train_spec, "init_routines", ()))

        def init_state(r) -> AppState:
            params = _unbox(init_fn(r))
            # registered init routines (model_initialized variant) replace the default
            # initializers — runs inside the same jitted, sharded init
            for i, routine in enumerate(init_routines):
                params = routine.initialize_in_place(params, jax.random.fold_in(r, 1000 + i))
            return AppState(params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32))

        # the state's shapes and, where it is materialized, its jitted init: a span of
        # its own inside the caller's `init` (the init program's compile falls in it)
        with span("state_init"):
            if mesh_handle is not None:
                abstract_state = jax.eval_shape(init_state, rng)
                param_treedef = jax.tree.structure(abstract_state.params)
                opt_shardings = _substitute_param_subtrees(
                    abstract_state.opt_state,
                    param_treedef,
                    zero_grad_shardings if zero_active else param_shardings,
                    replicated_sharding,
                )
                state_shardings = AppState(
                    params=param_shardings, opt_state=opt_shardings, step=replicated_sharding
                )
                if materialize:
                    with mesh:
                        state = jax.jit(init_state, out_shardings=state_shardings)(rng)
                else:
                    state = abstract_state
            else:
                state_shardings = None
                if materialize:
                    state = jax.jit(init_state)(rng)
                else:
                    state = jax.eval_shape(init_state, rng)

        logger.info(
            "%s AppState: %d params",
            "initialized" if materialize else "abstract (compile-only)",
            sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params)),
        )

        # --- step functions
        loss_fn = self.loss_fn
        sample_key = model.sample_key
        acc_steps = self.gradient_acc_steps
        expose_grads = self.expose_grads
        skip_on_anomaly = self.anomaly_policy in ("skip_step", "rollback")
        stop_consensus = self.stop_consensus
        from modalities_tpu.resilience.coordination import BALLOT_KEY

        # fault baking (chaos tests): armed faults are resolved ONCE at build time
        # and compiled into the program as a step-predicated jnp.where — the
        # steady-state program with no faults armed is unchanged
        from modalities_tpu.resilience.faults import get_fault

        nan_grads_fault = get_fault("nan_grads")
        loss_spike_fault = get_fault("loss_spike")

        model_spec = getattr(model, "config_spec", None)
        head_chunk = getattr(model_spec, "lm_head_chunk_size", None) if model_spec else None
        chunked_loss = (
            head_chunk is not None
            and hasattr(model, "apply_hidden")
            and hasattr(loss_fn, "sum_and_count")
        )
        if head_chunk is not None and not chunked_loss:
            # silently materializing the [B,S,V] logits would be the exact memory
            # blowup the chunking exists to prevent — refuse loudly instead
            raise ValueError(
                f"lm_head_chunk_size={head_chunk} requires a model exposing "
                "apply_hidden/head_logits and a loss with the sum_and_count "
                f"accumulation form (got loss {type(loss_fn).__name__}); unset the "
                "chunk size or use a CLM-style loss"
            )

        # what the model counts in a pass beside its loss (an expert layer's routing: models/gpt2/moe.py; nothing, for
        # most models): an auxiliary output of the loss, summed over the microbatches on the device; the scalars are
        # published with the step's metrics, and all of it goes to `model.after_update` once the optimizer is done
        # a looped model with its exit gate hands the loss every walk's exit and gate, and only the loss over the
        # exits takes them: one without the other is a mistake of the config, said here and not in the first step
        trains_on_exits = bool(getattr(model, "trains_on_exits", False))
        if trains_on_exits != hasattr(loss_fn, "exit_loss") or (trains_on_exits and not chunked_loss):
            raise ValueError(
                "a model that walks its layers several times with an exit gate (loop_config.exit_gate) trains on the loss over "
                "all exits (loss variant looped_exit_loss), which reads the exits' hidden states (set lm_head_chunk_size); "
                f"got model.trains_on_exits={trains_on_exits}, loss {type(loss_fn).__name__}, lm_head_chunk_size={head_chunk}"
            )
        counted_shapes = dict(model.counted)
        if counted_shapes and (mesh_handle is not None and mesh_handle.degrees.get("dcn", 1) > 1):
            raise NotImplementedError("a model that counts in a step (expert layers) under a dcn mesh axis: the per-slice groups do not carry what it counts")

        def _with_layers_term(loss, counted):
            """The loss with the term the layers hand up beside their counters (an expert layer's balance term:
            `NNModel.loss_from_layers`), added inside the differentiated function; the loss as it is where there is none."""
            term = model.loss_from_layers(counted)
            return loss if term is None else loss + term

        if chunked_loss:
            # fused head + CE per sequence chunk: the [B,S,V] fp32 logits never
            # materialize (6.6 GB at 32k ctx x 50k vocab). Each chunk's projection
            # runs under jax.checkpoint so the backward recomputes chunk logits
            # instead of storing them; the mean is token-weighted like the
            # pipeline executor's, so ignore_index semantics are exact.
            target_key = loss_fn.target_key

            # Pallas fused CE (ops/cross_entropy.py): where kernels run (ops/tiers.py: on a TPU) and the
            # loss and model expose the fused path, the vocab dimension streams through VMEM and not
            # even the [B,chunk,V] buffer exists, whatever `head_chunk`'s value; elsewhere the chunked
            # scan below. Asked ONCE at build so the form is baked at trace time.
            from modalities_tpu.ops import tiers

            fused_ce = hasattr(loss_fn, "fused_sum_and_count") and hasattr(model, "head_weight") and tiers.kernels_run()

            chunk_sum_count = jax.checkpoint(
                lambda params, hc, lc: loss_fn.sum_and_count(model.head_logits(params, hc), lc),
                prevent_cse=False,
            )

            @jax.named_scope(scopes.HEAD_LOSS)
            def _chunked_ce(params, hidden, labels):
                if fused_ce:
                    total, count = loss_fn.fused_sum_and_count(hidden, model.head_weight(params), labels)
                    return total / jnp.maximum(count, 1.0)
                seq = hidden.shape[1]
                if seq > head_chunk:
                    # ragged tail: scan the divisible prefix, then one short chunk
                    # for the remainder — odd eval sequence lengths need no config
                    # change and the [B,S,V] logits still never materialize
                    num_chunks, tail = divmod(seq, head_chunk)

                    def body(acc, i):
                        hc = jax.lax.dynamic_slice_in_dim(hidden, i * head_chunk, head_chunk, 1)
                        lc = jax.lax.dynamic_slice_in_dim(labels, i * head_chunk, head_chunk, 1)
                        s, c = chunk_sum_count(params, hc, lc)
                        return (acc[0] + s, acc[1] + c), None

                    (total, count), _ = jax.lax.scan(
                        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                        jnp.arange(num_chunks),
                    )
                    if tail:
                        s, c = chunk_sum_count(
                            params,
                            jax.lax.slice_in_dim(hidden, num_chunks * head_chunk, seq, axis=1),
                            jax.lax.slice_in_dim(labels, num_chunks * head_chunk, seq, axis=1),
                        )
                        total, count = total + s, count + c
                else:  # short sequences: one chunk, same code path
                    total, count = loss_fn.sum_and_count(model.head_logits(params, hidden), labels)
                return total / jnp.maximum(count, 1.0)

            chunk_rows = jax.checkpoint(
                lambda params, hc, lc: loss_fn.row_losses(model.head_logits(params, hc), lc)[0], prevent_cse=False
            )

            def _row_ce(params, exits, labels):
                """Every exit's per-row cross entropy `[T, B, S]` from the exits `[T, B, S, E]` of a looped
                model, by the forms of `_chunked_ce`: `T x B x S` rows of ONE fused call against one head,
                else the chunked scan over `T x B` rows of the sequence, else the whole logits."""
                walks, batch, seq, width = exits.shape
                tiled = jnp.broadcast_to(labels[None], (walks, batch, seq))
                if fused_ce:
                    return loss_fn.fused_row_losses(exits, model.head_weight(params), tiled)
                flat, flat_labels = exits.reshape(walks * batch, seq, width), tiled.reshape(walks * batch, seq)
                if seq <= head_chunk:
                    return chunk_rows(params, flat, flat_labels).reshape(walks, batch, seq)
                num_chunks, tail = divmod(seq, head_chunk)
                whole = num_chunks * head_chunk
                chunks = lambda a: jnp.moveaxis(a[:, :whole].reshape(a.shape[0], num_chunks, head_chunk, *a.shape[2:]), 1, 0)  # noqa: E731
                rows = jax.lax.map(lambda c: chunk_rows(params, *c), (chunks(flat), chunks(flat_labels)))
                rows = jnp.moveaxis(rows, 0, 1).reshape(walks * batch, whole)
                if tail:
                    rows = jnp.concatenate([rows, chunk_rows(params, flat[:, whole:], flat_labels[:, whole:])], axis=1)
                return rows.reshape(walks, batch, seq)

            @jax.named_scope(scopes.HEAD_LOSS)
            def _exit_ce(params, out, labels):
                rows = _row_ce(params, out["exits"], labels)
                with jax.named_scope(scopes.EXIT_LOSS):
                    return loss_fn.exit_loss(rows, out["gate_logits"], labels, beta=model_spec.loop.beta)

            def compute_loss(params, samples, targets, dropout_rng):
                rngs = {"dropout": dropout_rng} if dropout_rng is not None else None
                hidden, counted = model.apply_counted(params, samples, train=True, rngs=rngs, hidden=True)
                if trains_on_exits:
                    loss, exit_counted = _exit_ce(params, hidden, targets[target_key])
                    return loss, {**counted, **exit_counted}
                return _with_layers_term(_chunked_ce(params, hidden, targets[target_key]), counted), counted

        else:

            def compute_loss(params, samples, targets, dropout_rng):
                rngs = {"dropout": dropout_rng} if dropout_rng is not None else None
                predictions, counted = model.apply_counted(params, samples, train=True, rngs=rngs)
                with jax.named_scope(scopes.HEAD_LOSS):
                    return _with_layers_term(loss_fn(predictions, targets), counted), counted

        # scheduled pipelining (1F1B): hand-rolled fwd/bwd with in-region loss replaces
        # value_and_grad through the in-module autodiff GPipe (the "gpipe" default)
        pp_scheduled = (
            mesh_handle is not None
            and mesh_handle.degrees.get("pp", 1) > 1
            and model_spec is not None
            and getattr(model_spec, "pp_schedule", "gpipe") != "gpipe"
            and hasattr(model, "pp_stage_fns")
        )
        if pp_scheduled:
            from modalities_tpu.parallel.pipeline_scheduled import (
                scheduled_pipeline_loss_and_grads,
            )

            pp_stage_fns = model.pp_stage_fns(loss_fn)
            target_key = loss_fn.target_key
            pp_mesh = mesh_handle.mesh
            model_dropout = getattr(model_spec, "dropout", 0.0)
            # ring attention composes with the scheduled executor: cp joins the
            # manual region, stage fns are cp-aware (global positions, psum'd loss)
            pp_seq_axis = "cp" if mesh_handle.degrees.get("cp", 1) > 1 else None

            def loss_and_grads(params, samples, targets, dropout_rng):
                stacked, shared = model.split_pp_params(params)
                loss, g_stacked, g_shared = scheduled_pipeline_loss_and_grads(
                    pp_stage_fns,
                    stacked,
                    shared,
                    samples[sample_key],
                    targets[target_key],
                    pp_mesh,
                    schedule=model_spec.pp_schedule,
                    num_microbatches=model_spec.pp_num_microbatches,
                    num_virtual=getattr(model_spec, "pp_num_virtual", 1),
                    rng=dropout_rng if model_dropout > 0.0 else None,
                    seq_shard_axis=pp_seq_axis,
                )
                return (loss, {}), model.merge_pp_grads(g_stacked, g_shared)  # the stage functions carry nothing counted

        else:

            def loss_and_grads(params, samples, targets, dropout_rng):
                return jax.value_and_grad(compute_loss, has_aux=True)(params, samples, targets, dropout_rng)

        kept_attention = KeptAttention()

        def plan_kept_attention(state: AppState, microbatch_shape: tuple) -> None:
            """Runs while the step is traced, before the model is: under `full` remat, what a block keeps beside its input,
            the flash kernel's o and lse and the gated delta rule's o and group states (`attention_keep_plan`: from the
            calls' shapes, this state's bytes a device and the device's limit; no key of the config). The answer goes onto
            the model's spec, which the blocks read as they are traced, into five gauges and one log line."""
            flash_calls = getattr(model, "remat_flash_calls", None)
            if flash_calls is None or len(microbatch_shape) != 2:
                return
            from modalities_tpu.telemetry import get_active_telemetry
            from modalities_tpu.telemetry.device_memory import min_bytes_limit
            from modalities_tpu.training.activation_checkpointing import KEEP_VERDICTS, attention_keep_plan
            from modalities_tpu.utils.recipe_validation import _tree_per_device_bytes as held

            plan = attention_keep_plan(
                flash_calls(*microbatch_shape),
                state_bytes=sum(held(getattr(state, part), getattr(state_shardings, part, None)) for part in ("params", "opt_state")),
                gradient_bytes=held(jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, reduce_dtype), state.params),
                                    zero_grad_shardings if zero_active else getattr(state_shardings, "params", None)),
                bytes_limit=min_bytes_limit(), first_rung=kept_attention.first_rung,
            )
            kept_attention.plan = plan
            model.with_spec_updates(remat_keep_flash=plan["keep"], remat_keep_rule=plan["keep_rule"])
            telemetry = get_active_telemetry()
            gauge = telemetry.metrics.gauge
            gauge("train_remat_kept_attention_layers", "Attention layers whose rematerialized block keeps the flash kernel's o and lse").set(plan["layers"] * plan["keep"])
            gauge("train_remat_kept_attention_bytes", "Bytes a device holds of kept o and lse over those layers").set(plan["kept_bytes"] * plan["keep"])
            gauge("train_remat_kept_rule_layers", "Layers of the gated delta rule whose rematerialized block keeps the rule's o and group states"
                  ).set(plan["rule_layers"] * plan["keep_rule"])
            gauge("train_remat_kept_rule_bytes", "Bytes a device holds of kept o and group states over those layers").set(plan["rule_kept_bytes"] * plan["keep_rule"])
            verdict = gauge("train_remat_keep_verdict", "1 under the attention keep plan's verdict, 0 under the others")
            for name in KEEP_VERDICTS:
                verdict.set(float(name == plan["verdict"]), verdict=name)
            telemetry.emit_event_once("attention_keep_plan", plan)
            logger.info("attention keep plan: %s", plan)

        def make_train_step(with_grads: bool):
            def train_step(state: AppState, batch: dict) -> tuple[AppState, dict]:
                """batch: {"samples": {k: [acc, mb, ...]}, "targets": {k: [acc, mb, ...]}}"""
                samples, targets = batch["samples"], batch["targets"]
                plan_kept_attention(state, samples[sample_key].shape[1:])
                # fresh dropout mask per step AND per microbatch, rooted at the build seed
                step_rng = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)

                def micro(acc, xs):
                    mb_index, s, t = xs
                    dropout_rng = jax.random.fold_in(step_rng, mb_index)
                    g_acc, l_acc, c_acc = acc  # c_acc: what the model counted, an empty dict for a model that counts nothing
                    if hierarchical_dcn:
                        # per-slice groups: each slice computes grads over its own
                        # batch rows; all in-model collectives stay intra-slice
                        # (spmd_axis_name prepends dcn to every internal constraint)
                        s, t = to_dcn_groups(s), to_dcn_groups(t)
                        group_rngs = jax.vmap(
                            lambda i: jax.random.fold_in(dropout_rng, i)
                        )(jnp.arange(dcn_degree))
                        (loss, _), grads = jax.vmap(
                            loss_and_grads,
                            in_axes=(None, 0, 0, 0),
                            spmd_axis_name="dcn",
                        )(state.params, s, t, group_rngs)
                        loss = jax.lax.with_sharding_constraint(loss, dcn_loss_sharding)
                    else:
                        (loss, counted), grads = loss_and_grads(state.params, s, t, dropout_rng)
                        # a largest value is the largest over the microbatches, everything else their mean
                        c_acc = {name: jnp.maximum(c_acc[name], counted[name]) if name.endswith("_max")
                                 else c_acc[name] + counted[name] / acc_steps for name in c_acc}
                    # accumulate in reduce_dtype (fp32 by default) even when grads are bf16
                    g_acc = jax.tree.map(lambda a, g: a + g.astype(reduce_dtype), g_acc, grads)
                    if hierarchical_dcn:
                        # per-group partial sums keep the dcn dim sharded in place —
                        # NO cross-slice reduction inside the microbatch loop
                        g_acc = jax.lax.with_sharding_constraint(g_acc, dcn_grad_shardings)
                        l_acc = jax.lax.with_sharding_constraint(
                            l_acc + loss, dcn_loss_sharding
                        )
                        return (g_acc, l_acc, c_acc), None
                    if zero_grad_shardings is not None:
                        # each microbatch's partial-sum grads reshard into the ZeRO
                        # layout here — this is the constraint GSPMD lowers to the
                        # reduce-scatter over dp_replicate (instead of the stage-0
                        # all-reduce that would replicate the full grads)
                        g_acc = jax.lax.with_sharding_constraint(g_acc, zero_grad_shardings)
                    return (g_acc, l_acc + loss, c_acc), None

                # the whole microbatch loop sits under one scope: what autodiff marks inside it
                # (jvp, transpose) reads as a pass, the rest (the zero accumulator, the loop's own
                # slicing, sum and cast, the division) as gradient accumulation
                with jax.named_scope(scopes.GRAD_ACCUMULATE):
                    if hierarchical_dcn:
                        zero_grads = jax.tree.map(
                            lambda p: jnp.zeros((dcn_degree, *p.shape), reduce_dtype), state.params
                        )
                        zero_grads = jax.lax.with_sharding_constraint(zero_grads, dcn_grad_shardings)
                        loss_init = jax.lax.with_sharding_constraint(
                            jnp.zeros((dcn_degree,), jnp.float32), dcn_loss_sharding
                        )
                    else:
                        zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, reduce_dtype), state.params)
                        if zero_grad_shardings is not None:
                            zero_grads = jax.lax.with_sharding_constraint(zero_grads, zero_grad_shardings)
                        loss_init = 0.0
                    zero_counters = {name: jnp.zeros(shape, jnp.float32) for name, shape in counted_shapes.items()}
                    (grads, loss_sum, step_counted), _ = jax.lax.scan(
                        micro, (zero_grads, loss_init, zero_counters), (jnp.arange(acc_steps), samples, targets)
                    )
                    if hierarchical_dcn:
                        # THE hierarchical-reduction crossing point: the mean over the
                        # dcn group dim reduces the fully-accumulated grads across
                        # slices once per optimizer step, outside the scan body
                        grads = jax.tree.map(
                            lambda g, p: (g.mean(axis=0) / acc_steps).astype(p.dtype),
                            grads,
                            state.params,
                        )
                        grads = jax.lax.with_sharding_constraint(
                            grads, zero_grad_shardings if zero_grad_shardings is not None else param_shardings
                        )
                        loss = loss_sum.mean() / acc_steps
                    else:
                        grads = jax.tree.map(lambda g, p: (g / acc_steps).astype(p.dtype), grads, state.params)
                        loss = loss_sum / acc_steps

                if nan_grads_fault is not None:
                    poison = (
                        state.step == nan_grads_fault.step
                        if nan_grads_fault.step is not None
                        else jnp.asarray(True)
                    )
                    grads = jax.tree.map(
                        lambda g: g * jnp.where(poison, jnp.nan, 1.0).astype(g.dtype), grads
                    )
                if loss_spike_fault is not None:
                    spike = (
                        state.step == loss_spike_fault.step
                        if loss_spike_fault.step is not None
                        else jnp.asarray(True)
                    )
                    loss = loss + jnp.where(spike, float(loss_spike_fault.arg or 1e3), 0.0)

                with jax.named_scope(scopes.GRAD_NORM):
                    grad_norm = global_norm_by_mode(grads, norm_mode)
                with jax.named_scope(scopes.OPTIMIZER):
                    updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
                with jax.named_scope(scopes.APPLY_UPDATES):
                    new_params = model.after_update(optax.apply_updates(state.params, updates), step_counted)
                    if zero_grad_shardings is not None and param_shardings is not None:
                        # re-materialize full (dp_replicate-replicated) params: the one
                        # all-gather paired with the reduce-scatter above
                        new_params = jax.lax.with_sharding_constraint(new_params, param_shardings)
                if skip_on_anomaly:
                    # branch-free anomaly skip: a non-finite step keeps the old
                    # params/opt_state (jnp.where select, no lax.cond divergence
                    # across ranks) while the step counter still advances — so the
                    # data stream and sampler position stay aligned with a run that
                    # consumed the batch normally
                    with jax.named_scope(scopes.ANOMALY_SELECT):
                        ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
                        new_params = jax.tree.map(
                            lambda new, old: jnp.where(ok, new, old), new_params, state.params
                        )
                        new_opt_state = jax.tree.map(
                            lambda new, old: jnp.where(ok, new, old), new_opt_state, state.opt_state
                        )
                with jax.named_scope(scopes.STEP_METRICS):
                    new_state = AppState(params=new_params, opt_state=new_opt_state, step=state.step + 1)
                    metrics = {
                        "loss": loss,
                        "grad_norm": grad_norm,
                        "lr": jnp.asarray(lr_fn(state.step), jnp.float32),
                    }
                    for name, shape in counted_shapes.items():
                        if shape == ():
                            metrics[COUNTER_PREFIX + name] = step_counted[name]
                    if skip_on_anomaly:
                        metrics["skipped_step"] = (~ok).astype(jnp.int32)
                    if error_if_nonfinite:
                        # consumed by Trainer at the next host sync (async equivalent of
                        # torch clip_grad_norm_(error_if_nonfinite=True) raising inline)
                        metrics["nonfinite_grads"] = (~jnp.isfinite(grad_norm)).astype(jnp.int32)
                    if with_grads:
                        # debugging_enriched path: Trainer feeds these to DebugStatsLogger
                        metrics["grads"] = grads
                    if stop_consensus:
                        # the ONE consensus collective: max over every device's
                        # locally-cast vote. The replicated scalar result is read
                        # identically by all processes, so they exit the loop at the
                        # same step boundary (resilience/coordination.py).
                        metrics[BALLOT_KEY] = jnp.max(batch[BALLOT_KEY])
                return new_state, metrics

            return train_step

        train_step = make_train_step(False)

        if chunked_loss:

            def eval_loss(params, samples, targets):
                hidden = model.apply_hidden(params, samples, train=False)
                return _chunked_ce(params, hidden, targets[loss_fn.target_key])

        else:

            def eval_loss(params, samples, targets):
                predictions = model.apply(params, samples, train=False)
                with jax.named_scope(scopes.HEAD_LOSS):
                    return loss_fn(predictions, targets)

        if hierarchical_dcn:
            # same per-slice grouping as the train path: eval activations stay
            # intra-slice and only the final scalar mean crosses DCN
            def eval_step(state: AppState, batch: dict) -> dict:
                samples = to_dcn_groups(batch["samples"])
                targets = to_dcn_groups(batch["targets"])
                losses = jax.vmap(
                    eval_loss, in_axes=(None, 0, 0), spmd_axis_name="dcn"
                )(state.params, samples, targets)
                return {"loss": losses.mean()}

        else:

            def eval_step(state: AppState, batch: dict) -> dict:
                return {"loss": eval_loss(state.params, batch["samples"], batch["targets"])}

        if mesh_handle is not None:
            mesh = mesh_handle.mesh
            from modalities_tpu.parallel.sharding import activation_rules

            rules = self.rules
            metrics_shardings: dict = {
                "loss": replicated_sharding,
                "grad_norm": replicated_sharding,
                "lr": replicated_sharding,
            }
            for name, shape in counted_shapes.items():
                if shape == ():
                    metrics_shardings[COUNTER_PREFIX + name] = replicated_sharding
            if skip_on_anomaly:
                metrics_shardings["skipped_step"] = replicated_sharding
            if error_if_nonfinite:
                metrics_shardings["nonfinite_grads"] = replicated_sharding
            if stop_consensus:
                metrics_shardings[BALLOT_KEY] = replicated_sharding
            train_step_j = jax.jit(
                train_step,
                donate_argnums=(0,),
                in_shardings=(state_shardings, None),
                out_shardings=(state_shardings, metrics_shardings),
            )
            eval_step_j = jax.jit(eval_step, in_shardings=(state_shardings, None))
            kept_attention.jitted.append(train_step_j)

            # execute (and trace) under the mesh context so in-model collectives
            # (ring attention shard_map) resolve the ambient mesh, and under the
            # flax logical-axis rules so in-model with_sharding_constraint hints
            # (activation/SP shardings) lower to real mesh constraints
            def train_step_c(state, batch):
                with mesh, activation_rules(rules, mesh):
                    return train_step_j(state, batch)

            def eval_step_c(state, batch):
                with mesh, activation_rules(rules, mesh):
                    return eval_step_j(state, batch)

            def lower_train_step(batch_abstract):
                # `state` is the abstract tree in materialize=False mode and the real
                # one otherwise; jit.lower accepts either
                with mesh, activation_rules(rules, mesh):
                    return train_step_j.lower(state, batch_abstract)

            train_step_debug_c = None
            if expose_grads:
                debug_metrics_shardings = dict(
                    metrics_shardings,
                    grads=zero_grad_shardings if zero_active else param_shardings,
                )
                train_step_debug_j = jax.jit(
                    make_train_step(True),
                    donate_argnums=(0,),
                    in_shardings=(state_shardings, None),
                    out_shardings=(state_shardings, debug_metrics_shardings),
                )
                kept_attention.jitted.append(train_step_debug_j)

                def train_step_debug_c(state, batch):
                    with mesh, activation_rules(rules, mesh):
                        return train_step_debug_j(state, batch)

        else:
            train_step_c = jax.jit(train_step, donate_argnums=(0,))
            eval_step_c = jax.jit(eval_step)
            train_step_debug_c = (
                jax.jit(make_train_step(True), donate_argnums=(0,)) if expose_grads else None
            )
            lower_train_step = lambda batch_abstract: train_step_c.lower(state, batch_abstract)  # noqa: E731
            kept_attention.jitted.extend(step for step in (train_step_c, train_step_debug_c) if step is not None)

        put_batch = self._make_put_batch(data_sharding)

        handle = AppStateHandle(state, state_shardings, tx, lr_fn, model)
        return StepFunctions(
            train_step=train_step_c,
            eval_step=eval_step_c,
            put_batch=put_batch,
            app_state_handle=handle,
            mesh_handle=mesh_handle,
            train_step_debug=train_step_debug_c,
            lower_train_step=lower_train_step,
            zero_stage=self.zero_stage,
            gradient_acc_steps=self.gradient_acc_steps,
            kept_attention=kept_attention,
        )

    # ------------------------------------------------------------------ data
    def _make_put_batch(self, data_sharding):
        """Host numpy batch -> global sharded device arrays.

        Single-process: device_put with the batch sharding. Multi-host: each process
        contributes the rows its devices own (jax.make_array_from_process_local_data).

        `has_acc_dim` is explicit because it cannot be inferred from ndim: the Trainer
        always stacks a leading gradient-accumulation dim (trainer.py), the Evaluator
        and eval-profiler never do — and multimodal leaves (images [.., H, W, C]) make
        ndim ambiguous. Only the KNOWN token leaves (the model's sample key and the
        loss's target key) take the cp axis on their sequence dim; every other leaf
        keeps all trailing dims unsharded.
        """
        seq_sharded_keys = {
            k
            for k in (
                getattr(self.model, "sample_key", None),
                getattr(self.loss_fn, "target_key", None),
            )
            if k is not None
        }

        if data_sharding is None:

            def put_plain(batch_dict: dict, has_acc_dim: bool = True) -> dict:
                return jax.tree.map(jnp.asarray, batch_dict)

            return put_plain

        import jax.sharding as js

        spec = tuple(data_sharding.spec)
        batch_axes = spec[0]
        seq_axis = spec[1] if len(spec) > 1 else None

        # Both caches live OUTSIDE the per-call path and persist for the life of
        # the returned closure: steady-state training sees the same (leaf key,
        # shape, dtype, acc-dim) signatures every step, so the per-leaf
        # NamedSharding construction and the O(global devices)
        # devices_indices_map walk happen once per signature, not once per step.
        _seq_slice_cache: dict[int, slice] = {}
        _leaf_sharding_cache: dict[tuple, tuple] = {}

        def local_seq_slice(seq_len: int) -> slice:
            """This process's slice of a cp-sharded sequence dim. The loader
            always yields FULL sequences, but make_array_from_process_local_data
            treats local data as the per-process portion along dims whose
            sharding spans processes and INFERS the global extent from it —
            feeding the full sequence there silently builds a double-length
            global sequence of duplicated tokens (caught by the 2-process cp
            ring test). So when cp spans processes, slice first."""
            if seq_len in _seq_slice_cache:
                return _seq_slice_cache[seq_len]
            seq_sh = js.NamedSharding(data_sharding.mesh, js.PartitionSpec(seq_axis))
            spans = sorted(
                {
                    idx[0].indices(seq_len)[:2]
                    for dev, idx in seq_sh.devices_indices_map((seq_len,)).items()
                    if dev.process_index == jax.process_index()
                }
            )
            lo, hi = spans[0][0], spans[-1][1]
            covered = 0
            for s, e in spans:
                covered += e - s
            if covered != hi - lo:
                raise NotImplementedError(
                    f"this process's cp shards of the sequence are non-contiguous "
                    f"({spans}): the per-host feeding path needs one contiguous "
                    "block per process — reorder the mesh so cp is innermost "
                    "within each host"
                )
            _seq_slice_cache[seq_len] = slice(lo, hi)
            return _seq_slice_cache[seq_len]

        def leaf_sharding(leaf_key, shape: tuple, dtype, has_acc_dim: bool) -> tuple:
            """(NamedSharding, seq_sharded) for one leaf signature, cached."""
            sig = (leaf_key, shape, dtype, has_acc_dim)
            cached = _leaf_sharding_cache.get(sig)
            if cached is not None:
                return cached
            lead = (None,) if has_acc_dim else ()
            data_dims = len(shape) - len(lead) - 1  # dims after the batch dim
            tail = [None] * data_dims
            seq_sharded = leaf_key in seq_sharded_keys and data_dims == 1
            if seq_sharded:
                tail[0] = seq_axis  # tokens [.., batch, seq]: seq shards over cp
            full = js.NamedSharding(
                data_sharding.mesh, js.PartitionSpec(*lead, batch_axes, *tail)
            )
            _leaf_sharding_cache[sig] = (full, seq_sharded)
            return full, seq_sharded

        def put(batch_dict: dict, has_acc_dim: bool = True) -> dict:
            def put_leaf(path, x):
                x = np.asarray(x)
                leaf_key = getattr(path[-1], "key", None) if path else None
                full, seq_sharded = leaf_sharding(leaf_key, x.shape, x.dtype.str, has_acc_dim)
                if jax.process_count() == 1:
                    return jax.device_put(x, full)
                if seq_sharded and seq_axis is not None:
                    x = x[..., local_seq_slice(x.shape[-1])]
                return jax.make_array_from_process_local_data(full, x)

            return jax.tree_util.tree_map_with_path(put_leaf, batch_dict)

        return put
